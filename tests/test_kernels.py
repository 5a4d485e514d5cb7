"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.epitome import EpitomeSpec, reconstruct
from repro.kernels import ops
from repro.kernels.ref import (
    epitome_matmul_blocks_ref, quant_matmul_ref, wkv6_ref,
)

KEY = jax.random.PRNGKey(0)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)


class TestEpitomeMatmul:
    @pytest.mark.parametrize("M,N,m,n,bm,bn", [
        (512, 512, 256, 256, 128, 256),     # wrap: all col blocks identical
        (512, 256, 256, 256, 128, 256),
        (1024, 1024, 512, 512, 128, 256),   # mixed offsets
        (256, 768, 128, 256, 128, 256),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_vs_block_oracle(self, M, N, m, n, bm, bn, dtype):
        spec = EpitomeSpec(M=M, N=N, m=m, n=n, bm=bm, bn=bn)
        E = jax.random.normal(KEY, (m, n), dtype)
        x = jax.random.normal(jax.random.PRNGKey(1), (32, M), dtype)
        y = ops.epitome_matmul(x, E, spec, interpret=True)
        ref = epitome_matmul_blocks_ref(
            ops.fold_rows(x, spec), E, ops.kernel_col_blocks(spec), bn)
        np.testing.assert_allclose(
            np.asarray(y, np.float32), np.asarray(ref[:, :N], np.float32),
            **tol(dtype))

    def test_aligned_case_matches_reconstruction(self):
        """When col offsets are block-aligned, the kernel equals the exact
        epitome matmul (x @ W(E))."""
        spec = EpitomeSpec(M=512, N=512, m=256, n=256, bm=128, bn=256)
        E = jax.random.normal(KEY, (spec.m, spec.n))
        x = jax.random.normal(jax.random.PRNGKey(1), (64, spec.M))
        y = ops.epitome_matmul(x, E, spec, interpret=True)
        np.testing.assert_allclose(y, x @ reconstruct(E, spec),
                                   rtol=1e-4, atol=1e-4)

    def test_ragged_rows(self):
        spec = EpitomeSpec(M=640, N=512, m=256, n=256, bm=128, bn=256)
        E = jax.random.normal(KEY, (spec.m, spec.n))
        x = jax.random.normal(KEY, (10, spec.M))      # T not block aligned
        y = ops.epitome_matmul(x, E, spec, interpret=True)
        assert y.shape == (10, spec.N)
        assert bool(jnp.all(jnp.isfinite(y)))

    def test_batched_leading_dims(self):
        spec = EpitomeSpec(M=256, N=256, m=128, n=256, bm=128, bn=256)
        E = jax.random.normal(KEY, (spec.m, spec.n))
        x = jax.random.normal(KEY, (2, 3, spec.M))
        y = ops.epitome_matmul(x, E, spec, interpret=True)
        assert y.shape == (2, 3, spec.N)


class TestWKV6:
    @pytest.mark.parametrize("B,S,H,K,chunk", [
        (1, 16, 1, 8, 8),
        (2, 64, 2, 16, 16),
        (2, 50, 2, 8, 16),       # ragged sequence vs chunk
        (1, 128, 4, 32, 64),
    ])
    def test_vs_naive(self, B, S, H, K, chunk):
        ks = jax.random.split(KEY, 5)
        r = jax.random.normal(ks[0], (B, S, H, K))
        k = jax.random.normal(ks[1], (B, S, H, K))
        v = jax.random.normal(ks[2], (B, S, H, K))
        lw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, K)) * 0.5)
        u = jax.random.normal(ks[4], (H, K)) * 0.1
        o = ops.wkv6(r, k, v, lw, u, chunk=chunk, interpret=True)
        to_bh = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, K)
        ref = wkv6_ref(to_bh(r), to_bh(k), to_bh(v), to_bh(lw),
                       jnp.tile(u, (B, 1)))
        ref = ref.reshape(B, H, S, K).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(o, ref, rtol=1e-3, atol=1e-3)

    def test_strong_decay_stable(self):
        """Aggressive decays (log w ~ -20) must not produce inf/nan."""
        B, S, H, K = 1, 32, 1, 8
        r = jax.random.normal(KEY, (B, S, H, K))
        k = jax.random.normal(KEY, (B, S, H, K))
        v = jax.random.normal(KEY, (B, S, H, K))
        lw = jnp.full((B, S, H, K), -20.0)
        u = jnp.zeros((H, K))
        o = ops.wkv6(r, k, v, lw, u, chunk=16, interpret=True)
        assert bool(jnp.all(jnp.isfinite(o)))


class TestQuantMatmul:
    @pytest.mark.parametrize("T,M,N", [(8, 256, 256), (32, 512, 768),
                                       (7, 512, 256)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_vs_ref(self, T, M, N, dtype):
        ks = jax.random.split(KEY, 4)
        q = jax.random.randint(ks[0], (M, N), -127, 128).astype(jnp.int8)
        s = jax.random.uniform(ks[1], (M // 256, N // 256), minval=1e-3,
                               maxval=1e-2)
        z = jnp.round(jax.random.uniform(ks[2], (M // 256, N // 256),
                                         minval=-3, maxval=3))
        x = jax.random.normal(ks[3], (T, M), dtype)
        y = ops.quant_matmul(x, q, s, z, interpret=True)
        ref = quant_matmul_ref(x, q, s, z)
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(ref, np.float32), **tol(dtype))

    def test_matches_epitome_aware_quantizer(self):
        """End-to-end: quantize an epitome with per-crossbar scales, run the
        kernel, compare against the dequantized dense matmul."""
        from repro.core.quant import QuantConfig, quantize_epitome, dequantize
        spec = EpitomeSpec(M=512, N=512, m=512, n=512, bm=128, bn=256)
        E = jax.random.normal(KEY, (512, 512))
        cfg = QuantConfig(bits=8, per_crossbar=True, overlap_weighted=False,
                          tile=256)
        qfull, S, Z = quantize_epitome(E, spec, cfg)
        # shift the unsigned 8-bit codes into int8 range; fold the shift
        # into the per-tile zero point: (q-128 + (z+128)) * s == (q+z) * s
        s_t = S[::256, ::256]
        z_t = Z[::256, ::256] + 128.0
        q_i8 = (qfull - 128.0).astype(jnp.int8)
        x = jax.random.normal(KEY, (16, 512))
        y = ops.quant_matmul(x, q_i8, s_t, z_t, interpret=True)
        ref = x @ dequantize(qfull, S, Z)
        np.testing.assert_allclose(y, ref, rtol=1e-3, atol=1e-3)


# epitome designs whose col-block table is exact (bn-aligned offsets):
# aligned — two distinct column blocks at offsets [0, 256]
# wrapped — n == bn, every output block reuses epitome block 0 (cb=[0,0,0])
ALIGNED = dict(M=512, N=512, m=256, n=512, bm=128, bn=256)
WRAPPED = dict(M=512, N=768, m=256, n=256, bm=128, bn=256)


class TestQuantEpitomeMatmul:
    @pytest.mark.parametrize("bits", [8, 4, 3])
    @pytest.mark.parametrize("spec_kw", [ALIGNED, WRAPPED],
                             ids=["aligned", "wrapped"])
    def test_parity_vs_fake_quant_reconstruct(self, bits, spec_kw):
        """Fused kernel == x @ reconstruct(fake_quant(E)) within quantization
        tolerance (the acceptance contract).  The packed per-block (s, z)
        nest inside the quantizer's crossbar tiles, so codes are identical
        and the residual is pure fp accumulation error."""
        from repro.core.quant import QuantConfig, fake_quant
        spec = EpitomeSpec(**spec_kw)
        E = jax.random.normal(KEY, (spec.m, spec.n))
        x = jax.random.normal(jax.random.PRNGKey(1), (16, spec.M))
        cfg = QuantConfig(bits=bits)
        y = ops.quant_epitome_matmul(x, E, spec, cfg, interpret=True)
        ref = x @ reconstruct(fake_quant(E, spec, cfg), spec)
        tol = 1e-3 * max(1.0, float(jnp.abs(ref).max()))
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-3, atol=tol)

    @pytest.mark.parametrize("M,N,m,n,bm,bn", [
        (512, 512, 256, 512, 128, 256),
        (512, 768, 256, 256, 128, 256),
        (1024, 1024, 512, 512, 128, 256),   # non-aligned offsets (snapped)
    ])
    def test_vs_block_oracle(self, M, N, m, n, bm, bn):
        """Exact agreement with the jnp oracle on the kernel's own contract
        (col-block table + per-block dequant), incl. snapped offsets."""
        from repro.core.quant import QuantConfig
        from repro.kernels.ref import quant_epitome_matmul_blocks_ref
        spec = EpitomeSpec(M=M, N=N, m=m, n=n, bm=bm, bn=bn)
        E = jax.random.normal(KEY, (m, n))
        x = jax.random.normal(jax.random.PRNGKey(1), (32, M))
        p = ops.pack_epitome(E, spec, QuantConfig(bits=4))
        y = ops.quant_epitome_matmul(x, None, spec, packed=p, interpret=True)
        ref = quant_epitome_matmul_blocks_ref(
            ops.fold_rows(x, spec), p.q, p.scales, p.zeros,
            ops.kernel_col_blocks(spec), p.bk, p.bn)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref[:, :N]),
                                   rtol=1e-4, atol=1e-4)

    def test_prepacked_matches_on_the_fly(self):
        from repro.core.quant import QuantConfig
        spec = EpitomeSpec(**WRAPPED)
        E = jax.random.normal(KEY, (spec.m, spec.n))
        x = jax.random.normal(jax.random.PRNGKey(2), (8, spec.M))
        cfg = QuantConfig(bits=3)
        y1 = ops.quant_epitome_matmul(x, E, spec, cfg, interpret=True)
        y2 = ops.quant_epitome_matmul(x, None, spec,
                                      packed=ops.pack_epitome(E, spec, cfg),
                                      interpret=True)
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))

    def test_batched_and_ragged_T(self):
        from repro.core.quant import QuantConfig
        spec = EpitomeSpec(**ALIGNED)
        E = jax.random.normal(KEY, (spec.m, spec.n))
        x = jax.random.normal(KEY, (2, 5, spec.M))     # T = 10, non-pow2
        y = ops.quant_epitome_matmul(x, E, spec, QuantConfig(bits=8),
                                     interpret=True)
        assert y.shape == (2, 5, spec.N)
        assert bool(jnp.all(jnp.isfinite(y)))

    def test_layer_prepack_and_effective_weight(self):
        """prepack_linear feeds the kernel stored int8 (same result, no
        re-quantize), and effective_weight mirrors the fused path's packed
        quantization exactly on an aligned spec."""
        from repro.core.layers import (EpLayerConfig, apply_linear,
                                       effective_weight, init_linear,
                                       prepack_linear)
        from repro.core.quant import QuantConfig
        spec = EpitomeSpec(**ALIGNED)
        cfg = EpLayerConfig(spec=spec, mode="kernel", quant=QuantConfig(bits=4))
        params = init_linear(KEY, spec.M, spec.N, cfg)
        x = jax.random.normal(jax.random.PRNGKey(5), (16, spec.M))
        y = apply_linear(params, x, cfg)
        pp = prepack_linear(params, cfg)
        assert pp["Eq"].dtype == jnp.int8
        np.testing.assert_array_equal(np.asarray(apply_linear(pp, x, cfg)),
                                      np.asarray(y))
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(x @ effective_weight(params, cfg)),
            rtol=1e-4, atol=1e-4)

    def test_symmetric_quant(self):
        from repro.core.quant import QuantConfig, fake_quant
        spec = EpitomeSpec(**WRAPPED)
        E = jax.random.normal(KEY, (spec.m, spec.n))
        x = jax.random.normal(jax.random.PRNGKey(3), (16, spec.M))
        cfg = QuantConfig(bits=8, symmetric=True)
        y = ops.quant_epitome_matmul(x, E, spec, cfg, interpret=True)
        ref = x @ reconstruct(fake_quant(E, spec, cfg), spec)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)


class TestPickBkAndOverrides:
    """The contraction-dim twin of the bt cliff: prime/odd epitome row
    counts m must not collapse the k grid to a single m-sized block —
    callers zero-pad the folded activation (dot-neutral) up to a block
    multiple instead — plus the explicit bt override and the fused-fold
    kernel variant."""

    PRIME_M = dict(M=512, N=512, m=251, n=256, bm=128, bn=256)

    def test_pick_bk_no_degenerate_blocks(self):
        """Every bk is Mosaic-legal on the lane dim — a multiple of 128 or
        the whole m — and pads m by less than one block."""
        for m in (8, 16, 97, 193, 251, 256, 1000, 1024, 1100):
            bk = ops._pick_bk(m)
            assert bk == m or bk % 128 == 0, (m, bk)
            assert 8 <= bk <= max(m, 8), (m, bk)
            assert (-m) % bk < bk
        assert ops._pick_bk(1024) == 512         # exact divisor preferred
        assert ops._pick_bk(251) == 251          # fits: one whole block
        assert ops._pick_bk(1100) == 128         # least padding: 1152 rows
        assert ops._pick_bk(1000) == 512         # tie on 1024 rows: larger

    def test_pick_bk_quant_respects_tile(self):
        assert ops._pick_bk_quant(1024, 256) == 256
        assert ops._pick_bk_quant(251, 256) == 251   # prime m under the tile
        assert ops._pick_bk_quant(288, 256) == 128   # ResNet-50 3x3 conv
        assert ops._pick_bk_quant(576, 256) == 128   # pads to 640, not 768
        assert ops._pick_bk_quant(2000, 256) == 256  # both pad to 2048
        assert ops._pick_bk_quant(97, 64) == 64      # capped by tile
        assert ops._pick_bk_quant(4, 256) == 4       # tiny m: whole dim

    def test_prime_m_fp_kernel(self):
        spec = EpitomeSpec(**self.PRIME_M)
        E = jax.random.normal(KEY, (spec.m, spec.n))
        x = jax.random.normal(jax.random.PRNGKey(1), (16, spec.M))
        y = ops.epitome_matmul(x, E, spec, interpret=True)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(x @ reconstruct(E, spec)),
                                   rtol=1e-4, atol=1e-4)

    def test_prime_m_quant_kernel(self):
        from repro.core.quant import QuantConfig, fake_quant
        spec = EpitomeSpec(**self.PRIME_M)
        E = jax.random.normal(KEY, (spec.m, spec.n))
        x = jax.random.normal(jax.random.PRNGKey(2), (16, spec.M))
        cfg = QuantConfig(bits=8)
        p = ops.pack_epitome(E, spec, cfg)
        assert p.bk >= 8 and p.q.shape[0] == spec.m
        y = ops.quant_epitome_matmul(x, None, spec, packed=p, interpret=True)
        ref = x @ reconstruct(fake_quant(E, spec, cfg), spec)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)

    def test_bt_override_bit_identical(self):
        """Row-block choice never reassociates the contraction, so an
        explicit bt (the engine's fixed decode batch) is bit-identical to
        the heuristic pick."""
        from repro.core.quant import QuantConfig
        spec = EpitomeSpec(**ALIGNED)
        E = jax.random.normal(KEY, (spec.m, spec.n))
        x = jax.random.normal(jax.random.PRNGKey(3), (8, spec.M))
        p = ops.pack_epitome(E, spec, QuantConfig(bits=3))
        y0 = ops.quant_epitome_matmul(x, None, spec, packed=p, interpret=True)
        for bt in (8, 16, 64):
            y = ops.quant_epitome_matmul(x, None, spec, packed=p, bt=bt,
                                         interpret=True)
            np.testing.assert_array_equal(np.asarray(y0), np.asarray(y))

    @pytest.mark.parametrize("spec_kw", [ALIGNED, WRAPPED, PRIME_M],
                             ids=["aligned", "wrapped", "prime-m"])
    def test_fused_fold_bit_identical(self, spec_kw):
        """The fused-fold variant (fold inside the kernel, VMEM-resident)
        accumulates row blocks in ascending order exactly like
        fold_rows' segment_sum, so its output is bit-identical to the
        standard kernel's."""
        from repro.core.quant import QuantConfig
        spec = EpitomeSpec(**spec_kw)
        E = jax.random.normal(KEY, (spec.m, spec.n))
        x = jax.random.normal(jax.random.PRNGKey(4), (8, spec.M))
        p = ops.pack_epitome(E, spec, QuantConfig(bits=3))
        y0 = ops.quant_epitome_matmul(x, None, spec, packed=p, interpret=True)
        y1 = ops.quant_epitome_matmul(x, None, spec, packed=p,
                                      fused_fold=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))

    def test_pack_blocks_explicit_and_splittable(self):
        from repro.core.quant import QuantConfig
        spec = EpitomeSpec(**ALIGNED)
        q = QuantConfig(bits=3)
        assert ops.pack_blocks(spec, q) == (256, 256)
        assert ops.pack_blocks(spec, q, (8, 128, 256)) == (128, 256)
        assert ops.col_blocks_splittable(spec, 128)
        sub = ops.kernel_col_blocks(spec, 128)
        assert sub.shape[0] == 2 * ops.kernel_col_blocks(spec).shape[0]


class TestPickBt:
    def test_exact_divisor_preferred(self):
        """When a block divides T, no padding is needed and the largest
        such block wins."""
        assert ops._pick_bt(512) == 256
        assert ops._pick_bt(96) == 32
        assert ops._pick_bt(1024) == 256

    def test_no_degenerate_grids(self):
        """The performance cliff: prime/odd T must NOT collapse the grid to
        bt=1 row blocks — callers pad up to a block multiple instead."""
        for T in (1, 2, 3, 5, 7, 97, 193, 196, 1000):
            bt = ops._pick_bt(T)
            assert bt >= 8, (T, bt)
            # padding waste is bounded by one block
            assert (-T) % bt < bt

    def test_resnet_conv_row_counts(self):
        """N*H'*W' row counts of the paper's ResNet convs (batch 1 and 4)
        all get real row blocks."""
        for hw in (112, 56, 28, 14, 7):
            for batch in (1, 4):
                T = batch * hw * hw
                assert ops._pick_bt(T) >= 8, (T, ops._pick_bt(T))

    def test_pad_rows_shape_and_trim(self):
        x = jnp.ones((196, 16))
        xp, bt = ops._pad_rows(x)
        assert xp.shape[0] % bt == 0 and xp.shape[0] >= 196
        assert bool(jnp.all(xp[196:] == 0.0))      # zero rows, trimmed later

    @pytest.mark.parametrize("T", [12, 96, 97, 196])
    def test_non_divisible_T_both_kernel_paths(self, T):
        """Odd/prime row counts go through both the fp and the quantized
        epitome kernels padded, with the output trimmed back to T rows and
        no padding artifacts."""
        from repro.core.quant import QuantConfig
        spec = EpitomeSpec(**ALIGNED)
        E = jax.random.normal(KEY, (spec.m, spec.n))
        x = jax.random.normal(jax.random.PRNGKey(4), (T, spec.M))
        y_fp = ops.epitome_matmul(x, E, spec, interpret=True)
        np.testing.assert_allclose(np.asarray(y_fp),
                                   np.asarray(x @ reconstruct(E, spec)),
                                   rtol=1e-4, atol=1e-4)
        p = ops.pack_epitome(E, spec, QuantConfig(bits=8))
        y_q = ops.quant_epitome_matmul(x, None, spec, packed=p,
                                       interpret=True)
        assert y_q.shape == (T, spec.N)
        assert bool(jnp.all(jnp.isfinite(y_q)))


class TestSmemTablesAndLaneBlocks:
    """The int8 kernels read their per-block (scale, zero) tables whole
    from SMEM, indexed [k, cb[j]], and take lane-legal contraction blocks
    (a multiple of 128 or the whole m) — checked against the ref.py
    oracles in interpret mode."""

    @staticmethod
    def _tables(gk, gn, seed=0):
        # a distinct (s, z) per block, so any mis-indexed read shows up
        ks = jax.random.split(jax.random.PRNGKey(seed), 2)
        s = jax.random.uniform(ks[0], (gk, gn), minval=1e-3, maxval=1e-2)
        z = jnp.round(jax.random.uniform(ks[1], (gk, gn), minval=-4,
                                         maxval=4))
        return s, z

    @pytest.mark.parametrize("cb", [[0, 1, 2], [2, 0, 2, 1], [1, 1]])
    def test_blocks_kernel_vs_oracle(self, cb):
        from repro.kernels.quant_epitome_matmul import \
            quant_epitome_matmul_blocks
        from repro.kernels.ref import quant_epitome_matmul_blocks_ref
        m, n, bk, bn = 384, 384, 128, 128
        q = jax.random.randint(KEY, (m, n), -4, 4).astype(jnp.int8)
        s, z = self._tables(m // bk, n // bn)
        x = jax.random.normal(jax.random.PRNGKey(1), (16, m))
        y = quant_epitome_matmul_blocks(x, q, s, z, np.array(cb), bt=8,
                                        bk=bk, bn=bn, interpret=True)
        ref = quant_epitome_matmul_blocks_ref(x, q, s, z, np.array(cb),
                                              bk, bn)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_fused_fold_kernel_vs_oracle(self):
        """The fused-fold variant reads the same SMEM tables; its output
        agrees with fold_rows + the block oracle."""
        from repro.kernels.quant_epitome_matmul import \
            quant_epitome_matmul_fused_fold
        from repro.kernels.ref import quant_epitome_matmul_blocks_ref
        spec = EpitomeSpec(M=768, N=512, m=384, n=256, bm=256, bn=128)
        bk, bn = 128, 128
        q = jax.random.randint(KEY, (spec.m, spec.n), -4, 4).astype(jnp.int8)
        s, z = self._tables(spec.m // bk, spec.n // bn, seed=2)
        x = jax.random.normal(jax.random.PRNGKey(3), (8, spec.M))
        cb = ops.kernel_col_blocks(spec, bn)
        y = quant_epitome_matmul_fused_fold(
            x.T, q, s, z, cb, spec.row_offsets(), bm=spec.bm, bt=8, bk=bk,
            bn=bn, interpret=True)
        ref = quant_epitome_matmul_blocks_ref(ops.fold_rows(x, spec), q, s,
                                              z, cb, bk, bn)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("m,bk", [(288, 128), (576, 128), (2000, 256)])
    def test_padded_lane_blocks_vs_reconstruct(self, m, bk):
        """Epitome row counts that no lane-legal block divides (ResNet-50's
        288 and 576, its fc's 2000) pad m up to a 128-multiple block and
        still equal x @ W(deq(Q(E)))."""
        from repro.core.quant import QuantConfig, dequantize_packed
        spec = EpitomeSpec(M=2 * m, N=256, m=m, n=256, bm=min(256, m),
                           bn=128)
        qcfg = QuantConfig(bits=3)
        E = jax.random.normal(KEY, (spec.m, spec.n))
        p = ops.pack_epitome(E, spec, qcfg)
        assert p.bk == bk and p.scales.shape == (-(-m // bk), 2)
        x = jax.random.normal(jax.random.PRNGKey(5), (12, spec.M))
        W = reconstruct(dequantize_packed(p.q, p.scales, p.zeros,
                                          (p.bk, p.bn)), spec)
        for fused in (False, True):
            y = ops.quant_epitome_matmul(x, None, spec, packed=p,
                                         fused_fold=fused, interpret=True)
            np.testing.assert_allclose(np.asarray(y), np.asarray(x @ W),
                                       rtol=1e-4, atol=1e-4)

    def test_fused_fold_default_bt_is_lane_legal(self, monkeypatch):
        """The fused-fold kernel's bt is the lane dim of its activation
        block: the whole (8-padded) T up to 128 rows, 128 beyond."""
        from repro.core.quant import QuantConfig
        spec = EpitomeSpec(**ALIGNED)
        seen = []

        def spy(xt, *a, bt, **k):
            seen.append((xt.shape[1], bt))
            return jnp.zeros((xt.shape[1], spec.N), jnp.float32)
        monkeypatch.setattr(ops, "quant_epitome_matmul_fused_fold", spy)
        p = ops.pack_epitome(jnp.ones((spec.m, spec.n)), spec,
                             QuantConfig(bits=3))
        for T in (4, 100, 300):
            ops.quant_epitome_matmul(jnp.ones((T, spec.M)), None, spec,
                                     packed=p, fused_fold=True,
                                     interpret=True)
        assert seen == [(8, 8), (104, 104), (384, 128)]


class TestInterpretDecidedPerCall:
    def test_follows_the_backend_at_call_time(self, monkeypatch):
        assert ops.interpret_mode() == (jax.default_backend() == "cpu")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert ops.interpret_mode() is False
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        assert ops.interpret_mode() is True
        assert ops.interpret_mode(False) is False     # explicit wins

    def test_wrappers_ask_at_call_time(self, monkeypatch):
        """The public wrappers consult the backend on every call (not a
        flag frozen at import), so a process that reaches the chip never
        runs a kernel in interpret mode."""
        seen = []

        def spy(folded, E, cb, *, bt, bk, bn, interpret):
            seen.append(interpret)
            return jnp.zeros((folded.shape[0], len(cb) * bn), folded.dtype)
        monkeypatch.setattr(ops, "epitome_matmul_blocks", spy)
        spec = EpitomeSpec(M=256, N=256, m=128, n=256, bm=128, bn=256)
        x, E = jnp.ones((8, spec.M)), jnp.ones((spec.m, spec.n))
        for backend in ("tpu", "cpu"):
            monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
            ops.epitome_matmul(x, E, spec)
        assert seen == [False, True]
