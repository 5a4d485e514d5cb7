"""Epitome operator: reconstruction, wrapping, folding, overlap stats."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core.epitome import (
    EpitomeSpec, epitome_matmul_ref, epitomize_dense, fold_rows,
    folded_matmul, init_epitome, overlap_counts, overlap_mask, plan_epitome,
    reconstruct, wrapped_matmul,
)
from repro.kernels import ops

KEY = jax.random.PRNGKey(0)


def mk(M=512, N=384, m=256, n=128, bm=64, bn=64):
    return EpitomeSpec(M=M, N=N, m=m, n=n, bm=bm, bn=bn)


class TestSpec:
    def test_compression_rate(self):
        s = mk()
        assert s.compression_rate == (512 * 384) / (256 * 128)

    def test_plan_hits_target(self):
        for cr in (2.0, 4.0, 8.0):
            s = plan_epitome(4096, 4096, cr)
            assert s is not None
            assert 0.5 * cr <= s.compression_rate <= 2 * cr

    def test_plan_refuses_tiny(self):
        assert plan_epitome(64, 64, 4.0, patch=(256, 256)) is None

    def test_offsets_cover_epitome(self):
        s = mk()
        ro = s.row_offsets()
        assert ro.min() == 0 and ro.max() == s.m - s.bm
        co = s.col_offsets()
        assert co.min() == 0 and co.max() == s.n - s.bn

    def test_index_maps_bounds(self):
        s = mk()
        assert s.row_index_map().max() < s.m
        assert s.col_index_map().max() < s.n
        assert len(s.row_index_map()) == s.M

    def test_invalid_spec_raises(self):
        with pytest.raises(ValueError):
            EpitomeSpec(M=128, N=128, m=256, n=64, bm=64, bn=64)
        with pytest.raises(ValueError):
            EpitomeSpec(M=512, N=512, m=128, n=128, bm=256, bn=64)


class TestReconstruction:
    def test_patches_match_sampler(self):
        """Eq. 1: every patch of W is a contiguous sub-block of E."""
        s = mk()
        E = init_epitome(KEY, s)
        W = np.asarray(reconstruct(E, s))
        En = np.asarray(E)
        ro, co = s.row_offsets(), s.col_offsets()
        for i in range(s.gm):
            for j in range(s.gn):
                blk = W[i * s.bm:(i + 1) * s.bm, j * s.bn:(j + 1) * s.bn]
                ref = En[ro[i]:ro[i] + blk.shape[0], co[j]:co[j] + blk.shape[1]]
                np.testing.assert_array_equal(blk, ref)

    def test_wrapped_exact(self):
        s = EpitomeSpec(M=1024, N=512, m=512, n=128, bm=128, bn=128)
        E = init_epitome(KEY, s)
        x = jax.random.normal(KEY, (16, s.M))
        np.testing.assert_allclose(wrapped_matmul(x, E, s),
                                   epitome_matmul_ref(x, E, s),
                                   rtol=0, atol=1e-5)

    def test_folded_exact(self):
        s = mk()
        E = init_epitome(KEY, s)
        x = jax.random.normal(KEY, (16, s.M))
        np.testing.assert_allclose(folded_matmul(x, E, s),
                                   epitome_matmul_ref(x, E, s),
                                   rtol=1e-4, atol=1e-4)

    def test_folded_grad_matches(self):
        s = mk()
        E = init_epitome(KEY, s)
        x = jax.random.normal(KEY, (8, s.M))
        g1 = jax.grad(lambda e: (epitome_matmul_ref(x, e, s) ** 2).sum())(E)
        g2 = jax.grad(lambda e: (folded_matmul(x, e, s) ** 2).sum())(E)
        np.testing.assert_allclose(g1, g2, rtol=1e-3, atol=1e-2)

    def test_wrap_factor(self):
        s = EpitomeSpec(M=1024, N=1024, m=256, n=128, bm=128, bn=128)
        # n == bn: every column block identical -> full wrapping
        assert s.wrap_factor == s.gn

    def test_epitomize_dense_identity(self):
        """If the epitome equals the full weight, conversion is lossless."""
        s = EpitomeSpec(M=128, N=128, m=128, n=128, bm=128, bn=128)
        W = jax.random.normal(KEY, (128, 128))
        E = epitomize_dense(W, s)
        np.testing.assert_allclose(reconstruct(E, s), W, atol=1e-6)

    def test_epitomize_dense_reduces_error_vs_random(self):
        s = mk()
        W = jax.random.normal(KEY, (s.M, s.N))
        E_fit = epitomize_dense(W, s)
        E_rand = init_epitome(KEY, s)
        err_fit = float(jnp.mean((reconstruct(E_fit, s) - W) ** 2))
        err_rand = float(jnp.mean((reconstruct(E_rand, s) - W) ** 2))
        assert err_fit < err_rand


def _segment_sum_fold(x, spec):
    """The fold as a scatter-add: jax.ops.segment_sum over the static row
    map, with the virtual rows moved to the front and back."""
    xt = jnp.moveaxis(x, -1, 0)
    folded = jax.ops.segment_sum(xt, jnp.asarray(spec.row_index_map()),
                                 num_segments=spec.m)
    return jnp.moveaxis(folded, 0, -1)


# Every fold the rwkv6-7b and jamba2-mini-ep2 kernel-q3 configs run (the
# row map depends on M, m and bm alone), a ragged last block, and equal
# offsets (m == bm: every block lands on the same rows).
FOLD_SPECS = {
    "4096-1024-n4096": EpitomeSpec(4096, 4096, 1024, 4096),
    "4096-1024-n14336": EpitomeSpec(4096, 14336, 1024, 14336),
    "4096-1024-n16384": EpitomeSpec(4096, 16384, 1024, 16384),
    "14336-3584": EpitomeSpec(14336, 4096, 3584, 4096),
    "8192-2048": EpitomeSpec(8192, 4096, 2048, 4096),
    "4096-4096-n256": EpitomeSpec(4096, 1024, 4096, 256),
    "ragged": EpitomeSpec(1000, 256, 384, 256, bm=128, bn=128),
    "equal-offsets": EpitomeSpec(700, 256, 256, 256, bm=256, bn=256),
}


class TestFoldRows:
    @pytest.mark.parametrize("lead", [(32,), (2, 64)], ids=["T", "BS"])
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                             ids=["bf16", "f32"])
    @pytest.mark.parametrize("name", list(FOLD_SPECS))
    def test_equals_segment_sum_bit_for_bit(self, name, dtype, lead):
        spec = FOLD_SPECS[name]
        x = jax.random.normal(jax.random.PRNGKey(spec.M), lead + (spec.M,),
                              jnp.float32).astype(dtype)
        got = jax.jit(fold_rows, static_argnums=1)(x, spec)
        want = jax.jit(_segment_sum_fold, static_argnums=1)(x, spec)
        assert got.shape == lead + (spec.m,) and got.dtype == dtype
        bits = np.uint16 if dtype == jnp.bfloat16 else np.uint32
        np.testing.assert_array_equal(np.asarray(got).view(bits),
                                      np.asarray(want).view(bits))

    def test_expert_down_fold_lowers_without_scatter(self):
        """The 14336 -> 3584 fold at a 64-row decode step: static slices
        and adds, where segment_sum lowers to a scatter."""
        spec = FOLD_SPECS["14336-3584"]
        x = jnp.zeros((64, spec.M), jnp.bfloat16)
        text = jax.jit(ops.fold_rows, static_argnums=1).lower(
            x, spec).as_text()
        assert "scatter" not in text
        assert "slice" in text and "add" in text
        assert "scatter" in jax.jit(_segment_sum_fold, static_argnums=1).lower(
            x, spec).as_text()


class TestOverlap:
    def test_counts_cover(self):
        s = mk()
        cnt = overlap_counts(s)
        assert cnt.min() >= 1          # every cell used at least once
        # total coverage equals the number of virtual cells
        assert cnt.sum() == s.M * s.N

    def test_counts_match_index_maps(self):
        s = mk()
        cnt = overlap_counts(s)
        rc = np.bincount(s.row_index_map(), minlength=s.m)
        cc = np.bincount(s.col_index_map(), minlength=s.n)
        np.testing.assert_array_equal(cnt, rc[:, None] * cc[None, :])

    def test_center_overlaps_more(self):
        """Paper Fig 2(c): interior cells repeat more than edges."""
        s = EpitomeSpec(M=1024, N=1024, m=256, n=256, bm=128, bn=128)
        cnt = overlap_counts(s)
        assert cnt[s.m // 2, s.n // 2] >= cnt[0, 0]
        mask = overlap_mask(s)
        assert mask.any() and not mask.all()


@settings(max_examples=25, deadline=None)
@given(
    M=st.integers(2, 40).map(lambda k: 32 * k),
    N=st.integers(2, 40).map(lambda k: 32 * k),
    cr=st.floats(1.5, 16.0),
)
def test_property_plan_and_reconstruct(M, N, cr):
    """Any planned epitome reconstructs to the exact virtual shape, and the
    folded matmul agrees with explicit reconstruction."""
    s = plan_epitome(M, N, cr, patch=(64, 64), align=32)
    if s is None:
        return
    assert s.compression_rate > 1.0
    E = init_epitome(jax.random.PRNGKey(M * N), s)
    W = reconstruct(E, s)
    assert W.shape == (M, N)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, M))
    np.testing.assert_allclose(folded_matmul(x, E, s), x @ W,
                               rtol=2e-3, atol=2e-3)


@settings(max_examples=20, deadline=None)
@given(m=st.integers(2, 8), n=st.integers(2, 8),
       gm=st.integers(1, 6), gn=st.integers(1, 6))
def test_property_overlap_counts_positive(m, n, gm, gn):
    s = EpitomeSpec(M=16 * gm, N=16 * gn, m=16 * m if 16 * m <= 16 * gm else 16 * gm,
                    n=16 * n if 16 * n <= 16 * gn else 16 * gn, bm=16, bn=16)
    cnt = overlap_counts(s)
    assert cnt.min() >= 0
    assert (cnt > 0).any()
