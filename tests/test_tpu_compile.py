"""AOT compiles for a described TPU v5e: the fused Pallas kernels, and
where the compiled decode step places the state pool.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
block dims that are neither multiples of (8, 128) nor the full array dims,
or more fast memory than a kernel may use.  These tests compile the fused
kernels at the real widths of the serving path for a v5e chip that is
described, not attached, and assert each lowers to a Mosaic kernel
(``tpu_custom_call``).  The CPU backend inserts copies of its own, so only
a TPU module shows whether the decode step writes the donated pool in
place.  Nothing runs, so they say nothing about results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU compiler library, and with several test
workers the others must still collect the same tests.  Keep every such
compile in this one file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.epitome import EpitomeSpec
from repro.core.quant import QuantConfig
from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _rwkv_specs():
    """The distinct epitome geometries of rwkv6-7b kernel-q3 (mixer
    d x d, channel-mix d x ff and ff x d), with their quant configs."""
    from repro.configs import get_config
    from repro.models import lm
    cfg = get_config("rwkv6-7b", "kernel-q3")
    seen = {}
    for name, lc in lm.lm_layer_configs(cfg).items():
        if lc.is_epitome and lc.spec not in seen:
            seen[lc.spec] = (name, lc.quant)
    return [(name, spec, q) for spec, (name, q) in seen.items()]


def _compile_quant(spec, qcfg, T, dtype, sharding, *, fused_fold=False):
    bk, bn = ops.pack_blocks(spec, qcfg)
    grid = (-(-spec.m // bk), spec.n // bn)

    def fn(x, q, s, z):
        packed = ops.PackedEpitome(q, s, z, bk, bn)
        return ops.quant_epitome_matmul(x, None, spec, packed=packed,
                                        fused_fold=fused_fold,
                                        interpret=False)
    _assert_mosaic(fn, _sds((T, spec.M), dtype, sharding),
                   _sds((spec.m, spec.n), jnp.int8, sharding),
                   _sds(grid, jnp.float32, sharding),
                   _sds(grid, jnp.float32, sharding))


@pytest.mark.parametrize("T", [8, 256], ids=["decode", "prefill"])
def test_quant_kernel_rwkv6_7b_widths(one_chip, T):
    """The int8 kernel at every rwkv6-7b kernel-q3 projection geometry, at
    the engine's decode width (4 slots pad to 8 rows) and a prefill
    chunk."""
    specs = _rwkv_specs()
    assert len(specs) == 3, [s for _, s, _ in specs]
    for _, spec, qcfg in specs:
        _compile_quant(spec, qcfg, T, jnp.bfloat16, one_chip)


def test_fused_fold_kernel(one_chip):
    """The in-kernel fold variant at the decode width of the widest fan-in
    projection (ff x d, 56 row blocks folded in VMEM)."""
    name, spec, qcfg = max(_rwkv_specs(), key=lambda t: t[1].M)
    assert spec.M == 14336, name
    _compile_quant(spec, qcfg, 8, jnp.bfloat16, one_chip, fused_fold=True)


def test_fp_epitome_kernel(one_chip):
    """The unquantized epitome kernel at the rwkv6-7b mixer geometry."""
    spec = EpitomeSpec(M=4096, N=4096, m=1024, n=4096, bm=256, bn=256)

    def fn(x, E):
        return ops.epitome_matmul(x, E, spec, interpret=False)
    for T in (8, 256):
        _assert_mosaic(fn, _sds((T, spec.M), jnp.bfloat16, one_chip),
                       _sds((spec.m, spec.n), jnp.float32, one_chip))


def test_resnet50_conv_patch_matrix(one_chip):
    """ResNet-50 layer2 3x3 conv at batch 8: a (6272, 1152) im2col patch
    matrix over a 288-row epitome.  bk must be lane-legal (a multiple of
    128 or the whole dim), so the 288 rows pad to 3 blocks of 128."""
    spec = EpitomeSpec(M=1152, N=128, m=288, n=128, bm=256, bn=128)
    qcfg = QuantConfig(bits=3)
    assert ops.pack_blocks(spec, qcfg) == (128, 128)
    _compile_quant(spec, qcfg, 8 * 28 * 28, jnp.float32, one_chip)


def test_resnet50_specs_match_inventory():
    """The conv geometry above is the one get_resnet plans (guards the
    compile test against drifting away from the served network)."""
    from repro.configs import get_resnet
    model = get_resnet("resnet50", "kernel-q3")
    specs = {l.name: c.spec for l, c in zip(model.layers, model._cfgs())}
    assert specs["layer2.0.conv2"] == EpitomeSpec(
        M=1152, N=128, m=288, n=128, bm=256, bn=128)
    assert np.prod([8, 28, 28]) == 6272


_HLO_DTYPE = {"float32": "f32", "bfloat16": "bf16"}


def _hlo_shape(leaf):
    return f"{_HLO_DTYPE[str(leaf.dtype)]}[{','.join(map(str, leaf.shape))}]"


def test_decode_writes_state_pool_in_place(one_chip):
    """The engine's decode dispatch updates the donated state pool in
    place: no pool leaf is allocated afresh and the f32 recurrent state is
    never copied whole.  Scanning the pool as xs/ys put one AllocateBuffer
    per leaf in the module and a root copy of each into the donated
    buffer.  (The smoke size's 2 KB bf16 ``x_prev`` leaves are staged
    through fast memory by copies either way; at the served widths those
    copies go too.)"""
    from repro.configs import get_smoke_config
    from repro.launch import engine
    from repro.models import lm
    from repro.models.kv_pool import SlotStatePool
    C = 8
    cfg = get_smoke_config("rwkv6-7b")

    def sds(leaf):
        return _sds(leaf.shape, leaf.dtype, one_chip)
    params = jax.tree.map(sds, jax.eval_shape(
        lambda key: lm.init_params(key, cfg), jax.random.PRNGKey(0)))
    pool = jax.tree.map(sds, jax.eval_shape(
        lambda: SlotStatePool(cfg, C, 24).tree))
    rows = lambda *shape, dtype=jnp.int32: _sds((C,) + shape, dtype, one_chip)
    text = engine._decode_multi.lower(
        params, pool, rows(1), rows(), rows(2, dtype=jnp.uint32),
        rows(dtype=jnp.float32), rows(), None, cfg=cfg, k=1
    ).compile().as_text()

    pool_shapes = {_hlo_shape(l) for l in jax.tree.leaves(pool)}
    s = _hlo_shape(pool["L0"]["s"])
    assert s == f"f32[{cfg.n_groups},{C},4,16,16]", s
    allocs = re.findall(r"= (\w+\[[\d,]*\])\{[^}]*\} custom-call\(\)"
                        r"[^\n]*AllocateBuffer", text)
    assert not pool_shapes & set(allocs), allocs
    copies = re.findall(r"= (\w+\[[\d,]*\])\{[^}]*\} copy\(", text)
    assert s not in copies, copies


def test_jamba2_share_decode_step(one_chip, monkeypatch):
    """The engine's decode dispatch for Jamba2-Mini's one-chip share at
    published widths (8 layers, 8 of 16 experts, 64 slots, 2560 tokens in
    pages of 16): the held experts' projections run the fused int8 kernel
    (Mosaic custom calls under the ``epim.moe`` scope), and neither the
    Mamba state nor the paged KV pool is allocated afresh or copied whole:
    the donated pool is written in place."""
    from repro.configs import get_config
    from repro.launch import engine
    from repro.models import lm
    from repro.models.kv_pool import SlotStatePool
    # the kernels pick Mosaic over interpret mode from the default backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    C, max_len = 64, 2560
    cfg = get_config("jamba2-mini-ep2", "kernel-q3")

    def sds(leaf):
        return _sds(leaf.shape, leaf.dtype, one_chip)
    params = jax.tree.map(sds, jax.eval_shape(
        lambda key: lm.prepack_params(lm.init_params(key, cfg), cfg),
        jax.random.PRNGKey(0)))
    assert params["groups"]["L1"]["ffn"]["w_gate"]["Eq"].shape == (
        1, 8, 1024, 14336)
    pool = jax.tree.map(sds, jax.eval_shape(
        lambda: SlotStatePool(cfg, C, max_len, page_size=16).tree))
    rows = lambda *shape, dtype=jnp.int32: _sds((C,) + shape, dtype, one_chip)
    text = engine._decode_multi.lower(
        params, pool, rows(1), rows(), rows(2, dtype=jnp.uint32),
        rows(dtype=jnp.float32), rows(), rows(max_len // 16), cfg=cfg, k=1
    ).compile().as_text()

    # int8 codes each kernel call reads: the 4 dense FFNs' gate and up
    # (1024 x 14336) and down (3584 x 4096) and, once each in the expert
    # scan's body, the 4 MoE layers' — 16 and 8 with the experts on the
    # kernel, 8 and 4 without; 7 Mamba in_proj and out_proj; attention
    codes = [re.search(r"s8\[[\d,]*\]", l).group(0)
             for l in text.splitlines() if "tpu_custom_call" in l]
    assert sorted(set(codes)) == ["s8[1024,14336]", "s8[1024,16384]",
                                  "s8[1024,4096]", "s8[2048,4096]",
                                  "s8[3584,4096]", "s8[4096,256]"], codes
    assert codes.count("s8[1024,14336]") == 16
    assert codes.count("s8[3584,4096]") == 8
    assert codes.count("s8[1024,16384]") == codes.count("s8[2048,4096]") == 7
    h, k = _hlo_shape(pool["L0"]["h"]), _hlo_shape(pool["L4"]["k"])
    assert (h, k) == ("f32[1,64,16,8192]", "bf16[1,10241,16,8,128]")
    pool_shapes = {_hlo_shape(l) for l in jax.tree.leaves(pool)}
    allocs = re.findall(r"= (\w+\[[\d,]*\])\{[^}]*\} custom-call\(\)"
                        r"[^\n]*AllocateBuffer", text)
    assert not pool_shapes & set(allocs), allocs
    copies = re.findall(r"= (\w+\[[\d,]*\])\{[^}]*\} copy\(", text)
    assert not pool_shapes & set(copies), copies
    # the fold is static slices and adds: no scatter under it
    fold = [l for l in text.splitlines() if "/epim.fold/" in l]
    assert fold and not any(" scatter(" in l for l in fold)


@pytest.mark.parametrize("B,S", [(64, 1), (1, 128)], ids=["decode", "chunk"])
def test_mamba_scan_keeps_pooled_layout(one_chip, B, S):
    """Jamba2-Mini's conv, selective scan and gate (``ssm._mamba_core``) at
    published widths (d_inner 8192, d_state 16, dt_rank 256; dense random
    f32 x_proj and dt_proj, f32 throughout) for the cell's decode step
    (64 rows, one token) and its prefill chunk (one row, 128 tokens).

    The scan runs token by token on ``h`` as the pool holds it, (B,
    d_state, d_inner): no f32 array with (d_inner, d_state) as its last
    two dims is built, so nothing of shape (B, L, d_inner, d_state) exists
    in either program.  The chunk's temp was 368.8 MB under the
    associative scan and is now under 64 MB (0 here), and its bytes
    accessed 4,749.7 MB and now under 1 GB (163.2 MB here; the token
    loop's body, 16 tokens, is counted once).  At one token both forms read 379.3 MB:
    XLA fused the one-element associative scan into a single pass over
    ``h`` as well, so the decode case checks the layout alone."""
    import dataclasses
    from repro.configs import get_config
    from repro.models import ssm
    cfg = dataclasses.replace(get_config("jamba2-mini-ep2"),
                              compute_dtype="float32")
    di, ds, dc = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
    assert (di, ds, cfg.dt_rank, cfg.mamba_chunk) == (8192, 16, 256, 128)
    assert not cfg.epitome.enabled

    def sds(leaf):
        return _sds(leaf.shape, leaf.dtype, one_chip)
    params = jax.tree.map(sds, jax.eval_shape(
        lambda key: ssm.init_mamba(key, cfg, prefix="L0/mixer"),
        jax.random.PRNGKey(0)))
    params = {k: v for k, v in params.items()
              if k not in ("in_proj", "out_proj")}
    assert params["x_proj"]["W"].dtype == jnp.float32

    def core(p, xi, z, conv, h):
        return ssm._mamba_core(p, xi, z, conv, h, cfg, cfg.mamba_chunk,
                               "L0/mixer", None)
    xi, conv, h = (_sds(shape, jnp.float32, one_chip)
                   for shape in ((B, S, di), (B, dc - 1, di), (B, ds, di)))
    assert jax.eval_shape(core, params, xi, xi, conv, h)[2].shape == h.shape
    compiled = jax.jit(core).lower(params, xi, xi, conv, h).compile()
    text = compiled.as_text()
    assert not re.search(rf"f32\[[\d,]*,{di},{ds}\]", text)
    if S > 1:
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, list) else cost
        assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20
        assert cost["bytes accessed"] < 1e9
