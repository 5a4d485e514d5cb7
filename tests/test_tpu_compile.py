"""AOT compiles of the fused Pallas kernels for a described TPU v5e.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
block dims that are neither multiples of (8, 128) nor the full array dims,
or more fast memory than a kernel may use.  These tests compile the fused
kernels at the real widths of the serving path for a v5e chip that is
described, not attached, and assert each lowers to a Mosaic kernel
(``tpu_custom_call``).  Nothing runs, so they say nothing about results or
times.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU compiler library, and with several test
workers the others must still collect the same tests.  Keep every such
compile in this one file.
"""
import os

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
