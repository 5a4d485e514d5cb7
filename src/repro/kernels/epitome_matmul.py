"""Pallas TPU kernel: epitome-space blocked matmul with output indirection.

Computes  y[:, j*bn:(j+1)*bn] = x_folded @ E[:, cb[j]*bn:(cb[j]+1)*bn]
for every output-column block j, where ``cb`` is the static column-block
offset table derived from the EpitomeSpec (the TPU analogue of the paper's
OFAT: it steers which epitome columns produce which output columns, at
trace time instead of runtime).  Duplicated ``cb`` entries ARE the paper's
output channel wrapping — the same E block is re-read from VMEM, which is
free, instead of recomputed.

The fold (IFRT analogue — virtual rows scatter-added into epitome rows) is
a cheap bandwidth-bound segment-sum done by the ops.py wrapper; this kernel
is the MXU hot loop.

Grid: (T/bt, gn, m/bk), k innermost for accumulation.  VMEM per step:
x (bt, bk) + E (bk, bn) + acc (bt, bn) fp32 — MXU-aligned multiples of 128.

The (i, j) grid dims are declared ``parallel`` (only k carries the
accumulator), so Mosaic is free to double-buffer the E tiles across the k
loop and overlap the next block's HBM->VMEM copy with the current dot —
the pipelining half of the autotuner story (kernels/autotune.py picks the
block shapes, the dimension semantics let the compiler hide the loads).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _kernel(cb_ref, x_ref, e_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], e_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def epitome_matmul_blocks(x_folded: Array, E: Array, col_blocks,
                          *, bt: int = 256, bk: int = 256, bn: int = 0,
                          interpret: bool = False) -> Array:
    """x_folded: (T, m); E: (m, n); col_blocks: (gn,) int32 block indices
    into E's column blocks of width bn.  Returns (T, gn*bn)."""
    T, m = x_folded.shape
    n = E.shape[1]
    col_blocks = jnp.asarray(col_blocks, jnp.int32)
    gn = col_blocks.shape[0]
    bn = bn or min(n, 256)
    assert n % bn == 0, f"epitome cols {n} must tile by {bn}"
    bt = min(bt, T)
    bk = min(bk, m)
    assert T % bt == 0 and m % bk == 0, (T, bt, m, bk)
    nk = m // bk

    grid = (T // bt, gn, nk)
    kernel = functools.partial(_kernel, nk=nk)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bt, bk), lambda i, j, k, cb: (i, k)),
                pl.BlockSpec((bk, bn), lambda i, j, k, cb: (k, cb[j])),
            ],
            out_specs=pl.BlockSpec((bt, bn), lambda i, j, k, cb: (i, j)),
            scratch_shapes=[pltpu.VMEM((bt, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((T, gn * bn), x_folded.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="epim_epitome_matmul",
        metadata={"epim_kernel": "epim_epitome_matmul"},
    )(col_blocks, x_folded, E)
