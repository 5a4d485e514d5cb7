"""Pallas TPU kernel: intN dequant matmul with per-crossbar-tile scales.

Executes the paper's per-crossbar quantization (§4.2) on the MXU: the
weight is stored as int8 codes (any bitwidth <= 8 packed into int8 range)
with one (scale, zero) pair per 256x256 tile — exactly one crossbar in the
PIM mapping.  Dequantization happens in VMEM registers per block:
    W_blk = (Q_blk + z[jm, jn]) * s[jm, jn]
so the HBM traffic is the int8 codes (4x smaller than bf16 x2).

Grid (T/bt, N/bn, M/bk) with k innermost; bk = bn = 256 = the tile size, so
each grid step consumes exactly one (scale, zero) scalar.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

TILE = 256


def _kernel(x_ref, q_ref, s_ref, z_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = (q_ref[...].astype(jnp.float32) + z_ref[0, 0]) * s_ref[0, 0]
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def quant_matmul(x: Array, q: Array, scales: Array, zeros: Array,
                 *, bt: int = 256, interpret: bool = False) -> Array:
    """x: (T, M); q: (M, N) int8; scales/zeros: (M/TILE, N/TILE) fp32.
    Returns x @ ((q + z) * s) with per-tile (s, z)."""
    T, M = x.shape
    M2, N = q.shape
    assert M == M2 and M % TILE == 0 and N % TILE == 0, (M, N)
    bt = min(bt, T)
    assert T % bt == 0
    nk = M // TILE
    grid = (T // bt, N // TILE, nk)
    kernel = functools.partial(_kernel, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, TILE), lambda i, j, k: (i, k)),
            pl.BlockSpec((TILE, TILE), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bt, TILE), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((T, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, TILE), jnp.float32)],
        interpret=interpret,
        name="epim_quant_matmul",
        metadata={"epim_kernel": "epim_quant_matmul"},
    )(x, q, scales, zeros)
