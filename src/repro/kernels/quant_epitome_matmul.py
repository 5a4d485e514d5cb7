"""Pallas TPU kernel: fused quantized-epitome matmul (EPIM's flagship path).

The paper's headline configuration combines BOTH compression axes — the
epitome operator (crossbar-area reduction) and epitome-aware quantization
(§4.2, per-crossbar scaling factors).  This kernel executes that combination
in one MXU hot loop:

  y[:, j*bn:(j+1)*bn] = x_folded @ deq(Q[:, cb[j]*bn:(cb[j]+1)*bn])
  deq(Q_blk) = (Q_blk + z[k, cb[j]]) * s[k, cb[j]]

for every output-column block j, where

  * ``Q`` is the epitome stored as **int8 codes** — it stays int8 all the
    way into VMEM, so HBM traffic is 4x smaller than bf16 x2 and the whole
    (already CR-x-compressed) epitome is read from HBM exactly once;
  * ``(s, z)`` are one (scale, zero) pair per kernel block (bk x bn) — the
    crossbar-tile contract shared with quant_matmul: each grid step reads
    exactly one scalar pair, dequantized in registers right before the dot.
    Both tables live whole in SMEM and are indexed ``[k, cb[j]]`` in the
    body: a (1, 1) VMEM block per grid step breaks Mosaic's rule that block
    dims be multiples of (8, 128) or the full array dims;
  * ``cb`` is the scalar-prefetched OFAT column-block table from
    kernel_col_blocks.  Duplicated entries ARE the paper's output channel
    wrapping: the same int8 block is re-read from VMEM for free.

Grid: (T/bt, gn, m/bk), k innermost for accumulation.  VMEM per step:
x (bt, bk) + Q (bk, bn) int8 + acc (bt, bn) fp32.  The caller (ops.py)
picks bk as a multiple of 128 or the whole (padded) m, so the activation
block's lane dim is legal on the chip.

Two pipelining levers live here (the kernel half of kernels/autotune.py):

  * The (i, j) grid dims are declared ``parallel`` — only k carries the
    accumulator — so Mosaic double-buffers the int8 code tiles across the
    k loop: the next block's HBM->VMEM copy overlaps the current dot.
  * ``quant_epitome_matmul_fused_fold`` takes the *unfolded* activation
    plus the scalar-prefetched row-offset table and performs the fold_rows
    slice-add into a VMEM scratch inside the kernel, so on the decode
    path the folded activation never round-trips HBM between the fold and
    the matmul.  Contributions accumulate in ascending virtual-block order
    — the same order as ops.fold_rows' slice-adds — so on the CPU the
    fold is bit-identical to the ops.fold_rows + quant_epitome_matmul_blocks
    path (on a TPU, XLA may round that path's fused bf16 adds once).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _dequant(q_ref, s_ref, z_ref, cb_ref):
    """This grid step's int8 code tile, dequantized in registers with its
    one (scale, zero) pair read from the SMEM tables."""
    k, cb = pl.program_id(2), cb_ref[pl.program_id(1)]
    return (q_ref[...].astype(jnp.float32) + z_ref[k, cb]) * s_ref[k, cb]


def _kernel(cb_ref, x_ref, q_ref, s_ref, z_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _dequant(q_ref, s_ref, z_ref, cb_ref)
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def quant_epitome_matmul_blocks(x_folded: Array, q: Array, scales: Array,
                                zeros: Array, col_blocks,
                                *, bt: int = 256, bk: int = 256, bn: int = 0,
                                interpret: bool = False) -> Array:
    """x_folded: (T, m); q: (m, n) int8 epitome codes; scales/zeros:
    (m/bk, n/bn) fp32 per-block dequant params; col_blocks: (gn,) int32
    block indices into q's column blocks of width bn.  Returns (T, gn*bn)."""
    T, m = x_folded.shape
    m2, n = q.shape
    col_blocks = jnp.asarray(col_blocks, jnp.int32)
    gn = col_blocks.shape[0]
    bn = bn or min(n, 256)
    assert m == m2, (m, m2)
    assert n % bn == 0, f"epitome cols {n} must tile by {bn}"
    bt = min(bt, T)
    bk = min(bk, m)
    assert T % bt == 0 and m % bk == 0, (T, bt, m, bk)
    assert scales.shape == (m // bk, n // bn), (scales.shape, m // bk, n // bn)
    assert zeros.shape == scales.shape, (zeros.shape, scales.shape)
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
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((bt, bn), lambda i, j, k, cb: (i, j)),
            scratch_shapes=[pltpu.VMEM((bt, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((T, gn * bn), x_folded.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="epim_qmm",
        metadata={"epim_kernel": "epim_qmm"},
    )(col_blocks, x_folded, q, scales, zeros)


def _fused_fold_kernel(cb_ref, ro_ref, xt_ref, q_ref, s_ref, z_ref, o_ref,
                       fold_ref, acc_ref, *, nk: int, gm: int, bm: int):
    """Fold + dequant + dot in one grid step.  ``xt_ref`` holds the whole
    (Mp, bt) transposed activation slab for row block i; the fold runs once
    per i (at j == k == 0) into the (m_pad, bt) scratch, then every (j, k)
    step contracts one (bk, bt) slice of it against one int8 code tile."""
    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _fold():
        fold_ref[...] = jnp.zeros_like(fold_ref)
        for i in range(gm):   # ascending block order, as ops.fold_rows
            off = ro_ref[i]
            fold_ref[pl.dslice(off, bm), :] = (
                fold_ref[pl.dslice(off, bm), :]
                + xt_ref[pl.dslice(i * bm, bm), :])

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k = pl.program_id(2)
    w = _dequant(q_ref, s_ref, z_ref, cb_ref)
    xk = fold_ref[pl.dslice(k * (fold_ref.shape[0] // nk),
                            fold_ref.shape[0] // nk), :]
    # contract the fold scratch's row dim (epitome rows) against w's rows
    acc_ref[...] += jax.lax.dot_general(
        xk.astype(jnp.float32), w, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def quant_epitome_matmul_fused_fold(xt: Array, q: Array, scales: Array,
                                    zeros: Array, col_blocks, row_offsets,
                                    *, bm: int, bt: int, bk: int, bn: int,
                                    interpret: bool = False) -> Array:
    """Fused-fold variant: xt is the (Mp, T) *transposed, unfolded*
    activation (Mp = gm*bm zero-padded virtual rows); row_offsets is the
    scalar-prefetched (gm,) epitome-row offset table (spec.row_offsets()).
    q/scales/zeros as in quant_epitome_matmul_blocks with m pre-padded to a
    bk multiple.  Returns (T, gn*bn) without the folded activation ever
    leaving VMEM."""
    Mp, T = xt.shape
    m, n = q.shape
    col_blocks = jnp.asarray(col_blocks, jnp.int32)
    row_offsets = jnp.asarray(row_offsets, jnp.int32)
    gn = col_blocks.shape[0]
    gm = row_offsets.shape[0]
    assert Mp == gm * bm, (Mp, gm, bm)
    assert T % bt == 0 and m % bk == 0 and n % bn == 0, (T, bt, m, bk, n, bn)
    assert scales.shape == (m // bk, n // bn), (scales.shape, m // bk, n // bn)
    nk = m // bk

    grid = (T // bt, gn, nk)
    kernel = functools.partial(_fused_fold_kernel, nk=nk, gm=gm, bm=bm)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((Mp, bt), lambda i, j, k, cb, ro: (0, i)),
                pl.BlockSpec((bk, bn), lambda i, j, k, cb, ro: (k, cb[j])),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((bt, bn), lambda i, j, k, cb, ro: (i, j)),
            scratch_shapes=[pltpu.VMEM((m, bt), jnp.float32),
                            pltpu.VMEM((bt, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((T, gn * bn), jnp.float32),
        # the fold scratch is shared across j and k for a fixed i, so only
        # the row-block dim may be reordered freely
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="epim_qmm_fused_fold",
        metadata={"epim_kernel": "epim_qmm_fused_fold"},
    )(col_blocks, row_offsets, xt, q, scales, zeros)
