"""Public jit'd wrappers around the Pallas kernels.

`epitome_matmul` is what core/layers.py mode="kernel" calls: it folds the
activations into epitome-row space (the IFRT analogue, a cheap segment-sum),
runs the MXU kernel with the static OFAT offset table, and trims the result
to the virtual width.  On the CPU backend (the unit tests) the kernels run
in Pallas interpret mode; on the chip the same code compiles to Mosaic.
The choice is made per call (``interpret_mode``), never at import.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.epitome import EpitomeSpec
from ..core.quant import QuantConfig, quantize_epitome_packed
from .epitome_matmul import epitome_matmul_blocks
from .quant_epitome_matmul import (quant_epitome_matmul_blocks,
                                   quant_epitome_matmul_fused_fold)
from .quant_matmul import quant_matmul as _quant_matmul
from .wkv6 import wkv6_chunked


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a kernel call runs in Pallas interpret mode: an explicit
    ``interpret`` wins; otherwise only where JAX's default backend is the
    CPU — so a chip run always compiles the kernels to Mosaic."""
    if interpret is not None:
        return interpret
    return jax.default_backend() == "cpu"


def kernel_col_blocks(spec: EpitomeSpec,
                      bn: Optional[int] = None) -> np.ndarray:
    """Static OFAT table: output block j <- epitome column block cb[j].
    Exact only for bn-aligned column offsets (the planner's wrap_cols
    designs give offset 0; pim.plan.plan_conv_specs emits only
    aligned families); unaligned spread offsets are snapped to their
    containing block — the kernel then defines its own (snapped) sampling,
    tested against the block oracle rather than exact reconstruction.

    With ``bn`` (a divisor of spec.bn — an autotuned narrower kernel
    block), each spec.bn-wide virtual block splits into spec.bn/bn
    sub-blocks; requires bn-aligned offsets so the split samples exactly
    the same columns (``col_blocks_splittable`` gates candidates)."""
    offs = spec.col_offsets()
    if bn is None or bn == spec.bn:
        return (offs // spec.bn).astype(np.int32)
    assert spec.bn % bn == 0 and (offs % bn == 0).all(), (spec, bn)
    sub = spec.bn // bn
    cb = offs[:, None] // bn + np.arange(sub)[None, :]
    return cb.reshape(-1).astype(np.int32)


def col_blocks_splittable(spec: EpitomeSpec, bn: int) -> bool:
    """True iff ``kernel_col_blocks(spec, bn)`` samples exactly the same W
    columns as the spec.bn table — the gate for autotuned bn candidates."""
    if bn == spec.bn:
        return True
    return (spec.bn % bn == 0 and spec.n % bn == 0
            and bool((spec.col_offsets() % bn == 0).all()))


@jax.named_scope("epim.fold")
def fold_rows(x: jax.Array, spec: EpitomeSpec) -> jax.Array:
    """IFRT analogue: scatter-add virtual fan-in into epitome rows.  Its
    device ops carry ``epim.fold`` in their op_name (profile reductions
    group the fold's time by it)."""
    rmap = jnp.asarray(spec.row_index_map())
    xt = jnp.moveaxis(x, -1, 0)
    folded = jax.ops.segment_sum(xt, rmap, num_segments=spec.m)
    return jnp.moveaxis(folded, 0, -1)


def epitome_matmul(x: jax.Array, E: jax.Array, spec: EpitomeSpec,
                   *, bt: Optional[int] = None, bk: Optional[int] = None,
                   bn: Optional[int] = None,
                   interpret: Optional[bool] = None) -> jax.Array:
    """y = x @ W(E) via the fused epitome-space kernel.

    Leading dims are free-form — (B, M), (B, S, M) or a conv patch matrix
    (N, H', W', kh*kw*cin) all flatten to (T, m) rows; the fold runs once
    per row regardless of how many kernel windows produced it.  bt/bk/bn
    override the heuristic block shapes (an autotuned triple); a bk that
    tiles m raggedly zero-pads the contraction dim (dot-neutral)."""
    interpret = interpret_mode(interpret)
    *lead, M = x.shape
    x2 = x.reshape(-1, M)
    T = x2.shape[0]
    bk = _pick_bk(spec.m) if bk is None else bk
    bn = spec.bn if bn is None else bn
    folded, bt = _pad_rows(fold_rows(x2, spec), bt)  # (Tp, m)
    folded, E = _pad_contraction(folded, E.astype(x.dtype), bk)
    y = epitome_matmul_blocks(folded, E, kernel_col_blocks(spec, bn),
                              bt=bt, bk=bk, bn=bn, interpret=interpret)
    return y[:T, :spec.N].reshape(*lead, spec.N)


_BT_BLOCKS = (256, 128, 64, 32, 16, 8)


def _pick_bt(T: int) -> int:
    """Row block for a T-row matmul.  Prefers the largest block that divides
    T exactly (no padding); otherwise the largest block not exceeding T —
    the caller pads T up to a multiple (`_pad_rows`) and trims the output.
    A prime or odd T (e.g. N*H'*W' = 4*7*7 = 196, or T = 97) therefore
    costs at most one partially-wasted row block instead of collapsing the
    whole grid to bt=1."""
    for bt in _BT_BLOCKS:
        if T % bt == 0:
            return bt
    for bt in _BT_BLOCKS:
        if bt <= T:
            return bt
    return _BT_BLOCKS[-1]     # T < 8: a single (padded) row block


def _pad_rows(x2: jax.Array, bt: Optional[int] = None) -> tuple:
    """Zero-pad the row dim of (T, m) up to a multiple of the chosen row
    block.  Returns (padded, bt); callers slice the output back to T rows
    (zero rows are neutral through every matmul path)."""
    T = x2.shape[0]
    bt = _pick_bt(T) if bt is None else bt
    pad = (-T) % bt
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    return x2, bt


# Contraction-block menu.  bk is the lane (last) dim of the activation
# block, so on the chip it must be a multiple of 128 or the whole (padded)
# contraction dim; the sub-128 entries serve only quant tiles narrower
# than 128, which no chip configuration uses (interpret-mode tests).
_BK_LANE_BLOCKS = (512, 256, 128)
_BK_NARROW_BLOCKS = (64, 32, 16, 8)


def _pick_k_block(m: int, cap: int) -> int:
    """Contraction block for an m-row epitome, at most ``cap`` wide.  An m
    that fits under the cap is one whole block (the full dim: legal at any
    m, no padding).  Otherwise the lane-legal block that pads m least wins,
    the larger on a tie; the caller zero-pads the contraction dim up to a
    block multiple (``_pad_contraction``: zero activation columns make the
    padded weight rows dot-neutral)."""
    if m <= cap:
        return m
    menu = [b for b in _BK_LANE_BLOCKS if b <= cap] or \
        [b for b in _BK_NARROW_BLOCKS if b <= cap][:1]
    return min(menu, key=lambda b: (-(-m // b) * b, -b))


def _pick_bk(m: int) -> int:
    """Contraction block of the fp epitome kernel (at most 512 rows)."""
    return _pick_k_block(m, _BK_LANE_BLOCKS[0])


def _pad_contraction(folded: jax.Array, w_rows: jax.Array, bk: int) -> tuple:
    """Zero-pad the contraction dim of (T, m) x (m, n) up to a bk multiple.
    The folded activation's padded *columns* are zero, so the padded weight
    *rows* contribute exactly 0 to every dot regardless of their values —
    the same neutrality argument as ``_pad_rows``, on the other axis."""
    m = folded.shape[1]
    pad = (-m) % bk
    if pad:
        folded = jnp.pad(folded, ((0, 0), (0, pad)))
        w_rows = jnp.pad(w_rows, ((0, pad), (0, 0)))
    return folded, w_rows


def wkv6(r, k, v, logw, u, *, chunk: int = 64,
         interpret: Optional[bool] = None):
    """r/k/v/logw: (B, S, H, K); u: (H, K) -> (B, S, H, K)."""
    interpret = interpret_mode(interpret)
    B, S, H, K = r.shape
    to_bh = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, K)
    pad = (-S) % chunk
    rb, kb, vb, lb = (to_bh(t) for t in (r, k, v, logw))
    if pad:
        z = lambda t: jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
        rb, kb, vb = z(rb), z(kb), z(vb)
        lb = jnp.pad(lb, ((0, 0), (0, pad), (0, 0)))
    ub = jnp.tile(u, (B, 1))                          # (B*H, K)
    o = wkv6_chunked(rb, kb, vb, lb, ub, chunk=chunk, interpret=interpret)
    o = o[:, :S]
    return o.reshape(B, H, S, K).transpose(0, 2, 1, 3)


def quant_matmul(x, q, scales, zeros, *, interpret: Optional[bool] = None):
    interpret = interpret_mode(interpret)
    *lead, M = x.shape
    x2 = x.reshape(-1, M)
    T = x2.shape[0]
    x2, bt = _pad_rows(x2)
    y = _quant_matmul(x2, q, scales, zeros, bt=bt, interpret=interpret)
    return y[:T].reshape(*lead, q.shape[1])


# ---------------------------------------------------------------------------
# Fused quantized-epitome path (the paper's flagship configuration)
# ---------------------------------------------------------------------------
class PackedEpitome(NamedTuple):
    """An epitome packed for the fused kernel: int8 codes + per-block
    (scale, zero).  Pack once (offline / at load), reuse every forward."""
    q: jax.Array          # (m, n) int8
    scales: jax.Array     # (m/bk, n/bn) fp32
    zeros: jax.Array      # (m/bk, n/bn) fp32
    bk: int
    bn: int


def _pick_bk_quant(m: int, tile: int) -> int:
    """Row-block for the quant kernel: never wider than the quantizer's
    crossbar tile, so each kernel block nests inside one scale tile and the
    packed codes stay bit-identical to fake_quant's.  Same menu as
    ``_pick_bk`` (the ragged tail is zero-padded at kernel-call time, never
    inside the quantizer)."""
    return _pick_k_block(m, tile)


def pack_blocks(spec: EpitomeSpec, qcfg: QuantConfig,
                blocks: Optional[tuple] = None) -> tuple:
    """The (bk, bn) kernel block a pack of (spec, qcfg) uses — deterministic,
    so prepacked storage only needs the arrays.  ``blocks`` is an autotuned
    (bt, bk, bn) triple (kernels/autotune.py; plan provenance) overriding
    the heuristic — bt is the activation-side block and does not affect the
    pack layout."""
    if blocks is not None:
        bt, bk, bn = blocks
        assert col_blocks_splittable(spec, bn), (spec, bn)
        return bk, bn
    return _pick_bk_quant(spec.m, qcfg.tile), spec.bn


def pack_epitome(E: jax.Array, spec: EpitomeSpec, qcfg: QuantConfig,
                 blocks: Optional[tuple] = None) -> PackedEpitome:
    """Quantize an epitome into the kernel's storage layout."""
    bk, bn = pack_blocks(spec, qcfg, blocks)
    q, scales, zeros = quantize_epitome_packed(E, spec, qcfg, (bk, bn))
    return PackedEpitome(q, scales, zeros, bk, bn)


def quant_epitome_matmul(x: jax.Array, E: Optional[jax.Array],
                         spec: EpitomeSpec, qcfg: Optional[QuantConfig] = None,
                         *, packed: Optional[PackedEpitome] = None,
                         bt: Optional[int] = None, fused_fold: bool = False,
                         interpret: Optional[bool] = None) -> jax.Array:
    """y = x @ W(deq(Q(E))) via the fused int8-epitome kernel.

    Pass ``packed`` (from pack_epitome) to skip re-quantizing per call —
    the serving path; otherwise E is packed on the fly (jit folds the pack
    into the same program, still one HBM read of int8 codes).

    ``bt`` overrides the per-call ``_pad_rows`` derivation so a fixed
    decode batch reuses one tuned row block instead of re-picking per T;
    ``fused_fold=True`` runs the fold inside the kernel (the folded
    activation never round-trips HBM — decode-path pipelining).  Both come
    from kernels/autotune.py via plan provenance.  The fused-fold kernel
    puts bt on the lane dim of its (Mp, bt) activation block, so its
    default bt is the whole (8-padded) T up to 128 rows and 128 beyond —
    a multiple of 128 or the full dim, as Mosaic requires."""
    interpret = interpret_mode(interpret)
    if packed is None:
        assert E is not None and qcfg is not None
        packed = pack_epitome(E, spec, qcfg)
    *lead, M = x.shape
    x2 = x.reshape(-1, M)
    T = x2.shape[0]
    bk, bn = packed.bk, packed.bn
    q = packed.q
    pad_m = (-spec.m) % bk          # ragged (prime/odd) epitome row count
    if pad_m:
        q = jnp.pad(q, ((0, pad_m), (0, 0)))
    cb = kernel_col_blocks(spec, bn)
    if fused_fold:
        if bt is None:
            bt = min(-(-T // 8) * 8, 128)
        x2p, bt = _pad_rows(x2.astype(jnp.float32), bt)
        gm, bm = spec.gm, spec.bm
        xt = jnp.pad(x2p, ((0, 0), (0, gm * bm - M))).T   # (Mp, Tp)
        y = quant_epitome_matmul_fused_fold(
            xt, q, packed.scales, packed.zeros, cb, spec.row_offsets(),
            bm=bm, bt=bt, bk=bk, bn=bn, interpret=interpret).astype(x.dtype)
        return y[:T, :spec.N].reshape(*lead, spec.N)
    folded, bt = _pad_rows(fold_rows(x2, spec), bt)  # (Tp, m)
    if pad_m:
        folded = jnp.pad(folded, ((0, 0), (0, pad_m)))
    y = quant_epitome_matmul_blocks(
        folded.astype(x.dtype), q, packed.scales, packed.zeros,
        cb, bt=bt, bk=bk, bn=bn, interpret=interpret)
    return y[:T, :spec.N].reshape(*lead, spec.N)
