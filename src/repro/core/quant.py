"""Epitome-aware quantization (EPIM §4.2, Eqs. 2-5, Table 2).

Three ingredients, reproduced faithfully and composable:

1. *naive*      — one (alpha, beta) = (min, max) range for the whole tensor.
2. *+crossbar*  — one scaling factor per crossbar-sized tile (the paper: PIM
                  crossbars compute in parallel, so per-crossbar scales cost
                  nothing; TPU analogue: per-128/256-tile scales, dequantized
                  inside the matmul kernel).
3. *+overlap*   — the range is a weighted sum of the min/max over the
                  high-repetition ("overlap", green) region and the rest
                  (Eq. 4-5): alpha = w1*min_ovl + w2*min_other, etc.

Asymmetric affine quantization per Eq. 2-3:
    Q(r) = round(r / S) - Z,   S = (beta - alpha) / (2^k - 1)

Fake-quant (QAT) uses a straight-through estimator so epitomes can be
trained under quantization, matching the paper's retraining of quantized
models (§7.1).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .epitome import EpitomeSpec, overlap_mask

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    bits: int = 8
    per_crossbar: bool = True        # paper's "+ Adjust with Crossbars"
    overlap_weighted: bool = True    # paper's "+ Adjusted with Overlap"
    w1: float = 0.7                  # weight of the overlap (center) region
    w2: float = 0.3                  # weight of the rest  (w1 + w2 = 1)
    tile: int = 256                  # crossbar size (PIM) / scale-tile (TPU)
    symmetric: bool = False

    @property
    def levels(self) -> int:
        return (1 << self.bits) - 1


# ---------------------------------------------------------------------------
# Range selection
# ---------------------------------------------------------------------------
def _masked_min_max(x: Array, mask: Array) -> Tuple[Array, Array]:
    big = jnp.asarray(jnp.finfo(x.dtype).max, x.dtype)
    mn = jnp.min(jnp.where(mask, x, big))
    mx = jnp.max(jnp.where(mask, x, -big))
    return mn, mx


def overlap_weighted_range(E: Array, spec: EpitomeSpec, w1: float, w2: float) -> Tuple[Array, Array]:
    """Eq. 4-5: weighted min/max over the overlap region vs. the rest."""
    # the mask is a host constant: decide its degenerate cases here, since
    # XLA constant-folds an in-graph any/all over an (m, n) mask for
    # seconds per layer shape at real widths
    mask = overlap_mask(spec)
    m = jnp.asarray(mask)
    mn_o, mx_o = _masked_min_max(E, m)
    mn_r, mx_r = _masked_min_max(E, ~m)
    # degenerate cases: everything (or nothing) is overlap -> plain min/max
    if not mask.any():
        mn_o, mx_o = mn_r, mx_r
    if mask.all():
        mn_r, mx_r = mn_o, mx_o
    alpha = w1 * mn_o + w2 * mn_r
    beta = w1 * mx_o + w2 * mx_r
    return alpha, beta


def tensor_range(x: Array) -> Tuple[Array, Array]:
    return jnp.min(x), jnp.max(x)


# ---------------------------------------------------------------------------
# Affine quantize / dequantize (Eq. 2-3)
# ---------------------------------------------------------------------------
def scale_zero(alpha: Array, beta: Array, cfg: QuantConfig) -> Tuple[Array, Array]:
    if cfg.symmetric:
        amax = jnp.maximum(jnp.abs(alpha), jnp.abs(beta))
        S = (2 * amax) / cfg.levels
        Z = jnp.zeros_like(S)
    else:
        S = (beta - alpha) / cfg.levels
        Z = jnp.round(alpha / jnp.maximum(S, 1e-12))
    S = jnp.maximum(S, 1e-12)
    return S, Z


def quantize(x: Array, S: Array, Z: Array, cfg: QuantConfig) -> Array:
    q = jnp.round(x / S) - Z
    lo = -(1 << (cfg.bits - 1)) if cfg.symmetric else 0
    hi = lo + cfg.levels
    return jnp.clip(q, lo, hi)


def dequantize(q: Array, S: Array, Z: Array) -> Array:
    return (q + Z) * S


# ---------------------------------------------------------------------------
# Per-crossbar tiling
# ---------------------------------------------------------------------------
def _tile_reduce(x: Array, tile: int, fn) -> Array:
    """Reduce (m, n) -> (gm, gn) per (tile x tile) block, ragged edges ok:
    edge-replicated padding duplicates values already inside the ragged
    tile, so it is neutral under min/max."""
    m, n = x.shape
    gm, gn = -(-m // tile), -(-n // tile)
    pm, pn = gm * tile - m, gn * tile - n
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)), mode="edge")
    blocks = x.reshape(gm, tile, gn, tile).transpose(0, 2, 1, 3)
    return fn(blocks, axis=(2, 3))


def per_crossbar_range(E: Array, cfg: QuantConfig) -> Tuple[Array, Array]:
    """(alpha, beta) per crossbar tile, shape (gm, gn)."""
    mn = _tile_reduce(E, cfg.tile, jnp.min)
    mx = _tile_reduce(E, cfg.tile, jnp.max)
    return mn, mx


def _expand_tiles(t: Array, shape: Tuple[int, int], tile: int) -> Array:
    m, n = shape
    return jnp.repeat(jnp.repeat(t, tile, 0), tile, 1)[:m, :n]


# ---------------------------------------------------------------------------
# The full epitome-aware quantizer
# ---------------------------------------------------------------------------
def epitome_ranges(E: Array, spec: Optional[EpitomeSpec], cfg: QuantConfig) -> Tuple[Array, Array]:
    """Produce (alpha, beta) maps of E.shape combining both paper tricks."""
    if cfg.overlap_weighted and spec is not None:
        a_g, b_g = overlap_weighted_range(E, spec, cfg.w1, cfg.w2)
    else:
        a_g, b_g = tensor_range(E)

    if cfg.per_crossbar:
        a_t, b_t = per_crossbar_range(E, cfg)
        if cfg.overlap_weighted and spec is not None:
            # blend: per-crossbar range, softly clipped toward the
            # overlap-weighted global range (the global range acts as the
            # outlier-robust envelope; per-tile adapts locally).
            a_t = jnp.maximum(a_t, a_g)   # alpha_g <= 0 <= beta_g typically
            b_t = jnp.minimum(b_t, b_g)
            # never allow an inverted range
            bad = a_t >= b_t
            a_t = jnp.where(bad, _bcast(a_g, a_t), a_t)
            b_t = jnp.where(bad, _bcast(b_g, b_t), b_t)
        alpha = _expand_tiles(a_t, E.shape, cfg.tile)
        beta = _expand_tiles(b_t, E.shape, cfg.tile)
    else:
        alpha = jnp.broadcast_to(a_g, E.shape)
        beta = jnp.broadcast_to(b_g, E.shape)
    return alpha, beta


def _bcast(g, t):
    return jnp.broadcast_to(g, t.shape)


def quantize_epitome(E: Array, spec: Optional[EpitomeSpec], cfg: QuantConfig
                     ) -> Tuple[Array, Array, Array]:
    """Returns (q_int, S, Z) where S/Z have E's shape (expanded tiles)."""
    alpha, beta = epitome_ranges(E, spec, cfg)
    S, Z = scale_zero(alpha, beta, cfg)
    q = quantize(E, S, Z, cfg)
    return q, S, Z


def dequantize_epitome(q: Array, S: Array, Z: Array) -> Array:
    return dequantize(q, S, Z)


# ---------------------------------------------------------------------------
# Packed (int8-storage) quantization — the kernel-side contract
# ---------------------------------------------------------------------------
def _block_reduce(x: Array, bk: int, bn: int, fn) -> Array:
    """Reduce an (m, n) map to (ceil(m/bk), ceil(n/bn)) per (bk x bn)
    block.  Ragged edges (prime/odd m) are edge-replicated like
    ``_tile_reduce``: the padding duplicates values already inside the
    ragged block, so it is neutral under min/max — the ranges of real rows
    never see the kernel-side zero padding."""
    m, n = x.shape
    gm, gn = -(-m // bk), -(-n // bn)
    pm, pn = gm * bk - m, gn * bn - n
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)), mode="edge")
    return fn(x.reshape(gm, bk, gn, bn), axis=(1, 3))


def _expand_blocks(t: Array, bk: int, bn: int) -> Array:
    return jnp.repeat(jnp.repeat(t, bk, 0), bn, 1)


def code_shift(cfg: QuantConfig) -> int:
    """Shift folding the unsigned code range into int8: storing q - shift and
    z + shift leaves (q + z) * s unchanged (symmetric codes are already
    signed, shift 0)."""
    return 0 if cfg.symmetric else 1 << (cfg.bits - 1)


def quantize_epitome_packed(E: Array, spec: Optional[EpitomeSpec],
                            cfg: QuantConfig, block: Tuple[int, int]
                            ) -> Tuple[Array, Array, Array]:
    """Pack an epitome for the fused quant kernel.

    Returns (q, scales, zeros): q is (m, n) **int8** codes; scales/zeros are
    (m/bk, n/bn) fp32, one pair per kernel block — the per-crossbar-tile
    contract of kernels/quant_epitome_matmul.  Ranges come from the same
    epitome-aware machinery as fake_quant (overlap-weighted + per-crossbar,
    Eq. 4-5), reduced to one envelope per block, so when blocks nest inside
    ``cfg.tile`` crossbars the codes are bit-identical to fake_quant's.
    """
    bk, bn = block
    m, n = E.shape
    alpha, beta = epitome_ranges(E, spec, cfg)
    a_b = _block_reduce(alpha, bk, bn, jnp.min)
    b_b = _block_reduce(beta, bk, bn, jnp.max)
    S, Z = scale_zero(a_b, b_b, cfg)
    # trim the expanded maps back to (m, n) when blocks tile raggedly —
    # the scale grid keeps its ceil shape (the kernel zero-pads q's rows
    # instead, which the zero-padded folded activation makes dot-neutral)
    q = quantize(E, _expand_blocks(S, bk, bn)[:m, :n],
                 _expand_blocks(Z, bk, bn)[:m, :n], cfg)
    shift = code_shift(cfg)
    return (q - shift).astype(jnp.int8), S, Z + shift


def dequantize_packed(q: Array, scales: Array, zeros: Array,
                      block: Tuple[int, int]) -> Array:
    """Inverse of quantize_epitome_packed (the jnp oracle the kernel's
    in-register dequant is tested against): (q + z) * s per block."""
    bk, bn = block
    m, n = q.shape
    S = _expand_blocks(scales, bk, bn)[:m, :n]
    Z = _expand_blocks(zeros, bk, bn)[:m, :n]
    return (q.astype(jnp.float32) + Z) * S


# ---------------------------------------------------------------------------
# Fake quant with straight-through estimator (for QAT retraining, §7.1)
# ---------------------------------------------------------------------------
@jax.custom_vjp
def _ste(x: Array, y: Array) -> Array:
    return y


def _ste_fwd(x, y):
    return y, None


def _ste_bwd(_, g):
    return g, None


_ste.defvjp(_ste_fwd, _ste_bwd)


def fake_quant(E: Array, spec: Optional[EpitomeSpec], cfg: QuantConfig) -> Array:
    q, S, Z = quantize_epitome(E, spec, cfg)
    return _ste(E, dequantize(q, S, Z).astype(E.dtype))


def quant_mse(E: Array, spec: Optional[EpitomeSpec], cfg: QuantConfig) -> Array:
    """Reconstruction MSE of quantize->dequantize — the offline proxy used to
    validate the Table 2 ordering (naive > +crossbar > +overlap)."""
    q, S, Z = quantize_epitome(E, spec, cfg)
    return jnp.mean((dequantize(q, S, Z) - E) ** 2)
