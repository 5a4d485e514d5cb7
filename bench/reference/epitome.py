"""Plain epitome weights: sampler, 3-bit quantizer and keyed init.

This is the benchmark's own statement of what an epitomized weight is.  It
imports nothing of the program.  Everything it needs is in the
configuration file: each layer's virtual shape (M, N), epitome shape
(m, n), patch (bm, bn) and the quantizer's settings.

* Sampler (EPIM, arXiv:2311.07620, Eq. 1).  W is tiled by (bm, bn)
  patches; patch (i, j) is read from E at (row_off[i], col_off[j]), the
  offsets spread evenly over E.
* Quantizer (EPIM Sec. 4.2).  Asymmetric codes of ``bits`` bits, one
  (scale, zero) pair per crossbar tile of ``tile`` x ``tile``.  A tile's
  range is its own (min, max), clipped by the overlap-weighted range of
  the whole epitome (w1 on the cells that more than the fewest patches
  cover, w2 on the rest); a tile whose clipped range inverts takes the
  overlap-weighted range.  Dense layers (no spec) take the per-tile range
  alone.  codes = clip(round(E / S) - Z, 0, 2^bits - 1), W = (codes + Z) S.
* Init.  Epitomes are N(0, 1) / sqrt(M) from the layer's key; dense conv
  weights N(0, 1) / sqrt(kh kw cin).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def spread_offsets(m: int, bm: int, g: int) -> np.ndarray:
    if g <= 1 or m == bm:
        return np.zeros(g, dtype=np.int64)
    return np.round(np.linspace(0, m - bm, g)).astype(np.int64)


def index_map(M: int, bm: int, offsets: np.ndarray) -> np.ndarray:
    idx = np.empty(M, dtype=np.int64)
    for i, off in enumerate(offsets):
        lo, hi = i * bm, min(M, (i + 1) * bm)
        idx[lo:hi] = off + np.arange(hi - lo)
    return idx


def row_map(spec: dict) -> np.ndarray:
    gm = -(-spec["M"] // spec["bm"])
    return index_map(spec["M"], spec["bm"],
                     spread_offsets(spec["m"], spec["bm"], gm))


def col_map(spec: dict) -> np.ndarray:
    gn = -(-spec["N"] // spec["bn"])
    return index_map(spec["N"], spec["bn"],
                     spread_offsets(spec["n"], spec["bn"], gn))


def overlap_mask(spec: dict) -> np.ndarray:
    """Cells covered by more patches than the least-covered cells."""
    def counts(m, bm, M):
        g = -(-M // bm)
        c = np.zeros(m, dtype=np.int64)
        for i, off in enumerate(spread_offsets(m, bm, g)):
            c[off:off + min(bm, M - i * bm)] += 1
        return c
    cnt = (counts(spec["m"], spec["bm"], spec["M"])[:, None]
           * counts(spec["n"], spec["bn"], spec["N"])[None, :])
    pos = cnt[cnt > 0]
    if pos.size == 0:
        return np.zeros_like(cnt, dtype=bool)
    return cnt > pos.min()


def _tile_reduce(x, tile: int, fn):
    m, n = x.shape
    gm, gn = -(-m // tile), -(-n // tile)
    x = jnp.pad(x, ((0, gm * tile - m), (0, gn * tile - n)), mode="edge")
    return fn(x.reshape(gm, tile, gn, tile), axis=(1, 3))


def _expand(t, tile: int, shape):
    return jnp.repeat(jnp.repeat(t, tile, 0), tile, 1)[:shape[0], :shape[1]]


def tables(spec: dict) -> dict:
    """The sampler's index maps and the overlap mask of one epitome, made
    on the host and handed to the device as arguments (as constants they
    would be folded into every program that reads them)."""
    return {"rows": row_map(spec), "cols": col_map(spec),
            "mask": overlap_mask(spec)}


def quantize(E, spec: Optional[dict], quant: dict, mask=None):
    """The dequantized weights (codes + Z) * S of E, float32.  ``mask`` is
    the overlap mask of an epitome (``tables``), None for a dense layer."""
    tile, levels = quant["tile"], (1 << quant["bits"]) - 1
    a_t = _tile_reduce(E, tile, jnp.min)
    b_t = _tile_reduce(E, tile, jnp.max)
    if spec is not None:
        host = overlap_mask(spec)
        big = jnp.asarray(jnp.finfo(E.dtype).max, E.dtype)
        mn_o, mx_o = jnp.min(jnp.where(mask, E, big)), jnp.max(jnp.where(mask, E, -big))
        mn_r, mx_r = jnp.min(jnp.where(mask, big, E)), jnp.max(jnp.where(mask, -big, E))
        if not host.any():
            mn_o, mx_o = mn_r, mx_r
        if host.all():
            mn_r, mx_r = mn_o, mx_o
        w1, w2 = quant["w1"], quant["w2"]
        a_g, b_g = w1 * mn_o + w2 * mn_r, w1 * mx_o + w2 * mx_r
        a_t, b_t = jnp.maximum(a_t, a_g), jnp.minimum(b_t, b_g)
        bad = a_t >= b_t
        a_t = jnp.where(bad, a_g, a_t)
        b_t = jnp.where(bad, b_g, b_t)
    S = (b_t - a_t) / levels
    Z = jnp.round(a_t / jnp.maximum(S, 1e-12))
    S = jnp.maximum(S, 1e-12)
    S, Z = _expand(S, tile, E.shape), _expand(Z, tile, E.shape)
    codes = jnp.clip(jnp.round(E / S) - Z, 0, levels)
    return (codes + Z) * S


def _take(x, host_idx: np.ndarray, idx, axis: int):
    """x gathered along ``axis`` by ``idx`` (the device copy of
    ``host_idx``), skipped where the map is the identity."""
    if np.array_equal(host_idx, np.arange(x.shape[axis])):
        return x
    return jnp.take(x, idx, axis=axis)


def epitome_weight(key, spec: dict, quant: dict, tab: dict):
    """W (M, N) float32 of one epitomized layer drawn from ``key``; ``tab``
    is ``tables(spec)``, passed in as device arrays."""
    E = (jax.random.normal(key, (spec["m"], spec["n"]))
         * (1.0 / math.sqrt(spec["M"]))).astype(jnp.float32)
    E = quantize(E, spec, quant, tab["mask"])
    E = _take(E, row_map(spec), tab["rows"], 0)
    return _take(E, col_map(spec), tab["cols"], 1)
