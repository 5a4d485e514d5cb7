"""Attention: GQA, RoPE (or none), flash-style chunked softmax, sliding windows,
softcapping (gemma2), training + prefill + decode paths.

The training/prefill path is a memory-efficient chunked attention (online
softmax over KV chunks via lax.scan) so 32k-token prefill never materializes
an (S x S) score matrix.  Heads are tensor-parallel over 'model'; the KV
cache at decode is sharded over 'model' on the *sequence* dim so GQA ratios
that don't divide the mesh axis never force padding (DESIGN.md §5).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.layers import apply_linear, init_linear
from .common import apply_rope, shard, softcap, BATCH_AXES, TENSOR_AXIS
from .config import ModelConfig, layer_name as _nm

Array = jax.Array

NEG_INF = -2.0 ** 30   # large-but-finite: keeps fully-masked rows NaN-free

# Dry-run knob: fully unroll the KV-chunk scan so XLA cost analysis counts
# every chunk (while bodies are otherwise counted once).
UNROLL_KV = False


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
def init_attn(key: Array, cfg: ModelConfig, prefix: str = "") -> dict:
    d, hd = cfg.d_model, cfg.hd
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    kq, kk, kv, ko = jax.random.split(key, 4)
    dt = cfg.pdtype
    return {
        "wq": init_linear(kq, d, nq * hd, cfg.ep(d, nq * hd, _nm(prefix, "wq")), bias=cfg.qkv_bias, dtype=dt),
        "wk": init_linear(kk, d, nkv * hd, cfg.ep(d, nkv * hd, _nm(prefix, "wk")), bias=cfg.qkv_bias, dtype=dt),
        "wv": init_linear(kv, d, nkv * hd, cfg.ep(d, nkv * hd, _nm(prefix, "wv")), bias=cfg.qkv_bias, dtype=dt),
        "wo": init_linear(ko, nq * hd, d, cfg.ep(nq * hd, d, _nm(prefix, "wo")), dtype=dt),
    }


def _rope(t: Array, positions: Array, cfg: ModelConfig) -> Array:
    """RoPE, or ``t`` as it is for a model without positional encoding
    (``cfg.rope`` False: jamba)."""
    return apply_rope(t, positions, cfg.rope_theta) if cfg.rope else t


# ---------------------------------------------------------------------------
# Chunked (flash-style) attention — training & prefill
# ---------------------------------------------------------------------------
def _chunk_attn(q, k, v, q_offset, kv_chunk, causal, window, cap):
    """Online-softmax attention: scan over KV chunks.

    q: (B, Sq, H, hd); k/v: (B, Skv, Hkv, hd).  GQA: H = G * Hkv.
    Returns (B, Sq, H, hd).

    Layout note (§Perf): K/V are repeated to the full H query heads BEFORE
    the scan, so every scan carrier is (B, Sq, H, ...) and shards cleanly on
    the 16-way 'model' axis.  The grouped (B, Sq, Hkv, G, ...) layout cannot
    shard (Hkv=8 < 16) — the partitioner then replicates the fp32 carriers
    and re-gathers ~2 GB per KV chunk, which dominated the baseline
    collective term (EXPERIMENTS.md §Perf, change A0).  The K/V repeat costs
    only the chunk-sized buffers (~MBs)."""
    B, Sq, H, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = 1.0 / math.sqrt(hd)
    nchunks = -(-Skv // kv_chunk)
    pad = nchunks * kv_chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    if G > 1:   # GQA: broadcast KV heads up front; shards over 'model'
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    k = shard(k, BATCH_AXES, None, TENSOR_AXIS, None)
    v = shard(v, BATCH_AXES, None, TENSOR_AXIS, None)

    qf = q.astype(jnp.float32)
    q_pos = q_offset + jnp.arange(Sq)

    def body(carry, idx):
        m, l, o = carry                                  # running max/denom/out
        start = idx * kv_chunk
        kc = jax.lax.dynamic_slice_in_dim(k, start, kv_chunk, 1).astype(jnp.float32)
        vc = jax.lax.dynamic_slice_in_dim(v, start, kv_chunk, 1).astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bqhk", qf, kc) * scale
        s = softcap(s, cap)
        kv_pos = start + jnp.arange(kv_chunk)
        mask = kv_pos[None, :] < Skv                     # in-bounds (padding)
        if causal:
            mask = mask & (kv_pos[None, :] <= q_pos[:, None])
        if window is not None and window > 0:
            mask = mask & (kv_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(mask[None, :, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        # zero out masked entries explicitly: a fully-masked chunk would
        # otherwise contribute exp(0)=1 everywhere
        p = p * mask[None, :, None, :]
        l_new = l * alpha + p.sum(axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum("bqhk,bkhd->bqhd", p, vc)
        return (m_new, l_new, o_new), None

    m0 = shard(jnp.full((B, Sq, H), NEG_INF, jnp.float32),
               BATCH_AXES, None, TENSOR_AXIS)
    l0 = shard(jnp.zeros((B, Sq, H), jnp.float32), BATCH_AXES, None, TENSOR_AXIS)
    o0 = shard(jnp.zeros((B, Sq, H, hd), jnp.float32),
               BATCH_AXES, None, TENSOR_AXIS, None)
    (m, l, o), _ = jax.lax.scan(body, (m0, l0, o0), jnp.arange(nchunks),
                                unroll=nchunks if UNROLL_KV else 1)
    o = o / jnp.maximum(l[..., None], 1e-30)
    return o.astype(q.dtype)


def attention(params: dict, x: Array, cfg: ModelConfig, *,
              local: bool = False, positions: Optional[Array] = None,
              kv_chunk: int = 0, return_kv: bool = False,
              prefix: str = ""):
    """Full-sequence causal attention (training / prefill)."""
    kv_chunk = kv_chunk or cfg.attn_kv_chunk
    B, S, d = x.shape
    hd, nq, nkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    if positions is None:
        positions = jnp.arange(S)
    q = apply_linear(params["wq"], x, cfg.ep(d, nq * hd, _nm(prefix, "wq"))).reshape(B, S, nq, hd)
    k = apply_linear(params["wk"], x, cfg.ep(d, nkv * hd, _nm(prefix, "wk"))).reshape(B, S, nkv, hd)
    v = apply_linear(params["wv"], x, cfg.ep(d, nkv * hd, _nm(prefix, "wv"))).reshape(B, S, nkv, hd)
    q = _rope(q, positions, cfg)
    k = _rope(k, positions, cfg)
    # heads tensor-parallel
    q = shard(q, BATCH_AXES, None, TENSOR_AXIS, None)
    k = shard(k, BATCH_AXES, None, TENSOR_AXIS, None)
    v = shard(v, BATCH_AXES, None, TENSOR_AXIS, None)
    window = cfg.window if local else None
    o = _chunk_attn(q, k, v, 0, min(kv_chunk, S), True, window, cfg.attn_softcap)
    o = o.reshape(B, S, nq * hd)
    # Replicate the head-sharded context before the output projection:
    # the flattened head dim is wo's contraction dim, and a sharded
    # contraction turns the down-projection into cross-device partial
    # sums whose addition order differs from the single-device dot —
    # bits drift and the serving cross-geometry contract breaks.  An
    # all-gather here keeps every contraction local and bit-exact.
    o = shard(o, BATCH_AXES, None, None)
    out = apply_linear(params["wo"], o, cfg.ep(nq * hd, d, _nm(prefix, "wo")))
    if return_kv:
        return out, (k, v)
    return out


# ---------------------------------------------------------------------------
# Decode path (single new token against a KV cache)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """Static decode-cache geometry."""
    max_len: int
    batch: int


def init_kv_cache(cfg: ModelConfig, spec: CacheSpec, n: int = 1) -> dict:
    """n stacked caches (one per attn position in a scanned group).

    kv_cache_bits=8: int8 codes + one fp16 scale per (token, head) — the
    paper's per-crossbar scaling applied to the cache (§Perf lever: decode
    is cache-bandwidth-bound at long contexts)."""
    shp = (n, spec.batch, spec.max_len, cfg.n_kv_heads, cfg.hd)
    if cfg.kv_cache_bits == 8:
        sshp = shp[:-1] + (1,)
        return {"k": jnp.zeros(shp, jnp.int8), "v": jnp.zeros(shp, jnp.int8),
                "k_s": jnp.zeros(sshp, jnp.float16),
                "v_s": jnp.zeros(sshp, jnp.float16)}
    return {"k": jnp.zeros(shp, cfg.cdtype), "v": jnp.zeros(shp, cfg.cdtype)}


def quantize_kv(t: Array) -> Tuple[Array, Array]:
    """(…, hd) -> int8 codes + per-(token, head) scale."""
    s = jnp.max(jnp.abs(t), axis=-1, keepdims=True) / 127.0 + 1e-8
    q = jnp.clip(jnp.round(t / s), -127, 127).astype(jnp.int8)
    return q, s.astype(jnp.float16)


def dequantize_kv(q: Array, s: Array, dtype) -> Array:
    return (q.astype(jnp.float32) * s.astype(jnp.float32)).astype(dtype)


def kv_cache_spec(batch_axes, seq_axes):
    """PartitionSpec factory for the cache (layers, B, S, Hkv, hd)."""
    from jax.sharding import PartitionSpec as P
    return P(None, batch_axes, seq_axes, None, None)


def chunked_prefill_attention(params: dict, x: Array, cache: dict,
                              chunk_start: Array, cfg: ModelConfig, *,
                              local: bool = False, valid_len=None,
                              prefix: str = "") -> Tuple[Array, dict]:
    """One prefill *chunk* against a running dense cache.

    x: (B, C, d) — the chunk's embeddings; cache k/v: (B, Smax, Hkv, hd)
    holding every earlier chunk's K/V; chunk_start: traced scalar row
    offset of this chunk.  Writes the chunk's K/V at chunk_start and
    attends the chunk's queries over the whole cache with the same
    online-softmax kernel the one-shot path uses (q_offset carries the
    causal mask; rows beyond chunk_start + C are exact zeros from the
    fresh cache, masked to exact-zero probability).  Bit-identical to the
    one-shot prefill for Smax <= attn_kv_chunk (one KV chunk — the smoke
    and CI regime); beyond that the two paths tile the online softmax at
    different boundaries.  Requires a float cache (the engine disables
    chunking for kv_cache_bits=8: re-reading dequantized int8 rows in
    chunk 2 would not be bit-identical to one-shot's fresh fp K/V).

    Pad rows of a final partial chunk (valid_len < C) need no masking
    here: their outputs are discarded, and their garbage K/V rows sit at
    positions the causal mask hides until sequential decode overwrites
    them — exactly the bucketed one-shot path's pad-row mechanism.
    """
    if cfg.kv_cache_bits == 8:
        raise NotImplementedError(
            "chunked prefill requires a float KV cache (kv_cache_bits=16)")
    B, C, d = x.shape
    hd, nq, nkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    Smax = cache["k"].shape[1]
    positions = chunk_start + jnp.arange(C)
    q = apply_linear(params["wq"], x, cfg.ep(d, nq * hd, _nm(prefix, "wq"))).reshape(B, C, nq, hd)
    k = apply_linear(params["wk"], x, cfg.ep(d, nkv * hd, _nm(prefix, "wk"))).reshape(B, C, nkv, hd)
    v = apply_linear(params["wv"], x, cfg.ep(d, nkv * hd, _nm(prefix, "wv"))).reshape(B, C, nkv, hd)
    q = _rope(q, positions, cfg)
    k = _rope(k, positions, cfg)
    q = shard(q, BATCH_AXES, None, TENSOR_AXIS, None)
    cache = dict(cache)
    cache["k"] = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), chunk_start, 1)
    cache["v"] = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), chunk_start, 1)
    window = cfg.window if local else None
    o = _chunk_attn(q, cache["k"], cache["v"], chunk_start,
                    min(cfg.attn_kv_chunk, Smax), True, window,
                    cfg.attn_softcap)
    o = o.reshape(B, C, nq * hd)
    o = shard(o, BATCH_AXES, None, None)   # replicate wo's contraction dim
    out = apply_linear(params["wo"], o, cfg.ep(nq * hd, d, _nm(prefix, "wo")))
    return out, cache


def decode_attention(params: dict, x: Array, cache: dict,
                     pos: Array, cfg: ModelConfig, *, local: bool = False,
                     page_table: Optional[Array] = None,
                     prefix: str = "") -> Tuple[Array, dict]:
    """One decode step.  x: (B, 1, d); cache: {k, v[, k_s, v_s]} with
    k/v (B, Smax, Hkv, hd); pos: scalar int32 write index, or a (B,)
    vector of per-row write indices (continuous batching: every slot of
    the engine's state pool decodes at its own position).  Per-row values
    are bit-identical to the scalar path at the same position — the
    vector form only changes where cache rows are written and how the
    causal mask broadcasts.  Returns (out, new cache).

    page_table — block-paged mode (requires per-row pos): cache k/v are a
    global page pool (num_pages + trash, page_size, Hkv, hd) shared by
    all slots, and page_table (B, pages_per_slot) maps each row's logical
    pages to physical ones.  The step's K/V lands at the physical row
    pos // page_size resolves to; attention gathers each row's pages back
    into a dense (B, Lg, Hkv, hd) view and proceeds exactly as the dense
    path — same mask, same softmax, same einsums — so paged values are
    bit-identical to dense at equal gathered length.  Rows gathered from
    the trash page (unmapped entries) may hold other slots' garbage; the
    causal mask turns them into exact-zero probabilities, and vc is
    zeroed under the mask so even NaN garbage cannot poison the output
    (0 * NaN is NaN; where(mask, ·, 0) is not)."""
    B, _, d = x.shape
    hd, nq, nkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    G = nq // nkv
    per_row = jnp.ndim(pos) == 1                   # (B,) per-slot positions
    paged = page_table is not None
    if paged:
        if not per_row:
            raise ValueError("page_table requires per-row (B,) positions")
        page = cache["k"].shape[1]
        Smax = page_table.shape[1] * page          # gathered rows per slot
    else:
        Smax = cache["k"].shape[1]
    q = apply_linear(params["wq"], x, cfg.ep(d, nq * hd, _nm(prefix, "wq"))).reshape(B, 1, nq, hd)
    k = apply_linear(params["wk"], x, cfg.ep(d, nkv * hd, _nm(prefix, "wk"))).reshape(B, 1, nkv, hd)
    v = apply_linear(params["wv"], x, cfg.ep(d, nkv * hd, _nm(prefix, "wv"))).reshape(B, 1, nkv, hd)
    posv = (pos[:, None] if per_row else
            jnp.full((1,), pos, jnp.int32) if jnp.ndim(pos) == 0 else pos[None])
    q = _rope(q, posv, cfg)
    k = _rope(k, posv, cfg)
    cache = dict(cache)
    if paged:
        # physical row of each slot's current token, then one flat scatter
        phys = jnp.take_along_axis(page_table, (pos // page)[:, None], 1)[:, 0]
        flat = phys * page + pos % page                          # (B,)
        upd = lambda c, t: c.reshape((-1,) + c.shape[2:]).at[flat].set(
            t[:, 0].astype(c.dtype)).reshape(c.shape)
        full = lambda c: c[page_table].reshape((B, Smax) + c.shape[2:])
    elif per_row:
        upd = lambda c, t: jax.vmap(
            lambda cb, tb, pb: jax.lax.dynamic_update_slice_in_dim(
                cb, tb.astype(cb.dtype), pb, 0))(c, t, pos)
        full = lambda c: c
    else:
        upd = lambda c, t: jax.lax.dynamic_update_slice_in_dim(
            c, t.astype(c.dtype), pos, 1)
        full = lambda c: c
    if cfg.kv_cache_bits == 8:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        cache["k"], cache["k_s"] = upd(cache["k"], kq), upd(cache["k_s"], ks)
        cache["v"], cache["v_s"] = upd(cache["v"], vq), upd(cache["v_s"], vs)
        kc = dequantize_kv(full(cache["k"]), full(cache["k_s"]), jnp.float32)
        vc = dequantize_kv(full(cache["v"]), full(cache["v_s"]), jnp.float32)
    else:
        cache["k"] = upd(cache["k"], k)
        cache["v"] = upd(cache["v"], v)
        kc = full(cache["k"]).astype(jnp.float32)
        vc = full(cache["v"]).astype(jnp.float32)

    qg = q.reshape(B, nkv, G, hd).astype(jnp.float32)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, kc) / math.sqrt(hd)
    s = softcap(s, cfg.attn_softcap)
    kv_pos = jnp.arange(Smax)
    if per_row:
        rmask = kv_pos[None, :] <= pos[:, None]            # (B, Smax)
        if local and cfg.window:
            rmask = rmask & (kv_pos[None, :] > pos[:, None] - cfg.window)
        mask = rmask[:, None, None, :]
    else:
        rmask = kv_pos[None, :] <= pos
        if local and cfg.window:
            rmask = rmask & (kv_pos[None, :] > pos - cfg.window)
        mask = rmask[:, None, None, :]
    # masked rows get exact-zero probability via exp underflow at NEG_INF,
    # but 0 * NaN = NaN: zero vc under the mask so garbage rows (trash
    # page, freed-slot scribbles) can never reach the output
    vc = jnp.where(rmask[..., None, None], vc, 0.0)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p, vc)
    o = o.reshape(B, 1, nq * hd).astype(x.dtype)
    o = shard(o, BATCH_AXES, None, None)   # replicate wo's contraction dim
    out = apply_linear(params["wo"], o, cfg.ep(nq * hd, d, _nm(prefix, "wo")))
    return out, cache
