"""Decoder blocks: one "group" = the smallest repeating super-block of a
model (jamba's attn+7xmamba, gemma2's local/global pair, or a single layer).
Group params are stacked over repeats; lm.py scans over them."""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.layers import apply_linear, init_linear
from .attention import (
    attention, chunked_prefill_attention, decode_attention, init_attn,
)
from .common import act_fn, init_rms_norm, rms_norm, shard, BATCH_AXES, TENSOR_AXIS
from .config import LayerKind, ModelConfig, layer_name as _nm
from .moe import init_moe, moe_ffn, moe_held
from .ssm import (
    init_mamba, init_rwkv, init_rwkv_ffn,
    mamba_mix, rwkv_channel_mix, rwkv_time_mix,
)

Array = jax.Array


# ---------------------------------------------------------------------------
# FFN (SwiGLU / gelu-MLP)
# ---------------------------------------------------------------------------
def init_ffn(key: Array, cfg: ModelConfig, prefix: str = "") -> dict:
    d, ff = cfg.d_model, cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    dt = cfg.pdtype
    return {
        "w_gate": init_linear(k1, d, ff, cfg.ep(d, ff, _nm(prefix, "w_gate")), dtype=dt),
        "w_up": init_linear(k2, d, ff, cfg.ep(d, ff, _nm(prefix, "w_up")), dtype=dt),
        "w_down": init_linear(k3, ff, d, cfg.ep(ff, d, _nm(prefix, "w_down")), dtype=dt),
    }


def ffn(params: dict, x: Array, cfg: ModelConfig, prefix: str = "") -> Array:
    d, ff = cfg.d_model, cfg.d_ff
    act = act_fn(cfg.act)
    g = apply_linear(params["w_gate"], x, cfg.ep(d, ff, _nm(prefix, "w_gate")))
    u = apply_linear(params["w_up"], x, cfg.ep(d, ff, _nm(prefix, "w_up")))
    h = act(g) * u
    # Replicate the hidden dim before w_down: it is w_down's contraction
    # dim, and keeping it tensor-sharded (Megatron row-parallel) turns the
    # down-projection into cross-device partial sums whose addition order
    # differs from the single-device dot — bits drift and the serving
    # cross-geometry bit-exactness contract breaks.  All-gather here keeps
    # every contraction local.
    h = shard(h, BATCH_AXES, None, None)
    return apply_linear(params["w_down"], h, cfg.ep(ff, d, _nm(prefix, "w_down")))


# ---------------------------------------------------------------------------
# One group (super-block)
# ---------------------------------------------------------------------------
def init_group(key: Array, cfg: ModelConfig) -> Dict[str, Any]:
    params: Dict[str, Any] = {}
    keys = jax.random.split(key, len(cfg.full_pattern))
    for i, (kind, ffn_kind) in enumerate(cfg.full_pattern):
        k_mix, k_ffn = jax.random.split(keys[i])
        layer: Dict[str, Any] = {"norm1": init_rms_norm(cfg.d_model, cfg.pdtype)}
        mixer_p, ffn_p = f"L{i}/mixer", f"L{i}/ffn"
        if kind in (LayerKind.ATTN.value, LayerKind.ATTN_LOCAL.value):
            layer["mixer"] = init_attn(k_mix, cfg, prefix=mixer_p)
        elif kind == LayerKind.MAMBA.value:
            layer["mixer"] = init_mamba(k_mix, cfg, prefix=mixer_p)
        elif kind == LayerKind.RWKV.value:
            layer["mixer"] = init_rwkv(k_mix, cfg, prefix=mixer_p)
        else:
            raise ValueError(kind)
        if ffn_kind != "none":
            layer["norm2"] = init_rms_norm(cfg.d_model, cfg.pdtype)
        if ffn_kind == "dense":
            layer["ffn"] = init_ffn(k_ffn, cfg, prefix=ffn_p)
        elif ffn_kind == "moe":
            layer["ffn"] = init_moe(k_ffn, cfg, prefix=ffn_p)
        elif ffn_kind == "rwkv_ffn":
            layer["ffn"] = init_rwkv_ffn(k_ffn, cfg, prefix=ffn_p)
        params[f"L{i}"] = layer
    return params


def apply_group(params: Dict[str, Any], x: Array, cfg: ModelConfig,
                positions: Optional[Array] = None) -> Array:
    """Training / prefill forward through one super-block."""
    for i, (kind, ffn_kind) in enumerate(cfg.full_pattern):
        layer = params[f"L{i}"]
        mixer_p, ffn_p = f"L{i}/mixer", f"L{i}/ffn"
        h = rms_norm(x, layer["norm1"], cfg.norm_eps)
        if kind == LayerKind.ATTN.value:
            mix = attention(layer["mixer"], h, cfg, local=False,
                            positions=positions, prefix=mixer_p)
        elif kind == LayerKind.ATTN_LOCAL.value:
            mix = attention(layer["mixer"], h, cfg, local=True,
                            positions=positions, prefix=mixer_p)
        elif kind == LayerKind.MAMBA.value:
            mix, _ = mamba_mix(layer["mixer"], h, cfg, prefix=mixer_p)
        elif kind == LayerKind.RWKV.value:
            mix, _ = rwkv_time_mix(layer["mixer"], h, cfg, prefix=mixer_p)
        x = x + mix
        x = (shard(x, BATCH_AXES, TENSOR_AXIS, None)   # seq-parallel residual
             if cfg.seq_shard_residual else shard(x, BATCH_AXES, None, None))
        if ffn_kind == "none":
            continue
        h = rms_norm(x, layer["norm2"], cfg.norm_eps)
        if ffn_kind == "dense":
            f = ffn(layer["ffn"], h, cfg, prefix=ffn_p)
        elif ffn_kind == "moe":
            f = moe_ffn(layer["ffn"], h, cfg, prefix=ffn_p)
        elif ffn_kind == "rwkv_ffn":
            f, _ = rwkv_channel_mix(layer["ffn"], h, cfg, prefix=ffn_p)
        x = x + f
        x = (shard(x, BATCH_AXES, TENSOR_AXIS, None)
             if cfg.seq_shard_residual else shard(x, BATCH_AXES, None, None))
    return x


def prefill_group(params: Dict[str, Any], state: Dict[str, Any], x: Array,
                  cfg: ModelConfig, positions: Optional[Array] = None,
                  valid_len: Optional[Array] = None,
                  chunk_start: Optional[Array] = None
                  ) -> Tuple[Array, Dict[str, Any]]:
    """Full-sequence forward that also fills the decode state (KV caches are
    written into the pre-allocated max_len buffers of ``state``).

    ``valid_len`` (traced scalar) marks a right-padded bucketed prefill
    (launch/engine.py): only the first ``valid_len`` tokens are real.  The
    SSM mixers mask their recurrences so pads leave the carried state
    exactly as it stood after the last real token; attention needs no
    masking — pad K/V beyond ``valid_len - 1`` are causally invisible to
    real queries and get overwritten by decode steps before any mask ever
    reaches them.

    ``chunk_start`` (traced scalar) marks a *chunked* prefill: x is one
    chunk of the prompt starting at that sequence offset, and ``state``
    carries everything earlier chunks built (KV rows below chunk_start,
    SSM recurrent state after the last earlier token).  Attention layers
    route through chunked_prefill_attention (write at chunk_start, attend
    over the running cache); the SSM mixers need no routing — the carried
    state is their whole past, and ``valid_len`` masking already makes a
    padded final chunk match the one-shot path's internal zero-padded
    windows (launch/engine.py aligns the chunk size to rwkv_chunk /
    mamba_chunk so chunk boundaries coincide with the one-shot scan's own
    window boundaries — that alignment, not this function, is what makes
    chunked recurrences bit-identical)."""
    for i, (kind, ffn_kind) in enumerate(cfg.full_pattern):
        layer = params[f"L{i}"]
        st = state[f"L{i}"]
        ns = dict(st)
        mixer_p, ffn_p = f"L{i}/mixer", f"L{i}/ffn"
        h = rms_norm(x, layer["norm1"], cfg.norm_eps)
        if (kind in (LayerKind.ATTN.value, LayerKind.ATTN_LOCAL.value)
                and chunk_start is not None):
            mix, new_cache = chunked_prefill_attention(
                layer["mixer"], h, {"k": st["k"], "v": st["v"]}, chunk_start,
                cfg, local=(kind == LayerKind.ATTN_LOCAL.value),
                valid_len=valid_len, prefix=mixer_p)
            ns.update(new_cache)
        elif kind in (LayerKind.ATTN.value, LayerKind.ATTN_LOCAL.value):
            mix, (k, v) = attention(layer["mixer"], h, cfg,
                                    local=(kind == LayerKind.ATTN_LOCAL.value),
                                    positions=positions, return_kv=True,
                                    prefix=mixer_p)
            if cfg.kv_cache_bits == 8:
                from .attention import quantize_kv
                kq, ks = quantize_kv(k)
                vq, vs = quantize_kv(v)
                wr = lambda c, t: jax.lax.dynamic_update_slice_in_dim(
                    c, t.astype(c.dtype), 0, 1)
                ns["k"], ns["k_s"] = wr(st["k"], kq), wr(st["k_s"], ks)
                ns["v"], ns["v_s"] = wr(st["v"], vq), wr(st["v_s"], vs)
            else:
                ns["k"] = jax.lax.dynamic_update_slice_in_dim(
                    st["k"], k.astype(st["k"].dtype), 0, 1)
                ns["v"] = jax.lax.dynamic_update_slice_in_dim(
                    st["v"], v.astype(st["v"].dtype), 0, 1)
        elif kind == LayerKind.MAMBA.value:
            mix, (conv, hst) = mamba_mix(layer["mixer"], h, cfg,
                                         state=(st["conv"].astype(h.dtype), st["h"]),
                                         prefix=mixer_p, valid_len=valid_len)
            ns["conv"], ns["h"] = conv.astype(st["conv"].dtype), hst
        elif kind == LayerKind.RWKV.value:
            mix, (xp, s) = rwkv_time_mix(layer["mixer"], h, cfg,
                                         state=(st["x_prev"].astype(h.dtype), st["s"]),
                                         prefix=mixer_p, valid_len=valid_len)
            ns["x_prev"], ns["s"] = xp.astype(st["x_prev"].dtype), s
        x = x + mix
        if ffn_kind != "none":
            h = rms_norm(x, layer["norm2"], cfg.norm_eps)
            if ffn_kind == "dense":
                f = ffn(layer["ffn"], h, cfg, prefix=ffn_p)
            elif ffn_kind == "moe":
                f = moe_ffn(layer["ffn"], h, cfg, prefix=ffn_p)
            elif ffn_kind == "rwkv_ffn":
                f, xp2 = rwkv_channel_mix(layer["ffn"], h, cfg,
                                          x_prev=st.get("ffn_x_prev", jnp.zeros(
                                              (x.shape[0], cfg.d_model), x.dtype)).astype(h.dtype),
                                          prefix=ffn_p, valid_len=valid_len)
                ns["ffn_x_prev"] = xp2.astype(cfg.cdtype)
            x = x + f
        state = {**state, f"L{i}": ns}
    return x, state


# ---------------------------------------------------------------------------
# Decode: one token through one group, updating per-layer state
# ---------------------------------------------------------------------------
def init_group_state(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    """Decode state for ONE group (lm.py stacks over groups via vmap)."""
    from .attention import CacheSpec, init_kv_cache
    from .ssm import init_mamba_state, init_rwkv_state
    state: Dict[str, Any] = {}
    for i, (kind, ffn_kind) in enumerate(cfg.full_pattern):
        if kind in (LayerKind.ATTN.value, LayerKind.ATTN_LOCAL.value):
            c = init_kv_cache(cfg, CacheSpec(max_len=max_len, batch=batch), n=1)
            state[f"L{i}"] = {kk: vv[0] for kk, vv in c.items()}
        elif kind == LayerKind.MAMBA.value:
            conv, h = init_mamba_state(cfg, batch, n=1)
            state[f"L{i}"] = {"conv": conv[0], "h": h[0]}
        elif kind == LayerKind.RWKV.value:
            xp, s = init_rwkv_state(cfg, batch, n=1)
            state[f"L{i}"] = {"x_prev": xp[0], "s": s[0]}
            if ffn_kind == "rwkv_ffn":
                state[f"L{i}"]["ffn_x_prev"] = jnp.zeros((batch, cfg.d_model), cfg.cdtype)
    return state


def decode_group(params: Dict[str, Any], state: Dict[str, Any], x: Array,
                 pos: Array, cfg: ModelConfig,
                 page_table: Optional[Array] = None
                 ) -> Tuple[Array, Dict[str, Any], Array]:
    """x: (B, 1, d).  Returns (x, new_state, routed): ``routed`` (B,) int32
    counts, per row, the (token, held expert) pairs the group's MoE layers
    routed (0 without MoE).  Decode runs the per-token MoE path: a decode
    batch of one token per row never fills capacity buffers.

    ``page_table`` (B, pages_per_slot): the attention layers' k/v state
    leaves are a shared block-paged pool (models/kv_pool.py) rather than
    per-row dense caches; decode_attention reads and writes through the
    table.  SSM leaves are dense per-row either way."""
    new_state: Dict[str, Any] = {}
    routed = jnp.zeros((x.shape[0],), jnp.int32)
    for i, (kind, ffn_kind) in enumerate(cfg.full_pattern):
        layer = params[f"L{i}"]
        st = state[f"L{i}"]
        ns = dict(st)
        mixer_p, ffn_p = f"L{i}/mixer", f"L{i}/ffn"
        h = rms_norm(x, layer["norm1"], cfg.norm_eps)
        if kind in (LayerKind.ATTN.value, LayerKind.ATTN_LOCAL.value):
            mix, new_cache = decode_attention(
                layer["mixer"], h, st, pos, cfg,
                local=(kind == LayerKind.ATTN_LOCAL.value),
                page_table=page_table, prefix=mixer_p)
            ns.update(new_cache)
        elif kind == LayerKind.MAMBA.value:
            mix, (conv, hst) = mamba_mix(layer["mixer"], h, cfg,
                                         state=(st["conv"], st["h"]),
                                         prefix=mixer_p)
            ns["conv"], ns["h"] = conv, hst
        elif kind == LayerKind.RWKV.value:
            mix, (xp, s) = rwkv_time_mix(layer["mixer"], h, cfg,
                                         state=(st["x_prev"].astype(h.dtype), st["s"]),
                                         prefix=mixer_p)
            ns["x_prev"], ns["s"] = xp.astype(cfg.cdtype), s
        x = x + mix
        if ffn_kind != "none":
            h = rms_norm(x, layer["norm2"], cfg.norm_eps)
            if ffn_kind == "dense":
                f = ffn(layer["ffn"], h, cfg, prefix=ffn_p)
            elif ffn_kind == "moe":
                f, r = moe_held(layer["ffn"], h, cfg, prefix=ffn_p)
                routed = routed + r[:, 0]
            elif ffn_kind == "rwkv_ffn":
                f, xp2 = rwkv_channel_mix(layer["ffn"], h, cfg,
                                          x_prev=st["ffn_x_prev"].astype(h.dtype),
                                          prefix=ffn_p)
                ns["ffn_x_prev"] = xp2.astype(cfg.cdtype)
            x = x + f
        new_state[f"L{i}"] = ns
    return x, new_state, routed
