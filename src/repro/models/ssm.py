"""Sequence-state models: RWKV6 (Finch) time mixing and Mamba selective SSM.

RWKV6 — data-dependent per-channel decay (arXiv:2404.05892):
    S_t = diag(w_t) S_{t-1} + k_t^T v_t          (per head, K x V state)
    o_t = r_t (S_{t-1} + diag(u) k_t^T v_t)
Chunked evaluation (the performant TPU form, also the Pallas kernel's
contract): within a chunk of length L all exponents are *non-positive*
(relative decays), so the math is stable in fp32 without rescaling:
    inter:  o_t += (r_t . A_{t-1}) @ S_prev
    intra:  o_t += sum_{i<t} (r_t . A_{t-1}/A_i . k_i) v_i  + u-bonus diag
    carry:  S' = diag(A_L) S_prev + sum_i (A_L/A_i . k_i)^T v_i
with A_t = exp(cumsum_log w)_t.

Mamba (arXiv:2312.00752, as used in jamba): selective scan with
input-dependent (dt, B, C), run token by token on the state in windows of
cfg.mamba_chunk tokens.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.layers import apply_linear, init_linear
from .common import (act_fn, init_rms_norm, rms_norm, shard, BATCH_AXES,
                     TENSOR_AXIS)
from .config import ModelConfig, layer_name as _nm

Array = jax.Array


# Tokens per iteration of Mamba's token-serial scan: 1, 4, 8 and 16 timed
# within 4% of each other on a TPU v5e at Jamba2-Mini's widths, 16 the
# fastest (PERF.md).
TOKEN_UNROLL = 16


def recurrence_alignment(cfg: ModelConfig) -> int:
    """Smallest chunk granularity at which a prefill can be split without
    changing any recurrent layer's bits (launch/engine.py chunked prefill).

    RWKV evaluates its recurrence in fixed internal windows
    (cfg.rwkv_chunk) whose carried state crosses window boundaries through
    non-associative fp arithmetic — exp(a)·exp(b) is not exp(a+b) in fp32
    — so splitting a prompt anywhere *except* a window boundary changes
    bits.  An engine chunk that is a common multiple of every recurrence
    window present makes each engine chunk an integer number of internal
    windows, so the chunked evaluation performs the identical sequence of
    window scans and state carries as the one-shot prefill (a padded final
    chunk matches one-shot's own zero-padded last window: pads are exact
    identity steps under valid_len masking).  Mamba's scan runs token by
    token, the same arithmetic wherever its windows (cfg.mamba_chunk)
    fall, so that argument no longer holds for it; its window is still
    counted here.  Attention-only stacks may split anywhere (returns 1)."""
    align = 1
    for kind, _ in cfg.full_pattern:
        if kind == "mamba":
            align = math.lcm(align, cfg.mamba_chunk)
        elif kind == "rwkv":
            align = math.lcm(align, cfg.rwkv_chunk)
    return align


# ===========================================================================
# RWKV6
# ===========================================================================
def init_rwkv(key: Array, cfg: ModelConfig, prefix: str = "") -> dict:
    d = cfg.d_model
    H = cfg.n_heads
    K = d // H
    ks = jax.random.split(key, 12)
    dt = cfg.pdtype
    lm, ld = cfg.rwkv_lora_mix, cfg.rwkv_lora_decay
    p = {
        # token-shift mix coefficients (x, r, k, v, w, g)
        "mu": jnp.full((6, d), 0.5, dt),
        # ddlerp LoRAs: 5 targets (r,k,v,w,g)
        "lora_A": (jax.random.normal(ks[0], (5, d, lm)) / math.sqrt(d)).astype(dt),
        "lora_B": jnp.zeros((5, lm, d), dt),
        # decay: w0 + tanh(x W_a) W_b
        "w0": jnp.full((d,), -1.0, dt),
        "wd_A": (jax.random.normal(ks[1], (d, ld)) / math.sqrt(d)).astype(dt),
        "wd_B": jnp.zeros((ld, d), dt),
        "u": (jax.random.normal(ks[2], (H, K)) * 0.1).astype(dt),
        "wr": init_linear(ks[3], d, d, cfg.ep(d, d, _nm(prefix, "wr")), dtype=dt),
        "wk": init_linear(ks[4], d, d, cfg.ep(d, d, _nm(prefix, "wk")), dtype=dt),
        "wv": init_linear(ks[5], d, d, cfg.ep(d, d, _nm(prefix, "wv")), dtype=dt),
        "wg": init_linear(ks[6], d, d, cfg.ep(d, d, _nm(prefix, "wg")), dtype=dt),
        "wo": init_linear(ks[7], d, d, cfg.ep(d, d, _nm(prefix, "wo")), dtype=dt),
        "ln_x": jnp.ones((d,), dt),
    }
    return p


def _rwkv_inputs(p: dict, x: Array, x_prev: Array, cfg: ModelConfig,
                 prefix: str = ""):
    """Token-shift ddlerp producing (r, k, v, g, logw) — all (B, S, d).
    x_prev: (B, d) last token of the previous chunk/step."""
    B, S, d = x.shape
    xx = jnp.concatenate([x_prev[:, None], x[:, :-1]], axis=1) - x   # shifted diff
    mu = p["mu"].astype(x.dtype)
    xxx = x + xx * mu[0]
    # ddlerp LoRA corrections: (5, B, S, d)
    lora = jnp.einsum(
        "fbsl,fld->fbsd",
        jnp.tanh(jnp.einsum("bsd,fdl->fbsl", xxx, p["lora_A"].astype(x.dtype))),
        p["lora_B"].astype(x.dtype))
    xr = x + xx * (mu[1] + lora[0])
    xk = x + xx * (mu[2] + lora[1])
    xv = x + xx * (mu[3] + lora[2])
    xw = x + xx * (mu[4] + lora[3])
    xg = x + xx * (mu[5] + lora[4])
    r = apply_linear(p["wr"], xr, cfg.ep(d, d, _nm(prefix, "wr")))
    k = apply_linear(p["wk"], xk, cfg.ep(d, d, _nm(prefix, "wk")))
    v = apply_linear(p["wv"], xv, cfg.ep(d, d, _nm(prefix, "wv")))
    g = jax.nn.silu(apply_linear(p["wg"], xg, cfg.ep(d, d, _nm(prefix, "wg"))))
    logw = -jnp.exp(
        (p["w0"].astype(jnp.float32)
         + (jnp.tanh(xw.astype(jnp.float32) @ p["wd_A"].astype(jnp.float32))
            @ p["wd_B"].astype(jnp.float32))))          # (B,S,d), <= 0
    return r, k, v, g, logw


def _heads(t: Array, H: int) -> Array:
    B, S, d = t.shape
    return t.reshape(B, S, H, d // H)


def rwkv_chunked(r, k, v, logw, u, state, chunk: int = 64):
    """Chunked WKV.  r/k/v: (B,S,H,K); logw: (B,S,H,K) (<=0); u: (H,K);
    state: (B,H,K,K_v).  Returns (out (B,S,H,Kv), new state)."""
    B, S, H, K = r.shape
    L = min(chunk, S)
    n = -(-S // L)
    pad = n * L - S
    if pad:
        z = lambda t: jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r, k, v = z(r), z(k), z(v)
        logw = jnp.pad(logw, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # keep every chunked tensor and the recurrent state sharded over heads
    # ('model') — unconstrained fp32 carriers replicate and re-gather per
    # chunk, the same pathology as attention's A0 (EXPERIMENTS.md §Perf E0)
    csh = lambda t: shard(t, BATCH_AXES, None, None, TENSOR_AXIS, None)
    rf = csh(r.astype(jnp.float32).reshape(B, n, L, H, K))
    kf = csh(k.astype(jnp.float32).reshape(B, n, L, H, K))
    vf = csh(v.astype(jnp.float32).reshape(B, n, L, H, K))
    lw = csh(logw.astype(jnp.float32).reshape(B, n, L, H, K))
    uf = u.astype(jnp.float32)

    def body(S_c, inp):
        rc, kc, vc, lwc = inp                         # (B,L,H,K)
        cs = jnp.cumsum(lwc, axis=1)                  # (B,L,H,K) <= 0
        cs_prev = cs - lwc                            # exclusive cumsum
        # inter-chunk: o_t = (r_t * exp(cs_prev)) @ S_c
        r_dec = rc * jnp.exp(cs_prev)
        o = jnp.einsum("blhk,bhkv->blhv", r_dec, S_c)
        # intra-chunk: scores_ti = sum_k r_tk exp(cs_prev_t - cs_i) k_ik, i<t
        expo = cs_prev[:, :, None] - cs[:, None, :]   # (B,L_t,L_i,H,K)
        expo = jnp.where(expo > 0, 0.0, expo)         # mask region; keep <=0
        scores = jnp.einsum("bthk,btihk,bihk->bthi", rc, jnp.exp(expo), kc)
        tri = jnp.tril(jnp.ones((L, L), bool), k=-1)  # strict: i < t
        scores = scores * tri[None, :, None, :]
        o = o + jnp.einsum("bthi,bihv->bthv", scores, vc)
        # current-token bonus
        bonus = jnp.einsum("blhk,blhk->blh", rc * uf[None, None], kc)
        o = o + bonus[..., None] * vc
        # carry: S' = diag(exp(cs_L)) S + sum_i (exp(cs_L - cs_i) k_i)^T v_i
        cs_L = cs[:, -1][:, None]                     # (B,1,H,K)
        k_dec = kc * jnp.exp(cs_L - cs)
        S_new = S_c * jnp.exp(cs_L[:, 0])[..., None] \
            + jnp.einsum("blhk,blhv->bhkv", k_dec, vc)
        S_new = shard(S_new, BATCH_AXES, TENSOR_AXIS, None, None)
        o = shard(o, BATCH_AXES, None, TENSOR_AXIS, None)
        return S_new, o

    state = state.astype(jnp.float32)
    inputs = (rf.transpose(1, 0, 2, 3, 4), kf.transpose(1, 0, 2, 3, 4),
              vf.transpose(1, 0, 2, 3, 4), lw.transpose(1, 0, 2, 3, 4))
    state, outs = jax.lax.scan(body, state, inputs)
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, n * L, H, K)
    return out[:, :S], state


def rwkv_step(r, k, v, logw, u, state):
    """Single-token recurrence (decode).  r/k/v/logw: (B,H,K)."""
    rf, kf, vf = (t.astype(jnp.float32) for t in (r, k, v))
    w = jnp.exp(logw.astype(jnp.float32))             # (B,H,K)
    kv = kf[..., :, None] * vf[..., None, :]          # (B,H,K,V)
    u32 = u.astype(jnp.float32)[None, :, :, None]     # (1,H,K,1)
    o = jnp.einsum("bhk,bhkv->bhv", rf, state + u32 * kv)
    state = state * w[..., None] + kv
    return o, state


def rwkv_time_mix(p: dict, x: Array, cfg: ModelConfig,
                  state: Optional[Tuple[Array, Array]] = None,
                  chunk: int = 0, prefix: str = "",
                  valid_len: Optional[Array] = None):
    chunk = chunk or cfg.rwkv_chunk
    """Full RWKV6 time-mixing block.  state = (x_prev (B,d), S (B,H,K,K)).

    ``valid_len`` (traced scalar) marks a right-padded prefill: only the
    first ``valid_len`` tokens are real.  Pad tokens must not touch the
    carried state, and the real tokens' outputs must keep their exact
    bits: zeroing k kills the pads' kv outer products in the state carry
    (their intra-chunk score contributions are already strictly-causal
    masked for real rows), and zeroing logw makes their decay exp(0)=1 so
    the decay cumsum is constant past the last real token — real-token
    prefixes of the cumsum are untouched because the pads sit strictly
    after them."""
    B, S, d = x.shape
    H = cfg.n_heads
    K = d // H
    if state is None:
        x_prev = jnp.zeros((B, d), x.dtype)
        S0 = jnp.zeros((B, H, K, K), jnp.float32)
    else:
        x_prev, S0 = state
    r, k, v, g, logw = _rwkv_inputs(p, x, x_prev, cfg, prefix)
    rh, kh, vh = _heads(r, H), _heads(k, H), _heads(v, H)
    lwh = _heads(logw, H)
    if valid_len is not None:
        m = (jnp.arange(S) < valid_len)[None, :, None, None]
        kh = jnp.where(m, kh, jnp.zeros((), kh.dtype))
        lwh = jnp.where(m, lwh, jnp.zeros((), lwh.dtype))
    rh = shard(rh, BATCH_AXES, None, TENSOR_AXIS, None)
    kh = shard(kh, BATCH_AXES, None, TENSOR_AXIS, None)
    vh = shard(vh, BATCH_AXES, None, TENSOR_AXIS, None)
    if S == 1:
        o, S1 = rwkv_step(rh[:, 0], kh[:, 0], vh[:, 0], lwh[:, 0],
                          p["u"], S0)
        o = o[:, None]
    else:
        o, S1 = rwkv_chunked(rh, kh, vh, lwh, p["u"], S0, chunk)
    o = o.reshape(B, S, d).astype(x.dtype)
    # group-norm over heads (ln_x), then gate and output proj
    o32 = o.astype(jnp.float32).reshape(B, S, H, K)
    mean = o32.mean(-1, keepdims=True)
    var = o32.var(-1, keepdims=True)
    o = ((o32 - mean) * jax.lax.rsqrt(var + 1e-5)).reshape(B, S, d)
    o = (o * p["ln_x"].astype(jnp.float32)).astype(x.dtype)
    out = apply_linear(p["wo"], o * g, cfg.ep(d, d, _nm(prefix, "wo")))
    x_last = (x[:, -1] if valid_len is None else
              jax.lax.dynamic_slice_in_dim(x, valid_len - 1, 1, 1)[:, 0])
    new_state = (x_last, S1)
    return out, new_state


def init_rwkv_state(cfg: ModelConfig, batch: int, n: int = 1):
    d, H = cfg.d_model, cfg.n_heads
    K = d // H
    return (jnp.zeros((n, batch, d), cfg.cdtype),
            jnp.zeros((n, batch, H, K, K), jnp.float32))


# -- RWKV channel mixing (the FFN of rwkv blocks) ----------------------------
def init_rwkv_ffn(key: Array, cfg: ModelConfig, prefix: str = "") -> dict:
    d, ff = cfg.d_model, cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    dt = cfg.pdtype
    return {
        "mu_k": jnp.full((d,), 0.5, dt),
        "mu_r": jnp.full((d,), 0.5, dt),
        "wk": init_linear(k1, d, ff, cfg.ep(d, ff, _nm(prefix, "wk")), dtype=dt),
        "wv": init_linear(k2, ff, d, cfg.ep(ff, d, _nm(prefix, "wv")), dtype=dt),
        "wr": init_linear(k3, d, d, cfg.ep(d, d, _nm(prefix, "wr")), dtype=dt),
    }


def rwkv_channel_mix(p: dict, x: Array, cfg: ModelConfig,
                     x_prev: Optional[Array] = None, prefix: str = "",
                     valid_len: Optional[Array] = None):
    """Pointwise over (shifted) positions, so a right-padded prefill only
    needs the carried x_prev gathered at the last *real* token
    (``valid_len - 1``) instead of the last position."""
    B, S, d = x.shape
    if x_prev is None:
        x_prev = jnp.zeros((B, d), x.dtype)
    xx = jnp.concatenate([x_prev[:, None], x[:, :-1]], axis=1) - x
    xk = x + xx * p["mu_k"].astype(x.dtype)
    xr = x + xx * p["mu_r"].astype(x.dtype)
    k = apply_linear(p["wk"], xk, cfg.ep(d, cfg.d_ff, _nm(prefix, "wk")))
    k = jnp.square(jax.nn.relu(k))
    kv = apply_linear(p["wv"], k, cfg.ep(cfg.d_ff, d, _nm(prefix, "wv")))
    r = jax.nn.sigmoid(apply_linear(p["wr"], xr, cfg.ep(d, d, _nm(prefix, "wr"))))
    x_last = (x[:, -1] if valid_len is None else
              jax.lax.dynamic_slice_in_dim(x, valid_len - 1, 1, 1)[:, 0])
    return r * kv, x_last


# ===========================================================================
# Mamba (jamba's SSM layer)
# ===========================================================================
def init_mamba(key: Array, cfg: ModelConfig, prefix: str = "") -> dict:
    d = cfg.d_model
    di, ds, dc = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
    dt_rank = cfg.dt_rank
    ks = jax.random.split(key, 7)
    dtp = cfg.pdtype
    norms = ({"dt_norm": init_rms_norm(dt_rank, dtp),
              "b_norm": init_rms_norm(ds, dtp),
              "c_norm": init_rms_norm(ds, dtp)}
             if cfg.mamba_dtbc_norm else {})
    return {**norms,
        "in_proj": init_linear(ks[0], d, 2 * di,
                               cfg.ep(d, 2 * di, _nm(prefix, "in_proj")),
                               dtype=dtp),
        "conv_w": (jax.random.normal(ks[1], (dc, di)) / math.sqrt(dc)).astype(dtp),
        "conv_b": jnp.zeros((di,), dtp),
        "x_proj": init_linear(ks[2], di, dt_rank + 2 * ds,
                              cfg.ep(di, dt_rank + 2 * ds,
                                     _nm(prefix, "x_proj")), dtype=dtp),
        "dt_proj": init_linear(ks[3], dt_rank, di,
                               cfg.ep(dt_rank, di, _nm(prefix, "dt_proj")),
                               bias=True, dtype=dtp),
        "A_log": jnp.log(jnp.tile(jnp.arange(1, ds + 1, dtype=jnp.float32)[None],
                                  (di, 1))),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": init_linear(ks[4], di, d,
                                cfg.ep(di, d, _nm(prefix, "out_proj")),
                                dtype=dtp),
    }


def mamba_mix(p: dict, x: Array, cfg: ModelConfig,
              state: Optional[Tuple[Array, Array]] = None,
              chunk: int = 0, prefix: str = "",
              valid_len: Optional[Array] = None):
    """Mamba block.  state = (conv buffer (B, dc-1, di), h (B, ds, di)):
    ``h`` keeps d_inner on its last (lane) axis, since a d_state of 16
    there would pad to a TPU's 128 lanes, and the selective scan runs
    token by token on that same (B, ds, di) layout, from the pool to the
    pool.  With ``cfg.mamba_dtbc_norm`` dt, B and C are RMS-normalised
    after ``x_proj`` (Jamba).  Every op from the conv to the gate runs
    under the ``epim.mamba`` scope, the recurrence under
    ``epim.mamba.scan``.

    ``valid_len`` (traced scalar) marks a right-padded prefill: pad
    positions get dt forced to 0 so their steps are the exact identity
    (exp(0)=1 times h, plus 0 — the same trick the chunk padding already
    relies on), and the carried conv window is gathered ending at the
    last real token instead of the last position."""
    chunk = chunk or cfg.mamba_chunk
    B, S, d = x.shape
    di, ds, dc = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
    dt_rank = cfg.dt_rank
    xz = apply_linear(p["in_proj"], x,
                      cfg.ep(d, 2 * di, _nm(prefix, "in_proj")))
    xi, z = jnp.split(xz, 2, axis=-1)
    xi = shard(xi, BATCH_AXES, None, TENSOR_AXIS)
    z = shard(z, BATCH_AXES, None, TENSOR_AXIS)

    if state is None:
        conv_buf = jnp.zeros((B, dc - 1, di), xi.dtype)
        h0 = jnp.zeros((B, ds, di), jnp.float32)
    else:
        conv_buf, h0 = state
    with jax.named_scope("epim.mamba"):
        y, new_conv, h_last = _mamba_core(p, xi, z, conv_buf, h0, cfg, chunk,
                                          prefix, valid_len)
    out = apply_linear(p["out_proj"], y,
                       cfg.ep(di, d, _nm(prefix, "out_proj")))
    return out, (new_conv, h_last)


def _mamba_core(p, xi, z, conv_buf, h0, cfg: ModelConfig, chunk: int,
                prefix: str, valid_len):
    """Conv, selective scan and gate: (y (B, S, di), conv window, h
    (B, ds, di))."""
    B, S, di = xi.shape
    ds, dc, dt_rank = cfg.mamba_d_state, cfg.mamba_d_conv, cfg.dt_rank
    # causal depthwise conv along S
    xpad = jnp.concatenate([conv_buf.astype(xi.dtype), xi], axis=1)
    cw = p["conv_w"].astype(xi.dtype)
    xc = sum(xpad[:, i:i + S] * cw[i][None, None] for i in range(dc))
    xc = jax.nn.silu(xc + p["conv_b"].astype(xi.dtype))
    if dc > 1:
        # carried window = the last dc-1 inputs up to the last real token:
        # xpad[valid_len : valid_len + dc - 1] (== the trailing window when
        # the whole sequence is real)
        new_conv = (xpad[:, -(dc - 1):] if valid_len is None else
                    jax.lax.dynamic_slice_in_dim(xpad, valid_len, dc - 1, 1))
    else:
        new_conv = conv_buf

    # input-dependent SSM parameters
    proj = apply_linear(p["x_proj"], xc,
                        cfg.ep(di, dt_rank + 2 * ds, _nm(prefix, "x_proj")))
    dt, Bp, Cp = jnp.split(proj, [dt_rank, dt_rank + ds], axis=-1)
    if cfg.mamba_dtbc_norm:
        dt = rms_norm(dt, p["dt_norm"], cfg.norm_eps)
        Bp = rms_norm(Bp, p["b_norm"], cfg.norm_eps)
        Cp = rms_norm(Cp, p["c_norm"], cfg.norm_eps)
    dt = jax.nn.softplus(apply_linear(
        p["dt_proj"], dt, cfg.ep(dt_rank, di, _nm(prefix, "dt_proj"))))
    dt = shard(dt, BATCH_AXES, None, TENSOR_AXIS)
    if valid_len is not None:
        dt = jnp.where((jnp.arange(S) < valid_len)[None, :, None], dt,
                       jnp.zeros((), dt.dtype))
    A_t = -jnp.exp(p["A_log"]).T                           # (ds, di)

    # the recurrence, token by token on h (B, ds, di) in windows of L
    # tokens: nothing of shape (.., L, ds, di) is ever built, so each
    # token reads and writes h once
    L = min(chunk, S)
    n = -(-S // L)
    pad = n * L - S

    def chunks(t):                                         # (n, L, B, ..)
        if pad:
            t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return t.reshape((B, n, L) + t.shape[2:]).transpose(
            (1, 2, 0) + tuple(range(3, t.ndim + 1)))

    def step(h, inp):
        dt_t, x_t, b_t, c_t = inp                          # (B, di), (B, ds)
        h = (jnp.exp(dt_t[:, None, :] * A_t) * h           # pad: exp(0)=1
             + (dt_t * x_t)[:, None, :] * b_t[:, :, None])  # pad: 0
        return h, jnp.sum(h * c_t[:, :, None], axis=1)

    @jax.checkpoint
    def chunk_fn(h, inp):
        # nested remat: the backward of one window keeps its L states
        # (B, ds, di) and no more; recomputing one window's forward is cheap
        return jax.lax.scan(step, h, inp, unroll=TOKEN_UNROLL)

    seq = tuple(chunks(t.astype(jnp.float32)) for t in (dt, xc, Bp, Cp))
    with jax.named_scope("epim.mamba.scan"):
        h_last, y = jax.lax.scan(chunk_fn, h0, seq)
    y = y.transpose(2, 0, 1, 3).reshape(B, n * L, di)[:, :S]
    y = y + xc.astype(jnp.float32) * p["D"][None, None]
    y = (y.astype(z.dtype)) * jax.nn.silu(z)
    return y, new_conv, h_last


def init_mamba_state(cfg: ModelConfig, batch: int, n: int = 1):
    """(conv (n, B, dc-1, di), h (n, B, ds, di)): d_inner last."""
    di, ds, dc = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
    return (jnp.zeros((n, batch, dc - 1, di), cfg.cdtype),
            jnp.zeros((n, batch, ds, di), jnp.float32))
