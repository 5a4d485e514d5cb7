"""Plain Jamba forward (AI21 Jamba2-Mini, config.json of
ai21labs/AI21-Jamba2-Mini; the same block as Jamba 1.5/1.6 Mini) with
epitome weights, for one device's share of the experts.

Float32, every weight product at ``precision="highest"`` unless a lower
precision is asked for (``dot``), no kernels, no cache, no batching
tricks: the Mamba recurrence runs token by token, attention is the full
causal softmax, and each held expert runs on every token and is weighted
by the token's routing weight (0 where the token did not pick it).  Sizes
come from the configuration file and weights are drawn from the seed
along the configuration's key tree (``_keys``), layer by layer, so only
one layer's weights are held at a time; it shares nothing with the
program but the seed.

The layers, as the configuration states them:

* Mamba-1 (``mamba``): in_proj to (x, z); causal depthwise conv of
  ``mamba_d_conv`` taps with bias, SiLU; x_proj to (dt, B, C), each
  RMS-normalised (Jamba's dt/b/c layernorms, gain 1); dt = softplus(dt_proj
  (dt) + bias); h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t, y_t = h_t C_t +
  D x_t; y * SiLU(z); out_proj.  A = -(1..d_state) per channel, D = 1.
* Attention (``attn``): GQA, no positional encoding, causal softmax over
  q k / sqrt(head_dim).
* FFN: SwiGLU, dense or MoE.  MoE: softmax over all ``n_experts`` router
  logits, the top ``top_k`` kept with those weights (not renormalised);
  only the experts in ``experts_held`` contribute (the chip's share).

Departures from the published model, which the program's model has too:
RMSNorm with a (1 + w) gain at w = 0 (Jamba's weight-1 init), the
embedding scaled by sqrt(d_model), random weights, and every projection
listed under ``layers`` with a ``spec`` an epitome with 3-bit codes; the
sites without one (x_proj, dt_proj: under ``min_params``) are dense
weights with 3-bit per-tile codes.
"""
from __future__ import annotations

import math
from typing import Callable, List

import jax
import jax.numpy as jnp
import numpy as np

from .epitome import _take, col_map, epitome_weight, quantize, row_map, tables

Dot = Callable[[jax.Array, jax.Array], jax.Array]
HIGHEST = jax.lax.Precision.HIGHEST


def highest_dot(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _keys(init_key, model: dict):
    """(embed, head, one key per group of ``len(pattern)`` layers)."""
    k_embed, k_groups, k_head = jax.random.split(init_key, 3)
    groups = jax.random.split(k_groups, model["n_layers"]
                              // len(model["pattern"]))
    return k_embed, k_head, groups


def _site(lay: dict, key, quant: dict, tab, stack: int = 0):
    """One projection's float32 weight(s): an epitome sampled to (M, N),
    or a dense (M, N) weight, 3-bit codes either way.  ``stack`` > 0 draws
    that many experts in one call, as the program does."""
    spec, M, N = lay["spec"], lay["M"], lay["N"]
    if spec is None:
        W = (jax.random.normal(key, (stack, M, N)) * (1.0 / math.sqrt(M))
             if stack else jax.random.normal(key, (M, N)) / np.sqrt(M))
        q = lambda w: quantize(w, None, quant)
        return jax.vmap(q)(W) if stack else q(W)
    if not stack:
        return epitome_weight(key, spec, quant, tab)
    E = jax.random.normal(key, (stack, spec["m"], spec["n"])) * (
        1.0 / math.sqrt(M))
    return E                    # quantized and sampled per expert: _held


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _mamba(x, key, model, sites, quant, tabs, dot: Dot):
    B, S, d = x.shape
    di = model["mamba_expand"] * d
    ds, dc, R = model["mamba_d_state"], model["mamba_d_conv"], model["mamba_dt_rank"]
    eps = model["norm_eps"]
    ks = jax.random.split(key, 7)
    w = {n: _site(sites[n], ks[i], quant, tabs[n])
         for n, i in (("in_proj", 0), ("x_proj", 2), ("dt_proj", 3),
                      ("out_proj", 4))}
    conv_w = jax.random.normal(ks[1], (dc, di)) / math.sqrt(dc)
    xi, z = jnp.split(dot(x, w["in_proj"]), 2, axis=-1)
    xpad = jnp.concatenate([jnp.zeros((B, dc - 1, di)), xi], axis=1)
    xc = jax.nn.silu(sum(xpad[:, i:i + S] * conv_w[i] for i in range(dc)))
    dt, Bp, Cp = jnp.split(dot(xc, w["x_proj"]), [R, R + ds], axis=-1)
    dt = jax.nn.softplus(dot(_rms(dt, eps), w["dt_proj"]))   # bias 0
    Bp, Cp = _rms(Bp, eps), _rms(Cp, eps)
    A = -jnp.tile(jnp.arange(1, ds + 1, dtype=jnp.float32)[None], (di, 1))

    def step(h, t):
        dt_t, x_t, b_t, c_t = t                        # (B, di), (B, ds)
        h = jnp.exp(dt_t[..., None] * A) * h + (dt_t * x_t)[..., None] \
            * b_t[:, None]
        return h, jnp.einsum("bdn,bn->bd", h, c_t, precision=HIGHEST)

    seq = tuple(jnp.moveaxis(t, 1, 0) for t in (dt, xc, Bp, Cp))
    _, y = jax.lax.scan(step, jnp.zeros((B, di, ds)), seq)
    y = (jnp.moveaxis(y, 0, 1) + xc) * jax.nn.silu(z)       # D = 1
    return dot(y, w["out_proj"])


def _attn(x, key, model, sites, quant, tabs, dot: Dot):
    B, S, d = x.shape
    H, Hk, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    ks = jax.random.split(key, 4)
    w = {n: _site(sites[n], ks[i], quant, tabs[n])
         for i, n in enumerate(("wq", "wk", "wv", "wo"))}
    q = dot(x, w["wq"]).reshape(B, S, Hk, H // Hk, hd)
    k = dot(x, w["wk"]).reshape(B, S, Hk, hd)
    v = dot(x, w["wv"]).reshape(B, S, Hk, hd)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def head(qkv):                   # one KV head and its query group
        qh, kh, vh = qkv
        s = jnp.einsum("bqgd,bkd->bgqk", qh, kh, precision=HIGHEST)
        s = jnp.where(causal, s / math.sqrt(hd), -jnp.inf)
        return jnp.einsum("bgqk,bkd->bqgd", jax.nn.softmax(s, -1), vh,
                          precision=HIGHEST)

    o = jax.lax.map(head, (jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0),
                           jnp.moveaxis(v, 2, 0)))
    return dot(jnp.moveaxis(o, 0, 2).reshape(B, S, H * hd), w["wo"])


def _dense_ffn(x, key, model, sites, quant, tabs, dot: Dot):
    ks = jax.random.split(key, 3)
    w = {n: _site(sites[n], ks[i], quant, tabs[n])
         for i, n in enumerate(("w_gate", "w_up", "w_down"))}
    return dot(jax.nn.silu(dot(x, w["w_gate"])) * dot(x, w["w_up"]),
               w["w_down"])


def route(x, router, model, dot: Dot):
    """(T, n_experts) combine weights: softmax over all experts, the top
    ``top_k`` kept as they are, the rest 0."""
    p = jax.nn.softmax(dot(x, router), axis=-1)
    kth = jax.lax.top_k(p, model["top_k"])[0][..., -1:]
    return jnp.where(p >= kth, p, 0.0)


def _moe_route(x, key, model, dot: Dot):
    d, E = model["d_model"], model["n_experts"]
    router = jax.random.normal(key, (d, E)) * (1.0 / math.sqrt(d))
    return route(x, router, model, dot)


def _expert(x, w_gate, w_up, w_down, weight, dot: Dot):
    return (dot(jax.nn.silu(dot(x, w_gate)) * dot(x, w_up), w_down)
            * weight[..., None])


def _held(lay: dict, stack, j: int, quant: dict, tab):
    """Expert j's float32 (M, N) weight from its site's drawn stack."""
    if lay["spec"] is None:
        return stack[j]
    spec = lay["spec"]
    E = quantize(stack[j], spec, quant, tab["mask"])
    E = _take(E, row_map(spec), tab["rows"], 0)
    return _take(E, col_map(spec), tab["cols"], 1)


def logits(init_key, model: dict, layers: List[dict], quant: dict,
           tokens: np.ndarray, dot: Dot = highest_dot) -> jax.Array:
    """Logits (B, S, vocab) of right-padded token rows ``tokens`` (B, S):
    position t sees tokens 0..t of its row, so padding after a row's end
    changes none of its positions.  Runs layer by layer; an MoE layer
    expert by expert."""
    V, d, eps = model["vocab"], model["d_model"], model["norm_eps"]
    k_embed, k_head, group_keys = _keys(init_key, model)
    embed = jax.random.normal(k_embed, (V, d)) / math.sqrt(d)
    x = jnp.take(embed, jnp.asarray(tokens), axis=0) * math.sqrt(d)
    del embed
    by_name = {lay["name"]: lay for lay in layers}
    tabs = {n: (jax.device_put(tables(lay["spec"])) if lay["spec"] else None)
            for n, lay in by_name.items()}
    lo, hi = model["experts_held"]

    def sites(i, block):
        pre = f"L{i}/{block}/"
        return ({n[len(pre):]: lay for n, lay in by_name.items()
                 if n.startswith(pre)},
                {n[len(pre):]: t for n, t in tabs.items()
                 if n.startswith(pre)})

    mixers = {"mamba": jax.jit(lambda x, k, t, s: _mamba(
                  x, k, model, s, quant, t, dot), static_argnums=3),
              "attn": jax.jit(lambda x, k, t, s: _attn(
                  x, k, model, s, quant, t, dot), static_argnums=3)}
    dense = jax.jit(lambda x, k, t, s: _dense_ffn(x, k, model, s, quant, t,
                                                  dot), static_argnums=3)
    rms = jax.jit(lambda x: _rms(x, eps))
    moe_route = jax.jit(lambda x, k: _moe_route(x, k, model, dot))
    expert = jax.jit(lambda *a: _expert(*a, dot=dot))
    E = model["n_experts"]
    stack = jax.jit(lambda k, lay: _site(lay, k, quant, None, stack=E)[lo:hi],
                    static_argnums=1)
    held = jax.jit(lambda s, j, t, lay: _held(lay, s, j, quant, t),
                   static_argnums=3)
    for g in group_keys:
        for i, (kind, ffn) in enumerate(zip(model["pattern"],
                                            model["ffn_pattern"])):
            k_mix, k_ffn = jax.random.split(jax.random.split(
                g, len(model["pattern"]))[i])
            s, t = sites(i, "mixer")
            x = x + mixers[kind](rms(x), k_mix, t, _Frozen(s))
            h = rms(x)
            s, t = sites(i, "ffn")
            if ffn == "dense":
                x = x + dense(h, k_ffn, t, _Frozen(s))
                continue
            kr, *ks = jax.random.split(k_ffn, 4)
            comb = moe_route(h, kr)
            stacks = {n: stack(k, _Frozen(s[n]))
                      for k, n in zip(ks, ("w_gate", "w_up", "w_down"))}
            for j in range(hi - lo):
                w = [held(stacks[n], j, t[n], _Frozen(s[n]))
                     for n in ("w_gate", "w_up", "w_down")]
                x = x + expert(h, *w, comb[..., lo + j])
            del stacks
    head = jax.random.normal(k_head, (d, V)) / math.sqrt(d)
    return dot(_rms(x, eps), head)


class _Frozen(dict):
    """A dict usable as a static jit argument (hashable by content)."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))
