"""Plain RWKV-6 "Finch" forward (arXiv:2404.05892) with epitome weights.

Float32, every matrix product at ``precision="highest"`` unless a lower
precision is asked for (``dot``), no kernels, no cache, no batching
tricks: the recurrence runs token by token.  It takes its sizes from the
configuration file and draws its weights from the seed along the
configuration's key tree (``_keys``), so it shares nothing with the
program but the seed.

The model as the configuration states it departs from the published
Finch in ways the program's model has too, and the reference follows the
configuration: RMSNorm with a (1 + w) gain in place of LayerNorm, no
``ln0`` after the embedding, the embedding scaled by sqrt(d_model),
GroupNorm eps 1e-5 over each head's output, and every projection listed
under ``layers`` an epitome with 3-bit codes.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .epitome import epitome_weight, tables

Dot = Callable[[jax.Array, jax.Array], jax.Array]


def highest_dot(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _keys(init_key, model: dict):
    """The configuration's key tree: (embed, head, one key per block)."""
    k_embed, k_groups, k_head = jax.random.split(init_key, 3)
    layers = jax.random.split(k_groups, model["n_layers"])
    return k_embed, k_head, layers


def _layer_keys(layer_key):
    k_mix, k_ffn = jax.random.split(jax.random.split(layer_key, 1)[0])
    return jax.random.split(k_mix, 12), jax.random.split(k_ffn, 3)


# position of each projection's key in its block's split
_MIX_KEY = {"wr": 3, "wk": 4, "wv": 5, "wg": 6, "wo": 7}
_FFN_KEY = {"wk": 0, "wv": 1, "wr": 2}


def layer_weights(layer_key, model: dict, layers: Sequence[dict],
                  quant: dict, tabs: Dict[str, dict]) -> Dict[str, jax.Array]:
    """Every weight of one RWKV-6 block, float32 (``tabs``: each layer's
    ``tables``, by name)."""
    d, lm, ld = model["d_model"], model["rwkv_lora_mix"], model["rwkv_lora_decay"]
    H = model["n_heads"]
    ks, kf = _layer_keys(layer_key)
    w = {
        "lora_A": jax.random.normal(ks[0], (5, d, lm)) / math.sqrt(d),
        "lora_B": jnp.zeros((5, lm, d)),
        "wd_A": jax.random.normal(ks[1], (d, ld)) / math.sqrt(d),
        "wd_B": jnp.zeros((ld, d)),
        "u": jax.random.normal(ks[2], (H, d // H)) * 0.1,
    }
    for lay in layers:
        block, name = lay["name"].split("/")[1:]
        key = (ks[_MIX_KEY[name]] if block == "mixer" else kf[_FFN_KEY[name]])
        w[f"{block}/{name}"] = epitome_weight(key, lay["spec"], quant,
                                              tabs[lay["name"]])
    return w


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _shift(x):
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def _block(x, w, model: dict, dot: Dot):
    """One block over (B, S, d) float32, from an empty state."""
    B, S, d = x.shape
    H = model["n_heads"]
    K = d // H
    eps = model["norm_eps"]
    h = _rms(x, eps)                       # norm1 gain (1 + 0)
    xx = _shift(h) - h
    xxx = h + xx * 0.5
    lora = jnp.stack([dot(jnp.tanh(dot(xxx, w["lora_A"][f])), w["lora_B"][f])
                      for f in range(5)])
    xr, xk, xv, xw, xg = (h + xx * (0.5 + lora[f]) for f in range(5))
    r = dot(xr, w["mixer/wr"]).reshape(B, S, H, K)
    k = dot(xk, w["mixer/wk"]).reshape(B, S, H, K)
    v = dot(xv, w["mixer/wv"]).reshape(B, S, H, K)
    g = jax.nn.silu(dot(xg, w["mixer/wg"]))
    logw = -jnp.exp(-1.0 + dot(jnp.tanh(dot(xw, w["wd_A"])), w["wd_B"]))
    decay = jnp.exp(logw).reshape(B, S, H, K)
    u = w["u"]

    def step(state, t):
        r_t, k_t, v_t, w_t = t                      # (B, H, K)
        kv = k_t[..., :, None] * v_t[..., None, :]  # (B, H, K, V)
        o = jnp.einsum("bhk,bhkv->bhv", r_t, state + u[None, :, :, None] * kv,
                       precision=jax.lax.Precision.HIGHEST)
        return state * w_t[..., None] + kv, o

    seq = tuple(jnp.moveaxis(t, 1, 0) for t in (r, k, v, decay))
    _, o = jax.lax.scan(step, jnp.zeros((B, H, K, K), jnp.float32), seq)
    o = jnp.moveaxis(o, 0, 1)                       # (B, S, H, V)
    mean = o.mean(-1, keepdims=True)
    var = o.var(-1, keepdims=True)
    o = ((o - mean) * jax.lax.rsqrt(var + 1e-5)).reshape(B, S, d)  # ln_x = 1
    x = x + dot(o * g, w["mixer/wo"])

    h = _rms(x, eps)                       # norm2
    xx = _shift(h) - h
    kk = jnp.square(jax.nn.relu(dot(h + xx * 0.5, w["ffn/wk"])))
    rr = jax.nn.sigmoid(dot(h + xx * 0.5, w["ffn/wr"]))
    return x + rr * dot(kk, w["ffn/wv"])


def logits(init_key, model: dict, layers: List[dict], quant: dict,
           tokens: np.ndarray, dot: Dot = highest_dot) -> jax.Array:
    """Logits (B, S, vocab) of right-padded token rows ``tokens`` (B, S):
    position t sees tokens 0..t of its row, so padding after a row's end
    changes none of its positions.  Runs block by block, so only one
    block's weights are held at a time."""
    V, d = model["vocab"], model["d_model"]
    k_embed, k_head, layer_keys = _keys(init_key, model)
    embed = jax.random.normal(k_embed, (V, d)) / math.sqrt(d)
    x = jnp.take(embed, jnp.asarray(tokens), axis=0) * math.sqrt(d)
    del embed
    block = jax.jit(lambda x, w: _block(x, w, model, dot))
    make = jax.jit(lambda k, t: layer_weights(k, model, layers, quant, t))
    tabs = jax.device_put({lay["name"]: tables(lay["spec"]) for lay in layers})
    for i in range(model["n_layers"]):
        x = block(x, make(layer_keys[i], tabs))
    head = jax.random.normal(k_head, (d, V)) / math.sqrt(d)
    return dot(_rms(x, model["norm_eps"]), head)
