"""Mixture-of-Experts with real expert parallelism.

Three execution paths (DESIGN.md §5):

1. ``dispatch``    — training / prefill at scale: `shard_map` over the batch
   axes; tokens are routed locally (sort-free rank-within-expert via cumsum
   counts), packed into per-destination-shard capacity buffers, exchanged
   with `lax.all_to_all`, run through the local expert (whose d_ff is still
   tensor-parallel: the matmuls are manually psum'd over 'model'), and
   returned.  Expert weights live fully sharded (E replicated, d_model over
   data, d_ff over model) and are reshaped to per-shard slots with a
   sharding constraint — XLA turns that into the FSDP-style expert
   all-gather, and its transpose into the gradient reduce-scatter.
2. ``dense``       — decode / tiny token counts: every expert computes every
   token, masked combine; weights stay resident.  FLOPs = E/topk times the
   dispatch path, but decode is bandwidth-bound and this is exactly how
   small-batch MoE serving reads weights anyway.
3. plain fallback  — no mesh installed (CPU smoke tests): same math as
   ``dense``.

Capacity model: per-destination-shard capacity C = ceil(T_local * topk * cf
/ n_shards); overflow tokens are dropped (standard GShard behaviour), tests
use cf large enough for zero drops when checking dispatch == dense.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.layers import apply_linear, init_linear
from .common import act_fn, get_mesh, shard, BATCH_AXES, TENSOR_AXIS
from .config import ModelConfig

Array = jax.Array


def init_moe(key: Array, cfg: ModelConfig) -> dict:
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    kr, kg, ku, kd = jax.random.split(key, 4)
    dt = cfg.pdtype
    scale_in = 1.0 / math.sqrt(d)
    scale_out = 1.0 / math.sqrt(ff)
    return {
        "router": (jax.random.normal(kr, (d, E)) * scale_in).astype(jnp.float32),
        "w_gate": (jax.random.normal(kg, (E, d, ff)) * scale_in).astype(dt),
        "w_up": (jax.random.normal(ku, (E, d, ff)) * scale_in).astype(dt),
        "w_down": (jax.random.normal(kd, (E, ff, d)) * scale_out).astype(dt),
    }


def moe_param_specs(cfg: ModelConfig) -> dict:
    """E replicated; d_model FSDP-sharded over data; d_ff TP over model."""
    return {
        "router": P(None, None),
        "w_gate": P(None, "data", TENSOR_AXIS),
        "w_up": P(None, "data", TENSOR_AXIS),
        "w_down": P(None, TENSOR_AXIS, "data"),
    }


def _route(x2d: Array, router: Array, cfg: ModelConfig
           ) -> Tuple[Array, Array]:
    """top-k routing.  x2d: (T, d) -> (weights (T,k), experts (T,k))."""
    logits = x2d.astype(jnp.float32) @ router
    weights, experts = jax.lax.top_k(logits, cfg.top_k)
    weights = jax.nn.softmax(weights, axis=-1)
    return weights, experts


# ---------------------------------------------------------------------------
# Path 2/3: dense-masked (decode, smoke tests, reference)
# ---------------------------------------------------------------------------
def moe_dense(params: dict, x: Array, cfg: ModelConfig) -> Array:
    B, S, d = x.shape
    act = act_fn(cfg.act)
    x2 = x.reshape(-1, d)
    weights, experts = _route(x2, params["router"], cfg)       # (T,k)
    comb = jnp.zeros((x2.shape[0], cfg.n_experts), jnp.float32)
    comb = comb.at[jnp.arange(x2.shape[0])[:, None], experts].add(weights)
    # all experts on all tokens, masked combine
    g = jnp.einsum("td,edf->tef", x2, params["w_gate"].astype(x.dtype))
    u = jnp.einsum("td,edf->tef", x2, params["w_up"].astype(x.dtype))
    h = act(g) * u                                             # (T,E,ff)
    o = jnp.einsum("tef,efd->ted", h, params["w_down"].astype(x.dtype))
    y = jnp.einsum("ted,te->td", o.astype(jnp.float32), comb)
    return y.reshape(B, S, d).astype(x.dtype)


# ---------------------------------------------------------------------------
# Path 1: shard_map dispatch with all_to_all (training / prefill)
# ---------------------------------------------------------------------------
def _local_pack(x2, weights, experts, n_dest: int, cap: int, repl: int,
                n_experts: int):
    """Pack local tokens into (n_dest, cap, d) send buffers.

    Destination shard for expert e, replica r: ``e * repl + r``; tokens are
    spread round-robin over replicas.  Returns (buf, combine info)."""
    T, d = x2.shape
    k = experts.shape[1]
    flat_e = experts.reshape(-1)                         # (T*k,)
    flat_t = jnp.repeat(jnp.arange(T), k)
    flat_w = weights.reshape(-1)
    # rank of each (token, expert-slot) within its expert queue
    onehot = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)   # (Tk, E)
    rank = (jnp.cumsum(onehot, axis=0) - 1)[jnp.arange(T * k), flat_e]
    dest = flat_e * repl + (rank % repl)                 # spread over replicas
    slot = rank // repl
    ok = slot < cap
    slot = jnp.where(ok, slot, 0)
    buf = jnp.zeros((n_dest, cap, d), x2.dtype)
    buf = buf.at[dest, slot].add(jnp.where(ok[:, None], x2[flat_t], 0))
    return buf, (flat_t, flat_w, dest, slot, ok)


def _local_unpack(recv_y, info, T: int, d: int):
    flat_t, flat_w, dest, slot, ok = info
    y_tok = recv_y[dest, slot]                            # (T*k, d)
    y_tok = jnp.where(ok[:, None], y_tok, 0.0) * flat_w[:, None]
    y = jnp.zeros((T, d), recv_y.dtype).at[flat_t].add(y_tok)
    return y


def moe_dispatch(params: dict, x: Array, cfg: ModelConfig) -> Array:
    """shard_map + all_to_all expert parallelism over the batch axes."""
    mesh = get_mesh()
    assert mesh is not None
    dp_axes = tuple(a for a in BATCH_AXES if a in mesh.axis_names)
    n_dest = math.prod(mesh.shape[a] for a in dp_axes)
    tp = mesh.shape.get(TENSOR_AXIS, 1)
    E = cfg.n_experts
    repl = max(1, n_dest // E)            # replicas per expert
    assert E * repl == n_dest, \
        f"experts {E} not mappable onto {n_dest} data shards"
    act = act_fn(cfg.act)

    B, S, d = x.shape
    T_local = (B // n_dest) * S
    cap = max(1, math.ceil(T_local * cfg.top_k * cfg.capacity_factor / n_dest))

    # slot-major expert weights: (n_dest, d, ff) — XLA inserts the expert
    # all-gather here (and a reduce-scatter for the gradient)
    def slots(w, transpose=False):
        wE = jnp.repeat(w, repl, axis=0) if repl > 1 else w
        spec = P(dp_axes, TENSOR_AXIS, None) if transpose else P(dp_axes, None, TENSOR_AXIS)
        return jax.lax.with_sharding_constraint(
            wE, jax.sharding.NamedSharding(mesh, spec))

    w_gate = slots(params["w_gate"].astype(x.dtype))
    w_up = slots(params["w_up"].astype(x.dtype))
    w_down = slots(params["w_down"].astype(x.dtype), transpose=True)

    def local_fn(x_l, router, wg_l, wu_l, wd_l):
        # x_l: (B_l, S, d); w*_l: (1, d, ff/tp) — this shard's expert slot
        Bl = x_l.shape[0]
        x2 = x_l.reshape(-1, d)
        weights, experts = _route(x2, router, cfg)
        buf, info = _local_pack(x2, weights, experts, n_dest, cap, repl, E)
        # exchange: (n_dest, cap, d) -> (n_dest, cap, d) with rows from peers
        recv = jax.lax.all_to_all(buf, dp_axes, split_axis=0, concat_axis=0,
                                  tiled=True)
        tok = recv.reshape(-1, d)                       # (n_dest*cap, d)
        g = tok @ wg_l[0]
        u = tok @ wu_l[0]
        h = act(g) * u                                  # (Ttok, ff/tp)
        y = h @ wd_l[0]                                 # partial over ff
        if tp > 1 and TENSOR_AXIS in mesh.axis_names:
            y = jax.lax.psum(y, TENSOR_AXIS)
        y = y.reshape(n_dest, cap, d)
        back = jax.lax.all_to_all(y, dp_axes, split_axis=0, concat_axis=0,
                                  tiled=True)
        out = _local_unpack(back, info, x2.shape[0], d)
        return out.reshape(Bl, S, d).astype(x_l.dtype)

    return jax.shard_map(
        local_fn, mesh=mesh, check_vma=False,
        in_specs=(P(dp_axes, None, None), P(None, None),
                  P(dp_axes, None, TENSOR_AXIS), P(dp_axes, None, TENSOR_AXIS),
                  P(dp_axes, TENSOR_AXIS, None)),
        out_specs=P(dp_axes, None, None),
    )(x, params["router"], w_gate, w_up, w_down)


def moe_ffn(params: dict, x: Array, cfg: ModelConfig, *,
            force_dense: bool = False) -> Array:
    """Entry point: picks the execution path."""
    mesh = get_mesh()
    if mesh is None or force_dense:
        return moe_dense(params, x, cfg)
    dp_axes = tuple(a for a in BATCH_AXES if a in mesh.axis_names)
    n_dp = math.prod(mesh.shape[a] for a in dp_axes)
    B, S, _ = x.shape
    # dispatch path needs: batch divisible over the data shards, an integer
    # replica count, and enough local tokens to fill capacity buffers;
    # otherwise use the dense-masked path (decode / tiny batches)
    if B % n_dp != 0 or n_dp % cfg.n_experts != 0:
        return moe_dense(params, x, cfg)
    if (B // n_dp) * S < cfg.n_experts and not cfg.moe_decode_dispatch:
        return moe_dense(params, x, cfg)    # decode default: weights resident
    return moe_dispatch(params, x, cfg)
