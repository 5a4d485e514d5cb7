"""Mixture-of-Experts: a router over all experts and the experts a device
holds, each expert's projections an ``init_linear``/``apply_linear`` site
stacked over the held experts (so epitomes, prepack and the fused int8
kernel reach them).

Two execution paths:

1. per token (``moe_held``): every held expert computes every row and is
   combined with the row's routing weight (0 where the row did not pick
   it).  Dropless, with no capacity, so rows never couple: bucketing pads
   and prefill chunks change no real row.  It is the one-device path, and
   the path of a device that holds a share of the experts (expert
   parallelism, model-configs guide §4): it routes over all
   ``n_experts`` and returns its own experts' part of the output.
2. capacity dispatch (``moe_dispatch``) — training / prefill at scale on a
   mesh whose data shards map onto the experts: ``shard_map`` over the
   batch axes; tokens are routed locally (sort-free rank-within-expert via
   cumsum counts), packed into per-destination-shard capacity buffers,
   exchanged with ``lax.all_to_all``, run through the local expert, and
   returned.  Capacity C = ceil(T_local * topk * cf / n_shards); overflow
   tokens are dropped (GShard), which couples the tokens of a dispatch.
   ``takes_dispatch`` says when ``moe_ffn`` runs it.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.layers import apply_linear, effective_weight
from .common import act_fn, get_mesh, BATCH_AXES, TENSOR_AXIS
from .config import ModelConfig, layer_name as _nm

Array = jax.Array


_EXPERT_SITES = (("w_gate", False), ("w_up", False), ("w_down", True))


def _site_ep(cfg: ModelConfig, name: str, down: bool, prefix: str):
    d, ff = cfg.d_model, cfg.d_ff
    M, N = (ff, d) if down else (d, ff)
    return M, N, cfg.ep(M, N, _nm(prefix, name))


def init_moe(key: Array, cfg: ModelConfig, prefix: str = "") -> dict:
    """Router over all ``n_experts`` and the held experts' projections,
    each a linear site stacked over the held experts: ``{"W": (E_held, M,
    N)}`` dense, or ``{"E": (E_held, m, n)}`` an epitome per expert.  All
    experts are drawn in one call and the held ones kept, so a share's
    experts are the same whichever device holds them."""
    E = cfg.n_experts
    lo, hi = cfg.held_experts
    kr, *ks = jax.random.split(key, 4)
    params = {"router": (jax.random.normal(kr, (cfg.d_model, E))
                         * (1.0 / math.sqrt(cfg.d_model))).astype(jnp.float32)}
    for k, (name, down) in zip(ks, _EXPERT_SITES):
        M, N, lc = _site_ep(cfg, name, down, prefix)
        shape = (lc.spec.m, lc.spec.n) if lc.is_epitome else (M, N)
        w = (jax.random.normal(k, (E,) + shape)
             * (1.0 / math.sqrt(M))).astype(cfg.pdtype)
        params[name] = {"E" if lc.is_epitome else "W": w[lo:hi]}
    return params


def route(x2d: Array, router: Array, cfg: ModelConfig) -> Array:
    """(T, n_experts) float32 combine weights: each token's top-k experts
    carry their weight, the rest 0.  ``moe_renormalize``: softmax over the
    top-k logits; else the softmax over all experts, top-k kept as they
    are (jamba)."""
    logits = x2d.astype(jnp.float32) @ router
    if cfg.moe_renormalize:
        weights, experts = jax.lax.top_k(logits, cfg.top_k)
        weights = jax.nn.softmax(weights, axis=-1)
    else:
        weights, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                         cfg.top_k)
    T = x2d.shape[0]
    return jnp.zeros((T, cfg.n_experts), jnp.float32).at[
        jnp.arange(T)[:, None], experts].add(weights)


def _route(x2d: Array, router: Array, cfg: ModelConfig
           ) -> Tuple[Array, Array]:
    """top-k routing for the dispatch path: (weights (T,k), experts
    (T,k)), with ``route``'s weights."""
    comb = route(x2d, router, cfg)
    weights, experts = jax.lax.top_k(comb, cfg.top_k)
    return weights, experts


def _stacked_weight(p: dict, lc) -> Array:
    """(E_held, M, N) dense weights of a stacked expert site."""
    if "W" in p:
        return p["W"]
    return jax.vmap(lambda e: effective_weight({"E": e}, lc))(p["E"])


# ---------------------------------------------------------------------------
# Per-token path: one device, every held expert on every row
# ---------------------------------------------------------------------------
@jax.named_scope("epim.moe")
def moe_held(params: dict, x: Array, cfg: ModelConfig, prefix: str = ""
             ) -> Tuple[Array, Array]:
    """The held experts' part of the layer's output, and per row the
    number of held experts it was routed to.

    Routes over all ``n_experts``; each held expert runs on every row
    (its projections through ``apply_linear``, so an epitome expert runs
    the fused int8 kernel) and its output is combined with the row's
    routing weight, 0 where the row did not pick it.  Per token and
    dropless: no capacity, so no row changes another's result."""
    B, S, d = x.shape
    act = act_fn(cfg.act)
    x2 = x.reshape(-1, d)
    lo, hi = cfg.held_experts
    with jax.named_scope("epim.moe.route"):
        comb = route(x2, params["router"], cfg)[:, lo:hi]     # (T, E_held)
    lcs = {name: _site_ep(cfg, name, down, prefix)[2]
           for name, down in _EXPERT_SITES}

    def expert(y, inp):
        p, w = inp
        g = apply_linear(p["w_gate"], x2, lcs["w_gate"])
        u = apply_linear(p["w_up"], x2, lcs["w_up"])
        o = apply_linear(p["w_down"], act(g) * u, lcs["w_down"])
        return y + o.astype(jnp.float32) * w[:, None], None

    experts = {name: params[name] for name, _ in _EXPERT_SITES}
    y, _ = jax.lax.scan(expert, jnp.zeros((x2.shape[0], d), jnp.float32),
                        (experts, comb.T))
    routed = jnp.sum(comb > 0, axis=-1, dtype=jnp.int32).reshape(B, S)
    return y.reshape(B, S, d).astype(x.dtype), routed


def moe_dense(params: dict, x: Array, cfg: ModelConfig,
              prefix: str = "") -> Array:
    """``moe_held``'s output alone."""
    return moe_held(params, x, cfg, prefix)[0]


# ---------------------------------------------------------------------------
# Capacity dispatch: shard_map with all_to_all (training / prefill at scale)
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


def moe_dispatch(params: dict, x: Array, cfg: ModelConfig,
                 prefix: str = "") -> Array:
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

    assert cfg.held_experts == (0, E), "dispatch needs every expert held"
    w = {name: _stacked_weight(params[name],
                               _site_ep(cfg, name, down, prefix)[2])
         for name, down in _EXPERT_SITES}
    w_gate = slots(w["w_gate"].astype(x.dtype))
    w_up = slots(w["w_up"].astype(x.dtype))
    w_down = slots(w["w_down"].astype(x.dtype), transpose=True)

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


def takes_dispatch(cfg: ModelConfig, B: int, S: int) -> bool:
    """Whether ``moe_ffn`` runs the capacity dispatch for a (B, S) input
    under the installed mesh: the batch divides over the data shards, the
    shards map onto the experts, and there are enough local tokens to fill
    capacity buffers."""
    mesh = get_mesh()
    if mesh is None or "moe" not in cfg.ffn_pattern:
        return False
    dp_axes = tuple(a for a in BATCH_AXES if a in mesh.axis_names)
    n_dp = math.prod(mesh.shape[a] for a in dp_axes)
    if B % n_dp != 0 or n_dp % cfg.n_experts != 0:
        return False
    return (B // n_dp) * S >= cfg.n_experts


def moe_ffn(params: dict, x: Array, cfg: ModelConfig, prefix: str = "",
            *, force_dense: bool = False) -> Array:
    """Entry point: the capacity dispatch where ``takes_dispatch`` says so
    (multi-device meshes), else the per-token path."""
    B, S, _ = x.shape
    if not force_dense and takes_dispatch(cfg, B, S):
        return moe_dispatch(params, x, cfg, prefix)
    return moe_dense(params, x, cfg, prefix)
