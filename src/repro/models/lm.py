"""Full language model: init / param specs / train forward / prefill /
decode, with scan-over-groups (stacked params) and per-group remat."""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.layers import (
    EpLayerConfig, constrained_sharding, placement_pspec, prepack_tree,
)
from .blocks import (
    apply_group, decode_group, init_group, init_group_state, prefill_group,
)
from .common import (
    BATCH_AXES, TENSOR_AXIS, embed_lookup, init_rms_norm, rms_norm, shard,
    softcap, unembed,
)
from .config import LayerKind, ModelConfig

Array = jax.Array


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def init_params(key: Array, cfg: ModelConfig) -> Dict[str, Any]:
    k_embed, k_groups, k_head = jax.random.split(key, 3)
    group_keys = jax.random.split(k_groups, cfg.n_groups)
    groups = jax.vmap(lambda k: init_group(k, cfg))(group_keys)
    params = {
        "embed": (jax.random.normal(k_embed, (cfg.vocab, cfg.d_model))
                  / math.sqrt(cfg.d_model)).astype(cfg.pdtype),
        "groups": groups,
        "final_norm": init_rms_norm(cfg.d_model, cfg.pdtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = (jax.random.normal(k_head, (cfg.d_model, cfg.vocab))
                          / math.sqrt(cfg.d_model)).astype(cfg.pdtype)
    return params


# ---------------------------------------------------------------------------
# Weight-stationary serving: vmapped tree prepack over the group axis
# ---------------------------------------------------------------------------
def lm_layer_configs(cfg: ModelConfig) -> Dict[str, EpLayerConfig]:
    """Every projection site's EpLayerConfig, keyed by param-tree path.

    The enumeration is pim.workloads.lm_layers — the same inventory the LM
    planners target — so a plan-driven ``cfg.layer_config`` and the global
    EpitomeSettings fallback both resolve here exactly as they do at each
    traced apply (init, forward, prepack all agree on specs by name)."""
    from ..pim.workloads import lm_layers
    return {l.name: cfg.ep(l.rows, l.cols, l.name) for l in lm_layers(cfg)}


def needs_prepack(cfg: ModelConfig) -> bool:
    """True iff any projection runs the fused kernel x quant path — the
    combination whose epitome should be packed to int8 once, not
    re-quantized inside every jitted forward."""
    return any(lc.is_epitome and lc.quant is not None and lc.mode == "kernel"
               for lc in lm_layer_configs(cfg).values())


def prepack_params(params: Dict[str, Any], cfg: ModelConfig,
                   mesh=None) -> Dict[str, Any]:
    """Pack every kernel x quant epitome in the scanned param tree once.

    ``params["groups"]`` stacks each leaf over a leading group axis, so
    prepack_tree vmaps the per-layer pack over that axis; the resulting
    Eq/Es/Ez leaves slice per group inside ``lax.scan`` like every other
    stacked leaf, and decode feeds the fused int8 kernel pure prepacked
    codes (weight-stationary serving).  Logits are bit-identical to the
    on-the-fly path — the same pack just runs once instead of per call.

    With ``mesh``, prepack_tree lays the packed codes of placement-carrying
    layers out with a NamedSharding from the plan as they are produced, and
    shard_params covers the rest of the tree (embed / head / norms, and
    layers without a placement record) with the bit-exact serving specs —
    one call produces a fully sharded weight-stationary param tree.
    shard_params resolves the placement-carrying layers to the identical
    shardings prepack_tree already applied, so its device_put on those
    leaves is a no-op."""
    out = dict(params)
    out["groups"] = prepack_tree(params["groups"], lm_layer_configs(cfg),
                                 mesh=mesh)
    if mesh is not None:
        out = shard_params(out, cfg, mesh)
    return out


# ---------------------------------------------------------------------------
# Sharding specs (FSDP over 'data', TP over 'model'; DESIGN.md §5)
#
# Resolution order (param_specs): a layer named by the plan-driven
# ``cfg.layer_config`` with a placement record is sharded exactly as the
# plan says (placement_pspec); everything else falls back to the
# hard-coded role rules below — _leaf_spec (training FSDP x TP) or
# _serving_leaf_spec (bit-exact column-parallel serving).
# ---------------------------------------------------------------------------
def _leaf_spec(path: str, shape: Tuple[int, ...]) -> P:
    """Spec by parameter role.  Fan-in is FSDP-sharded over 'data', fan-out
    TP-sharded over 'model' (transposed for down/out projections so the
    large d_ff/heads dim is always on 'model')."""
    def last(*names):
        return any(path.endswith(n) for n in names)

    if last("/embed"):
        return P(TENSOR_AXIS, "data")
    if last("/head"):
        return P("data", TENSOR_AXIS)
    if last("/router"):
        return P(None, None)

    # prepacked fused-kernel leaves (prepack_params): the int8 codes Eq are
    # E-shaped and shard exactly like E; the per-crossbar-tile scale/zero
    # grids Es/Ez are tiny and replicate
    if path.endswith("/Eq"):
        return _leaf_spec(path[:-1], shape)
    if path.endswith("/Es") or path.endswith("/Ez"):
        return P(*([None] * len(shape)))

    # rwkv channel-mix lives under /ffn/: wk is (d, ff) fan-out, wv is
    # (ff, d) fan-in (the mixer's wk/wv are (d, d) fan-out, handled below)
    if "/ffn/" in path:
        if last("wk/W", "wk/E", "wr/W", "wr/E"):
            return P(None, "data", TENSOR_AXIS)
        if last("wv/W", "wv/E"):
            return P(None, TENSOR_AXIS, "data")

    # fan-out projections: output dim on 'model', input dim FSDP on 'data'
    fan_out = ("wq/W", "wk/W", "wv/W", "wg/W", "wr/W", "in_proj/W",
               "x_proj/W", "dt_proj/W", "w_gate/W", "w_up/W", "wq/E",
               "wk/E", "wv/E", "wg/E", "wr/E", "in_proj/E", "x_proj/E",
               "dt_proj/E", "w_gate/E", "w_up/E")
    # fan-in projections: input dim on 'model' (it carries d_ff / heads)
    fan_in = ("wo/W", "out_proj/W", "w_down/W", "wo/E", "out_proj/E",
              "w_down/E")

    if last(*fan_out):
        if len(shape) == 4:        # MoE expert sites (G, E_held, d, ff)
            return P(None, None, "data", TENSOR_AXIS)
        return P(None, "data", TENSOR_AXIS)
    if last(*fan_in):
        if len(shape) == 4:
            return P(None, None, TENSOR_AXIS, "data")
        return P(None, TENSOR_AXIS, "data")
    # everything else (norms, biases, mu's, conv, LoRAs, decay, scalars) is
    # small: replicated
    return P(*([None] * len(shape)))


def _serving_leaf_spec(path: str, shape: Tuple[int, ...]) -> P:
    """Bit-exact serving default for layers no plan names: the role-based
    column-parallel placement (core.placement.default_placement) applied by
    path.  Only output dims shard — contraction (fan-in) dims replicate, so
    the sharded logits stay bit-identical to the single-device path (row
    sharding reorders the partial-sum accumulation)."""
    from ..core.placement import default_placement
    if path.endswith("/embed"):
        # (vocab, d): vocab rows gather exactly; d is every matmul's
        # contraction dim (and the tied head's) — keep it whole
        return P(TENSOR_AXIS, None)
    if path.endswith("/head"):
        return P(None, TENSOR_AXIS)
    if path.endswith("/router"):
        return P(None, None)
    name, _, leaf = path.rpartition("/")
    name = name[len("/groups/"):] if name.startswith("/groups/") else name
    if leaf in ("E", "W", "Eq", "Es", "Ez", "b") and name:
        return placement_pspec(default_placement(name), leaf, len(shape))
    return P(*([None] * len(shape)))


def param_specs(cfg: ModelConfig, params_shape: Dict[str, Any], *,
                serving: bool = False) -> Dict[str, Any]:
    """PartitionSpec tree matching the params tree (built from eval_shape).

    Layers the plan-driven ``cfg.layer_config`` names are sharded by their
    placement record; unlisted leaves fall back to the hard-coded role
    rules — FSDP x TP for training, or the bit-exact column-parallel
    serving layout when ``serving=True``."""
    placements = {name: lc.placement for name, lc in cfg.layer_config
                  if lc.placement is not None}
    fallback = _serving_leaf_spec if serving else _leaf_spec

    def leaf_spec(prefix, shape):
        name, _, leaf = prefix.rpartition("/")
        if name.startswith("/groups/"):
            pl = placements.get(name[len("/groups/"):])
            if pl is not None:
                return placement_pspec(pl, leaf, len(shape))
        return fallback(prefix, shape)

    def walk(tree, prefix):
        if isinstance(tree, dict):
            return {k: walk(v, f"{prefix}/{k}") for k, v in tree.items()}
        if isinstance(tree, tuple):
            return tuple(walk(v, f"{prefix}/{i}") for i, v in enumerate(tree))
        return leaf_spec(prefix, tree.shape)
    return walk(params_shape, "")


def shard_params(params: Dict[str, Any], cfg: ModelConfig,
                 mesh) -> Dict[str, Any]:
    """Lay a (possibly prepacked) param tree out on ``mesh`` for serving:
    plan placements where the config carries them, the bit-exact serving
    defaults elsewhere.  Axes that do not divide their dim degrade to
    replicated (constrained_sharding) instead of crashing.  Leaves already
    in that layout are returned as they are, not copied."""
    specs = param_specs(cfg, jax.eval_shape(lambda: params), serving=True)
    # params leads the tree.map, so each of its array leaves picks up the
    # corresponding PartitionSpec from the specs tree whole
    return jax.tree.map(
        lambda leaf, sp: jax.device_put(
            leaf, constrained_sharding(mesh, sp, leaf.shape)),
        params, specs)


# ---------------------------------------------------------------------------
# Forward (training)
# ---------------------------------------------------------------------------
def forward(params: Dict[str, Any], inputs: Array, cfg: ModelConfig,
            remat: bool = True) -> Array:
    """inputs: (B, S) int32 token ids, or (B, S, d) embeddings (modality
    stub).  Returns logits (B, S, vocab)."""
    if inputs.ndim == 2:
        x = embed_lookup(params["embed"], inputs, cfg.cdtype)
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.cdtype)
    else:
        x = inputs.astype(cfg.cdtype)
    x = shard(x, BATCH_AXES, TENSOR_AXIS, None)

    body = partial(apply_group, cfg=cfg)
    if remat:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat_policy == "dots"
                  else jax.checkpoint_policies.nothing_saveable)
        body = jax.checkpoint(body, policy=policy)

    def scan_fn(x, group_params):
        return body(group_params, x), None

    x, _ = jax.lax.scan(scan_fn, x, params["groups"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("head", None)
    if head is None:
        head = params["embed"].T
    logits = unembed(x, head, cfg.logit_softcap)
    return shard(logits, BATCH_AXES, None, TENSOR_AXIS)


def loss_fn(params: Dict[str, Any], batch: Dict[str, Array],
            cfg: ModelConfig) -> Array:
    """Next-token cross entropy.  batch: tokens (B,S) [+ optional embeds]."""
    inputs = batch.get("embeds", batch["tokens"])
    logits = forward(params, inputs, cfg)
    labels = batch["labels"]
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    onehot_ll = jnp.sum(
        jnp.where(labels[..., None] == jnp.arange(cfg.vocab)[None, None],
                  logits, 0.0), axis=-1)
    mask = batch.get("mask", jnp.ones_like(labels, jnp.float32))
    nll = (logz - onehot_ll) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------
def init_decode_state(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    """Per-group decode states stacked over groups (leading axis G)."""
    one = jax.eval_shape(lambda: init_group_state(cfg, batch, max_len))
    def stack_init(leaf):
        return jnp.zeros((cfg.n_groups,) + leaf.shape, leaf.dtype)
    return jax.tree.map(stack_init, one)


# ---------------------------------------------------------------------------
# Slot-pooled decode state (continuous batching; launch/engine.py)
#
# The engine owns ONE pooled decode-state abstraction — SlotStatePool in
# models/kv_pool.py — whose batch axis (axis 1, after the stacked group
# axis) is a pool of request slots: dense fixed-size recurrent rows per
# slot for the SSM arches, and a *block-paged* global KV pool + per-slot
# page table for attention arches (dense per-slot max_len blocks when
# paging is off).  A finished request frees its slot (and pages) and the
# next admission scatters a fresh prefill state over it.  The old
# init_state_pool / scatter_slot_state / gather_slot_state free functions
# are now SlotStatePool methods; the class is re-exported here so the
# launch layer keeps importing its state interface from models.lm.
# ---------------------------------------------------------------------------
from .kv_pool import SlotStatePool  # noqa: E402  (re-export; see above)


def prefill(params: Dict[str, Any], inputs: Array, state: Dict[str, Any],
            cfg: ModelConfig, valid_len: Optional[Array] = None,
            chunk_start: Optional[Array] = None
            ) -> Tuple[Array, Dict[str, Any]]:
    """Run the prompt, fill decode state.  Returns (last-token logits, state).

    ``valid_len`` (traced scalar) marks a right-padded bucketed prefill
    (launch/engine.py pads prompts up to power-of-two buckets so distinct
    prompt lengths share one compiled program): only the first
    ``valid_len`` tokens are real.  The returned logits are gathered at
    the last *real* token and the per-layer states are masked so pads
    never touch them — the result is bit-identical to an unpadded prefill
    of the same prompt.

    ``chunk_start`` (traced scalar) makes this one *chunk* of a chunked
    prefill: ``inputs`` is the chunk (already chunk-local), ``state``
    carries the earlier chunks, and the chunk's rows live at sequence
    positions chunk_start..chunk_start+C-1.  ``valid_len`` then counts the
    real tokens *within this chunk*.  The engine calls this once per
    chunk, interleaved with decode ticks, instead of once per prompt."""
    if inputs.ndim == 2:
        x = embed_lookup(params["embed"], inputs, cfg.cdtype)
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.cdtype)
    else:
        x = inputs.astype(cfg.cdtype)

    def scan_fn(x, gs):
        group_params, group_state = gs
        x, new_state = prefill_group(group_params, group_state, x, cfg,
                                     valid_len=valid_len,
                                     chunk_start=chunk_start)
        return x, new_state

    x, new_states = jax.lax.scan(scan_fn, x, (params["groups"], state))
    x_last = (x[:, -1:] if valid_len is None else
              jax.lax.dynamic_slice_in_dim(x, valid_len - 1, 1, 1))
    x = rms_norm(x_last, params["final_norm"], cfg.norm_eps)
    head = params.get("head", params["embed"].T if cfg.tie_embeddings else None)
    logits = unembed(x, head, cfg.logit_softcap)
    return logits, new_states


def decode_step(params: Dict[str, Any], state: Dict[str, Any], token: Array,
                pos: Array, cfg: ModelConfig,
                page_table: Optional[Array] = None, routed: bool = False):
    """token: (B, 1) int32 (or (B, 1, d) embeddings); pos: scalar int32,
    or (B,) int32 per-row positions (continuous batching: every slot of
    the engine's state pool sits at its own sequence position).
    ``page_table`` (B, pages_per_slot, requires per-row pos): the state's
    attention k/v leaves are a shared block-paged pool read/written
    through the table (models/kv_pool.py) instead of dense per-row rows.
    Returns (logits (B, 1, vocab), new state), and with ``routed`` also
    the (B,) int32 count of (token, held expert) pairs the MoE layers
    routed per row."""
    if token.ndim == 2:
        x = embed_lookup(params["embed"], token, cfg.cdtype)
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.cdtype)
    else:
        x = token.astype(cfg.cdtype)

    # The state rides the carry and each group's new state is written back
    # at its index, so a donated pool is updated in its own buffer.  As
    # scan xs/ys it went to a fresh buffer that was then copied whole.
    def scan_fn(carry, gp):
        x, state, n = carry
        i, group_params = gp
        group_state = jax.tree.map(
            lambda l: jax.lax.dynamic_index_in_dim(l, i, 0, keepdims=False),
            state)
        x, new_state, r = decode_group(group_params, group_state, x, pos,
                                       cfg, page_table=page_table)
        state = jax.tree.map(
            lambda l, n: jax.lax.dynamic_update_index_in_dim(l, n, i, 0),
            state, new_state)
        return (x, state, n + r), None

    n0 = jnp.zeros((x.shape[0],), jnp.int32)
    (x, state, n), _ = jax.lax.scan(
        scan_fn, (x, state, n0), (jnp.arange(cfg.n_groups), params["groups"]))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("head", params["embed"].T if cfg.tie_embeddings else None)
    logits = unembed(x, head, cfg.logit_softcap)
    return (logits, state, n) if routed else (logits, state)


def decode_scan(params: Dict[str, Any], state: Dict[str, Any], tok: Array,
                pos: Array, cfg: ModelConfig, aux: Any, sample, k: int,
                page_table: Optional[Array] = None):
    """Fuse ``k`` decode micro-steps into ONE ``lax.scan`` over the decode
    state — one device dispatch per K tokens instead of one per token.

    ``sample(logits, aux) -> (toks, aux, live)`` is the caller's sampling
    policy, traced into the scan body: ``logits`` is the (B, vocab)
    last-position row, ``toks`` the (B,) int32 next tokens, and ``live``
    a (B,) bool mask of rows still generating.  Rows where ``live`` is
    False are FROZEN: their carried token and position stop advancing, so
    a row that hits its stop condition at micro-step j < k keeps replaying
    its final (token, position) pair for the remaining micro-steps — the
    KV row it rewrites is the one it already owns (never past its page
    reservation), and per-row independence keeps the dead row's arithmetic
    away from live rows exactly as it does for freed slots.  State is
    deliberately NOT masked per row (that would copy the whole pool every
    micro-step): frozen attention rows rewrite their own cache rows
    idempotently and frozen recurrent rows advance into garbage a later
    ``scatter`` overwrites wholesale.  ``decode_step`` writes each layer
    group's slice of the carried state in place, so a donated pool is
    updated in its own buffer.

    ``page_table`` rides the scan as a loop-invariant operand: admission
    reserves every page a request will ever touch up front, so advancing
    ``pos`` inside the carry walks the table across page boundaries
    without the host re-mapping anything mid-scan.

    Returns ``(state, tok, pos, aux, toks, live, routed)`` with
    ``toks``/``live``/``routed`` stacked (k, B) — the per-micro-step
    emissions, their validity, and the (token, held expert) pairs each
    live row's MoE layers routed (``decode_step``'s count, 0 on frozen
    rows)."""
    def body(carry, _):
        state, tok, pos, aux = carry
        logits, state, n = decode_step(params, state, tok, pos, cfg,
                                       page_table=page_table, routed=True)
        toks, aux, live = sample(logits[:, -1], aux)
        tok = jnp.where(live[:, None], toks[:, None].astype(tok.dtype), tok)
        pos = jnp.where(live, pos + 1, pos)
        return (state, tok, pos, aux), (toks, live, jnp.where(live, n, 0))

    (state, tok, pos, aux), (toks, live, routed) = jax.lax.scan(
        body, (state, tok, pos, aux), None, length=k)
    return state, tok, pos, aux, toks, live, routed
