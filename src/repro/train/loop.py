"""Train step builder + fault-tolerant loop.

make_train_step(cfg, opt_cfg, grad_accum) -> step(state, batch) with:
 * microbatch gradient accumulation via lax.scan (activation memory is one
   microbatch; carries are fp32 gradient buffers, FSDP-sharded);
 * per-layer remat (lm.forward);
 * optional int8 gradient compression with error feedback — applied to the
   accumulated gradient before the optimizer; on the multi-pod mesh this is
   the cross-pod traffic reduction (the int8 payload is what crosses the
   DCN), with the residual carried to the next step;
 * optimizer with int8 moments / bf16 params / fp32 masters (optimizer.py).

The loop adds: checkpoint-every-N with async writes, restart recovery,
SIGTERM preemption checkpointing, and a straggler watchdog that flags steps
slower than `straggler_factor` x the running median (at fleet scale the
launcher remaps the slow pod; on one host we log and count).
"""
from __future__ import annotations

import dataclasses
import signal
import time
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.layers import constrained_sharding
from ..models import lm
from ..models.config import ModelConfig
from .optimizer import AdamWConfig, adamw_init, adamw_update, global_norm

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    grad_accum: int = 1
    accum_dtype: str = "float32"      # bf16 halves the accumulation carry
                                      # (grok-scale models on a single pod)
    compress_grads: bool = False      # int8 + error feedback
    checkpoint_every: int = 50
    log_every: int = 10
    straggler_factor: float = 2.0


# ---------------------------------------------------------------------------
# Gradient compression (int8, error feedback)
# ---------------------------------------------------------------------------
def _compress_ef(g: Array, residual: Array) -> Tuple[Array, Array]:
    g = g.astype(jnp.float32) + residual
    scale = jnp.max(jnp.abs(g)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(g / scale), -127, 127)
    deq = q * scale                    # this int8 payload is what crosses DCN
    return deq, g - deq


def compress_grads_ef(grads: Any, residuals: Any) -> Tuple[Any, Any]:
    pairs = jax.tree.map(_compress_ef, grads, residuals)
    deq = jax.tree.map(lambda p: p[0], pairs,
                       is_leaf=lambda x: isinstance(x, tuple))
    res = jax.tree.map(lambda p: p[1], pairs,
                       is_leaf=lambda x: isinstance(x, tuple))
    return deq, res


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------
def init_state(key: Array, cfg: ModelConfig, opt_cfg: AdamWConfig,
               train_cfg: TrainConfig = TrainConfig()) -> Dict[str, Any]:
    params = lm.init_params(key, cfg)
    state = {"params": params, "opt": adamw_init(params, opt_cfg),
             "step": jnp.zeros((), jnp.int32)}
    if train_cfg.compress_grads:
        state["ef_residual"] = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return state


def state_sharding_tree(cfg: ModelConfig, opt_cfg: AdamWConfig,
                        train_cfg: TrainConfig = TrainConfig()):
    """(abstract state, matching PartitionSpecs) for ``init_state``.

    Optimizer moments mirror the parameter specs; int8 moments are tuples
    (q, scale) that both inherit the param's spec (q is shape-preserving,
    the scale has the same rank with a smaller last dim)."""
    state_shape = jax.eval_shape(
        partial(init_state, cfg=cfg, opt_cfg=opt_cfg, train_cfg=train_cfg),
        jax.random.PRNGKey(0))
    p_specs = lm.param_specs(cfg, state_shape["params"])
    if opt_cfg.moments_dtype == "int8":
        m_specs = jax.tree.map(lambda sp: (sp, sp), p_specs,
                               is_leaf=lambda x: isinstance(x, P))
    else:
        m_specs = p_specs
    specs: Dict[str, Any] = {
        "params": p_specs,
        "step": P(),
        "opt": {"step": P(), "m": m_specs, "v": m_specs},
    }
    if "master" in state_shape["opt"]:
        specs["opt"]["master"] = p_specs
    if "ef_residual" in state_shape:
        specs["ef_residual"] = p_specs
    return state_shape, specs


def shaped(tree_shape: Any, tree_spec: Any, mesh) -> Any:
    """ShapeDtypeStructs with shardings attached (no allocation); mesh axes
    that are absent or do not divide a dim are dropped."""
    def one(sds, spec):
        return jax.ShapeDtypeStruct(
            sds.shape, sds.dtype,
            sharding=constrained_sharding(mesh, spec, sds.shape))
    return jax.tree.map(one, tree_shape, tree_spec,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig,
                    train_cfg: TrainConfig = TrainConfig()) -> Callable:
    accum = train_cfg.grad_accum

    def step_fn(state: Dict[str, Any], batch: Dict[str, Array]):
        params = state["params"]

        if accum > 1:
            adt = jnp.dtype(train_cfg.accum_dtype)

            def micro(g_sum, mb):
                loss, g = jax.value_and_grad(lm.loss_fn)(params, mb, cfg)
                g_sum = jax.tree.map(
                    lambda a, b: a + b.astype(adt), g_sum, g)
                return g_sum, loss

            mbs = jax.tree.map(
                lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]),
                batch)
            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, adt), params)
            grads, losses = jax.lax.scan(micro, g0, mbs)
            grads = jax.tree.map(lambda g: g / accum, grads)
            loss = losses.mean()
        else:
            loss, grads = jax.value_and_grad(lm.loss_fn)(params, batch, cfg)

        new_state = dict(state)
        if train_cfg.compress_grads:
            grads, new_state["ef_residual"] = compress_grads_ef(
                grads, state["ef_residual"])

        params, opt, metrics = adamw_update(params, grads, state["opt"], opt_cfg)
        new_state.update(params=params, opt=opt, step=state["step"] + 1)
        metrics["loss"] = loss
        return new_state, metrics

    return step_fn


# ---------------------------------------------------------------------------
# Fault-tolerant loop (single-host driver; the pod launcher wraps this)
# ---------------------------------------------------------------------------
def train_loop(state, step_fn, data, n_steps: int,
               ckpt=None, train_cfg: TrainConfig = TrainConfig(),
               log=print) -> Tuple[Any, Dict[str, list]]:
    preempted = {"flag": False}

    def _sigterm(_sig, _frm):
        preempted["flag"] = True
    old = signal.signal(signal.SIGTERM, _sigterm)

    start = int(state["step"])
    history: Dict[str, list] = {"loss": [], "step_time": [], "stragglers": []}
    times: list = []
    try:
        for step in range(start, n_steps):
            t0 = time.perf_counter()
            batch = data.batch(step)
            state, metrics = step_fn(state, batch)
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            times.append(dt)
            med = sorted(times)[len(times) // 2]
            if len(times) > 5 and dt > train_cfg.straggler_factor * med:
                history["stragglers"].append(step)
                log(f"[straggler] step {step}: {dt:.3f}s vs median {med:.3f}s")
            history["loss"].append(float(metrics["loss"]))
            history["step_time"].append(dt)
            if step % train_cfg.log_every == 0:
                log(f"step {step}: loss={float(metrics['loss']):.4f} "
                    f"gnorm={float(metrics['grad_norm']):.3f} {dt*1e3:.0f}ms")
            if ckpt is not None and (step + 1) % train_cfg.checkpoint_every == 0:
                ckpt.save(step + 1, state)
            if preempted["flag"]:
                log(f"[preempt] SIGTERM at step {step}; checkpointing and exiting")
                if ckpt is not None:
                    ckpt.save(step + 1, state, blocking=True)
                break
    finally:
        signal.signal(signal.SIGTERM, old)
        if ckpt is not None:
            ckpt.wait()
    return state, history
