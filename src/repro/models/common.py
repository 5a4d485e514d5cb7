"""Shared model pieces: norms, RoPE, activations, sharding helpers."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Array = jax.Array

# Logical mesh axes (launch/mesh.py): batch -> ('pod','data'), tensor -> 'model'
BATCH_AXES = ("pod", "data")
TENSOR_AXIS = "model"


_CURRENT_MESH: Optional[jax.sharding.Mesh] = None


def set_mesh(mesh: Optional[jax.sharding.Mesh]) -> None:
    """Install the mesh used by `shard()` constraints (launch code calls
    this; CPU smoke tests leave it unset and all constraints no-op)."""
    global _CURRENT_MESH
    _CURRENT_MESH = mesh


def get_mesh() -> Optional[jax.sharding.Mesh]:
    return _CURRENT_MESH


def clean_spec(*spec) -> P:
    """PartitionSpec with axes absent from the current mesh dropped."""
    mesh = _CURRENT_MESH
    names = set(mesh.axis_names) if mesh is not None else set()
    clean = []
    for s in spec:
        if s is None:
            clean.append(None)
        elif isinstance(s, (tuple, list)):
            keep = tuple(a for a in s if a in names)
            clean.append(keep if keep else None)
        else:
            clean.append(s if s in names else None)
    return P(*clean)


def shard(x: Array, *spec) -> Array:
    """with_sharding_constraint that no-ops without an installed mesh and
    drops axes that don't divide the corresponding dim (e.g. 8 KV heads on a
    16-way tensor axis) instead of forcing XLA to pad — the same
    degrade-to-replicated rule core.layers.constrained_sharding applies to
    placement-driven param layouts."""
    mesh = _CURRENT_MESH
    if mesh is None:
        return x
    from ..core.layers import constrained_sharding
    return jax.lax.with_sharding_constraint(
        x, constrained_sharding(mesh, P(*spec), x.shape))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def rms_norm(x: Array, w: Array, eps: float = 1e-6) -> Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(dt)


def init_rms_norm(d: int, dtype=jnp.float32) -> Array:
    return jnp.zeros((d,), dtype)   # stored as (w - 1), gemma convention


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_freqs(hd: int, theta: float) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (B, S, H, hd); positions: (B, S) or (S,).

    The rotated halves are assembled with stack+reshape rather than
    ``jnp.concatenate``: under a mesh, the concat formulation downstream of
    a head reshape whose head count does not divide the tensor axis (e.g.
    2 KV heads on a 4-way 'model' axis) trips a GSPMD partitioner bug on
    the CPU backend that re-reduces an already-replicated value — outputs
    come back exactly 2x too large, silently corrupting every sharded
    attention arch.  stack+reshape lowers to different HLO, produces
    bit-identical values on a single device, and partitions correctly, so
    sharded serving is bit-exact against the single-device path.  The
    constraint pins the head-parallel layout (degrading to replicated when
    heads don't divide the axis) so the choice is explicit, not
    propagation luck."""
    x = shard(x, BATCH_AXES, None, TENSOR_AXIS, None)
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (B,S,hd/2)
    if angles.ndim == 2:                                # (S, hd/2)
        angles = angles[None]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : hd // 2], xf[..., hd // 2:]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-2)
    return out.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations / capping
# ---------------------------------------------------------------------------
def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "relu": jax.nn.relu}[name]


def softcap(x: Array, cap: float) -> Array:
    if cap <= 0:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------
def embed_lookup(table: Array, ids: Array, compute_dtype) -> Array:
    return jnp.take(table, ids, axis=0).astype(compute_dtype)


def unembed(x: Array, table: Array, logit_cap: float = 0.0) -> Array:
    from ..core.layers import exact_dot
    logits = exact_dot(x, table)
    return softcap(logits, logit_cap)
