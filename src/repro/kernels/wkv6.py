"""Pallas TPU kernel: chunked RWKV6 (Finch) WKV with data-dependent decay.

One grid step processes one (batch*head, chunk) pair; the recurrent state
(K x V, fp32) lives in VMEM scratch and persists across the sequential
chunk dimension (TPU grids iterate the last axis innermost, so for a fixed
bh the chunks run in order).  All decay exponents are relative and
non-positive (see models/ssm.py derivation), so fp32 math is stable with no
rescaling pass.

Layout: r/k/v/logw (BH, S, K) -> blocks (1, L, K); out (BH, S, K);
u (BH, K) -> (1, K) per-head bonus.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, state_ref, *, L: int):
    @pl.when(pl.program_id(1) == 0)
    def _reset():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0].astype(jnp.float32)              # (L, K)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)            # <= 0
    u = u_ref[0].astype(jnp.float32)              # (K,)
    S = state_ref[...]                            # (K, V)

    cs = jnp.cumsum(lw, axis=0)                   # (L, K), <= 0
    cs_prev = cs - lw
    # inter-chunk: (r_t * A_{t-1}) @ S
    o = jnp.dot(r * jnp.exp(cs_prev), S, preferred_element_type=jnp.float32)
    # intra-chunk: scores_ti = sum_k r_tk exp(cs_prev_t - cs_i)_k k_ik (i<t)
    expo = cs_prev[:, None, :] - cs[None, :, :]   # (L, L, K)
    expo = jnp.where(expo > 0, 0.0, expo)
    scores = jnp.sum(r[:, None, :] * jnp.exp(expo) * k[None, :, :], axis=-1)
    tri = jnp.tril(jnp.ones((L, L), jnp.float32), k=-1)
    scores = scores * tri
    o += jnp.dot(scores, v, preferred_element_type=jnp.float32)
    # current-token bonus
    o += jnp.sum(r * u[None, :] * k, axis=-1, keepdims=True) * v
    o_ref[0] = o.astype(o_ref.dtype)
    # carry: S' = diag(A_L) S + sum_i (A_L/A_i * k_i)^T v_i
    cs_L = cs[-1:]                                # (1, K)
    k_dec = k * jnp.exp(cs_L - cs)                # (L, K)
    state_ref[...] = S * jnp.exp(cs_L).T + jnp.dot(
        k_dec.T, v, preferred_element_type=jnp.float32)


def wkv6_chunked(r: Array, k: Array, v: Array, logw: Array, u: Array,
                 *, chunk: int = 64, interpret: bool = False) -> Array:
    """r/k/v/logw: (BH, S, K); u: (BH, K).  Returns o: (BH, S, K).
    S must be a multiple of `chunk` (ops.py pads)."""
    BH, S, K = r.shape
    L = min(chunk, S)
    assert S % L == 0, (S, L)
    n = S // L
    grid = (BH, n)
    kernel = functools.partial(_kernel, L=L)
    blk = pl.BlockSpec((1, L, K), lambda bh, c: (bh, c, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[blk, blk, blk, blk,
                  pl.BlockSpec((1, K), lambda bh, c: (bh, 0))],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((BH, S, K), r.dtype),
        scratch_shapes=[pltpu.VMEM((K, K), jnp.float32)],
        interpret=interpret,
        name="epim_wkv6",
        metadata={"epim_kernel": "epim_wkv6"},
    )(r, k, v, logw, u)
