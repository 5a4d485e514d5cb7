"""Per-arch smoke tests + decode/prefill consistency + epitome modes.

Every assigned architecture is instantiated at a REDUCED same-family config
and run one forward/train step on CPU, asserting shapes and finiteness; the
full configs are built abstractly (ShapeDtypeStruct, no allocation)."""
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.models import lm
from repro.models.blocks import decode_group
from repro.models.common import embed_lookup, rms_norm, unembed
from repro.models.kv_pool import SlotStatePool

KEY = jax.random.PRNGKey(0)
B, S = 2, 16


def batch_for(cfg, key=KEY, b=B, s=S):
    toks = jax.random.randint(key, (b, s), 0, cfg.vocab)
    out = {"tokens": toks, "labels": toks,
           "mask": jnp.ones((b, s), jnp.float32)}
    if cfg.embed_inputs:
        out["embeds"] = jax.random.normal(key, (b, s, cfg.d_model)) * 0.02
    return out


@pytest.mark.slow
@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    cfg = get_smoke_config(arch)
    params = lm.init_params(KEY, cfg)
    batch = batch_for(cfg)
    loss, grads = jax.value_and_grad(lm.loss_fn)(params, batch, cfg)
    assert bool(jnp.isfinite(loss))
    assert np.isfinite(float(loss))
    for leaf in jax.tree.leaves(grads):
        assert bool(jnp.all(jnp.isfinite(leaf)))
    logits = lm.forward(params, batch.get("embeds", batch["tokens"]), cfg)
    assert logits.shape == (B, S, cfg.vocab)


@pytest.mark.slow
@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_decode(arch):
    cfg = get_smoke_config(arch)
    params = lm.init_params(KEY, cfg)
    state = lm.init_decode_state(cfg, B, 24)
    inputs = (jax.random.normal(KEY, (B, 8, cfg.d_model)) * 0.02
              if cfg.embed_inputs
              else jax.random.randint(KEY, (B, 8), 0, cfg.vocab))
    logits, state = lm.prefill(params, inputs, state, cfg)
    assert logits.shape == (B, 1, cfg.vocab)
    tok = (jax.random.normal(KEY, (B, 1, cfg.d_model)) * 0.02
           if cfg.embed_inputs
           else jnp.argmax(logits[:, -1:], -1).astype(jnp.int32))
    l2, _ = lm.decode_step(params, state, tok, jnp.int32(8), cfg)
    assert l2.shape == (B, 1, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(l2)))


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["qwen2-72b", "rwkv6-7b",
                                  "jamba-1.5-large-398b", "gemma2-2b",
                                  "phi3.5-moe-42b-a6.6b", "musicgen-large"])
def test_decode_matches_forward(arch):
    """prefill+decode logits == training forward logits (same math)."""
    cfg = get_smoke_config(arch)
    params = lm.init_params(jax.random.PRNGKey(42), cfg)
    if cfg.embed_inputs:
        seq = jax.random.normal(KEY, (B, S + 1, cfg.d_model)) * 0.02
        ref = lm.forward(params, seq, cfg, remat=False)[:, S]
        state = lm.init_decode_state(cfg, B, S + 8)
        _, state = lm.prefill(params, seq[:, :S], state, cfg)
        l2, _ = lm.decode_step(params, state, seq[:, S:S + 1],
                               jnp.int32(S), cfg)
    else:
        seq = jax.random.randint(jax.random.PRNGKey(7), (B, S + 1), 0, cfg.vocab)
        ref = lm.forward(params, seq, cfg, remat=False)[:, S]
        state = lm.init_decode_state(cfg, B, S + 8)
        _, state = lm.prefill(params, seq[:, :S], state, cfg)
        l2, _ = lm.decode_step(params, state, seq[:, S:S + 1], jnp.int32(S), cfg)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(l2[:, 0]),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("arch", ["qwen2-72b", "rwkv6-7b",
                                  "phi3.5-moe-42b-a6.6b"])
def test_epitome_modes_agree(arch):
    """paper (reconstruct) == wrapped == folded in fp32."""
    losses = {}
    for variant in ("paper", "wrapped", "folded"):
        cfg = dataclasses.replace(get_smoke_config(arch, epitome=variant),
                                  compute_dtype="float32")
        params = lm.init_params(jax.random.PRNGKey(1), cfg)
        batch = batch_for(cfg)
        losses[variant] = float(lm.loss_fn(params, batch, cfg))
    assert abs(losses["paper"] - losses["wrapped"]) < 1e-4
    assert abs(losses["paper"] - losses["folded"]) < 1e-4


def test_epitome_compresses_params():
    dense = get_smoke_config("qwen2-72b", epitome="off")
    ep = get_smoke_config("qwen2-72b", epitome="folded")
    p_d = lm.init_params(KEY, dense)
    p_e = lm.init_params(KEY, ep)
    n_d = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(p_d))
    n_e = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(p_e))
    assert n_e < n_d


def test_quantized_epitome_trains():
    cfg = get_smoke_config("qwen2-72b", epitome="folded-q3")
    params = lm.init_params(KEY, cfg)
    batch = batch_for(cfg)
    loss, grads = jax.value_and_grad(lm.loss_fn)(params, batch, cfg)
    assert bool(jnp.isfinite(loss))
    gn = sum(float(jnp.abs(g).sum()) for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0


def test_quantized_epitome_kernel_inference():
    """The flagship fused path (mode='kernel' x quant -> int8-packed Pallas
    kernel) serves a whole LM forward unchanged."""
    cfg = get_smoke_config("qwen2-72b", epitome="kernel-q3")
    params = lm.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab)
    logits = lm.forward(params, toks, cfg)
    assert logits.shape == (B, S, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_quantized_epitome_kernel_refuses_training():
    """The fused int8 path has no STE, so differentiating through it must
    fail loudly instead of silently training nothing."""
    cfg = get_smoke_config("qwen2-72b", epitome="kernel-q3")
    params = lm.init_params(KEY, cfg)
    batch = batch_for(cfg)
    with pytest.raises(NotImplementedError, match="inference-only"):
        jax.grad(lm.loss_fn)(params, batch, cfg)


def test_gemma2_softcaps_applied():
    cfg = get_smoke_config("gemma2-2b")
    assert cfg.logit_softcap == 30.0
    params = lm.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab)
    logits = lm.forward(params, toks, cfg)
    assert float(jnp.abs(logits).max()) <= 30.0


def test_local_attention_window():
    """Tokens beyond the sliding window cannot influence a local layer."""
    cfg = dataclasses.replace(get_smoke_config("gemma2-2b"),
                              pattern=("attn_local",), ffn_pattern=("dense",),
                              n_layers=1, window=4)
    params = lm.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (1, 12), 0, cfg.vocab)
    base = lm.forward(params, toks, cfg, remat=False)
    toks2 = toks.at[0, 0].set((int(toks[0, 0]) + 1) % cfg.vocab)
    pert = lm.forward(params, toks2, cfg, remat=False)
    # last position is > window away from position 0: unaffected
    np.testing.assert_allclose(base[0, -1], pert[0, -1], atol=1e-5)
    # but position 1 IS affected (inside window)
    assert float(jnp.abs(base[0, 1] - pert[0, 1]).max()) > 1e-6


def test_full_configs_build_abstractly():
    """Every architecture at published widths: its parameter tree traces
    without allocation and ``param_specs`` gives each leaf a spec that
    fits its rank."""
    is_spec = lambda x: isinstance(x, P)
    for arch in ARCHS:
        cfg = get_config(arch)
        pshape = jax.eval_shape(functools.partial(lm.init_params, cfg=cfg),
                                KEY)
        specs = lm.param_specs(cfg, pshape)
        assert (jax.tree.structure(specs, is_leaf=is_spec)
                == jax.tree.structure(pshape)), arch
        leaves = jax.tree.leaves(pshape)
        assert sum(l.size for l in leaves) > 1e9, arch
        for sp, leaf in zip(jax.tree.leaves(specs, is_leaf=is_spec), leaves):
            assert len(sp) <= leaf.ndim, (arch, sp, leaf.shape)


def _decode_step_stacked(params, state, token, pos, cfg, page_table=None,
                         routed=False):
    """The layer scan as it was before the state rode the carry: the
    pool scanned as xs and the new state stacked as ys.  The reference
    for the in-place ``lm.decode_step``."""
    x = embed_lookup(params["embed"], token, cfg.cdtype)
    x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.cdtype)

    def scan_fn(x, gs):
        group_params, group_state = gs
        x, new_state, n = decode_group(group_params, group_state, x, pos,
                                       cfg, page_table=page_table)
        return x, (new_state, n)

    x, (new_states, n) = jax.lax.scan(
        scan_fn, x, (params["groups"], state))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("head", params["embed"].T if cfg.tie_embeddings else None)
    logits = unembed(x, head, cfg.logit_softcap)
    return (logits, new_states, n.sum(0)) if routed else (logits, new_states)


def _filled_pool(cfg, capacity, page_size):
    """A slot pool with every slot's pages mapped and every state leaf
    filled with noise, so a leaf the step failed to write shows."""
    pool = SlotStatePool(cfg, capacity=capacity, max_len=24,
                         page_size=page_size)
    for slot in range(capacity):
        pool.alloc(slot, 24)
    leaves, treedef = jax.tree.flatten(pool.tree)
    keys = jax.random.split(jax.random.PRNGKey(3), len(leaves))
    leaves = [(jax.random.normal(k, l.shape) * 0.5).astype(l.dtype)
              for k, l in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, leaves), pool.page_table


def _greedy(logits, remaining):
    live = remaining > 0
    toks = jnp.argmax(logits, -1).astype(jnp.int32)
    return toks, jnp.where(live, remaining - 1, remaining), live


def _assert_trees_equal(a, b):
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a),
                            jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("arch,page_size", [
    ("rwkv6-7b", 0), ("qwen2-72b", 8), ("jamba-1.5-large-398b", 0),
    ("jamba2-mini-ep2", 8)],
    ids=["rwkv6", "qwen2-paged", "jamba-mamba", "jamba2-hybrid-paged"])
@pytest.mark.parametrize("k", [0, 1, 4], ids=["step", "scan1", "scan4"])
def test_decode_in_place_bit_identical(monkeypatch, arch, page_size, k):
    """Writing each group's new state into the carried pool gives the same
    logits and every state leaf bit for bit as the stacked xs/ys scan, in
    one step (k=0) and in fused decode scans of K=1 and K=4."""
    cfg = get_smoke_config(arch)
    params = lm.init_params(KEY, cfg)
    capacity = 4
    state, table = _filled_pool(cfg, capacity, page_size)
    tok = jax.random.randint(KEY, (capacity, 1), 0, cfg.vocab)
    pos = jnp.array([3, 9, 0, 15], jnp.int32)
    remaining = jnp.array([4, 2, 0, 3], jnp.int32)

    def run():
        if k == 0:
            return jax.jit(lambda p, s, t, q, pt: lm.decode_step(
                p, s, t, q, cfg, page_table=pt))(params, state, tok, pos,
                                                 table)
        return jax.jit(lambda p, s, t, q, a, pt: lm.decode_scan(
            p, s, t, q, cfg, a, _greedy, k, page_table=pt))(
                params, state, tok, pos, remaining, table)

    got = run()
    monkeypatch.setattr(lm, "decode_step", _decode_step_stacked)
    want = run()
    _assert_trees_equal(got, want)
