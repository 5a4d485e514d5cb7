"""Jamba2-Mini's one-chip share (``jamba2-mini-ep2``) at smoke size on the
CPU, against the plain float32 reference of ``bench/reference/jamba.py``,
and what the share and its routing are held to.

The engine comparison runs the program in float32 so it checks the
mathematics, not bfloat16 rounding: at d_model 64 the bf16 model's dt/B/C
norms over 4 channels move its logits by tenths (the benchmark cell's
bf16 limit is set on the chip at the published widths)."""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.launch import engine as eng
from repro.models import lm, moe, ssm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from bench.reference import jamba as ref  # noqa: E402

KEY = jax.random.PRNGKey(11)


def _ref_config(cfg):
    with open(os.path.join(ROOT, "bench", "tests", "configs",
                           "jamba2-smoke-q3.json")) as f:
        rc = json.load(f)
    assert rc["d_model"] == cfg.d_model and rc["n_layers"] == cfg.n_layers
    return rc


def test_engine_prefill_decode_matches_reference():
    """Chunked prefill (chunks of 8, Mamba windows of 8) into the paged
    slot pool (pages of 8), then decode steps through the page table, as
    ``EpimEngine`` runs them, against the reference's full forward over
    the same tokens.  Tolerance 2e-3 on logits of magnitude about 4: the
    two sum in other orders through 16 layers (both Mamba scans run token
    by token, the reference's on (B, d_inner, d_state) with an einsum over
    d_state, the program's on (B, d_state, d_inner) with a sum; the
    reference's A is -(1..d_state), the program's -exp(log(1..d_state)))."""
    cfg = dataclasses.replace(get_smoke_config("jamba2-mini-ep2", "kernel-q3"),
                              compute_dtype="float32", mamba_chunk=8)
    init_key = jax.random.split(KEY, 3)[0]
    params = lm.prepack_params(lm.init_params(init_key, cfg), cfg)
    e = eng.EpimEngine(cfg, params, capacity=2, max_len=40, page_size=8,
                       prefill_chunk=8)
    assert e.chunk == 8 and e.bucket_prompts
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(5), (1, 24), 0,
                                         cfg.vocab))
    P = 19
    state = eng._fresh_chunk_state(cfg=cfg, seq_len=e.seq_len)
    for lo in range(0, P, 8):
        n = min(8, P - lo)
        buf = np.zeros((1, 8), np.int32)
        buf[0, :n] = toks[0, lo:lo + n]
        logits, state = eng._prefill_chunk(params, jnp.asarray(buf), state,
                                           jnp.int32(lo), jnp.int32(n),
                                           cfg=cfg)
    got = [logits[0, 0]]
    slot = 1
    e._pool.alloc(slot, 40)
    e._pool.scatter(slot, state)
    pool = e._pool.tree
    for t in range(P, toks.shape[1]):
        tok = np.zeros((2, 1), np.int32)
        tok[slot, 0] = toks[0, t]
        pos = np.array([0, t], np.int32)
        lg, pool = lm.decode_step(params, pool, jnp.asarray(tok),
                                  jnp.asarray(pos), cfg,
                                  page_table=e._pool.page_table)
        got.append(lg[slot, 0])
    rc = _ref_config(cfg)
    want = ref.logits(init_key, rc, rc["layers"], rc["quant"], toks)[0]
    got = np.stack([np.asarray(g) for g in got])
    np.testing.assert_allclose(got, np.asarray(want[P - 1:]), atol=2e-3,
                               rtol=0)
    assert np.abs(np.asarray(want)).max() > 1.0


def test_expert_shares_sum_to_whole_layer():
    """Holding experts 0-7 and 8-15 of 16: the two devices' MoE outputs
    add up to the layer with every expert held (each routes over all 16
    with the same router), and so do their routed (token, held expert)
    counts.  Float32; tolerance 1e-6 for the two partial sums' order."""
    base = dataclasses.replace(get_smoke_config("jamba2-mini", "kernel-q3"),
                               n_experts=16, compute_dtype="float32")
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 5, base.d_model))
    outs = []
    for held in ((0, 8), (8, 16), ()):
        cfg = dataclasses.replace(base, experts_held=held)
        params = moe.init_moe(KEY, cfg, prefix="L1/ffn")
        outs.append(moe.moe_held(params, x, cfg, prefix="L1/ffn"))
    (a, ra), (b, rb), (whole, rw) = outs
    np.testing.assert_allclose(np.asarray(a + b), np.asarray(whole),
                               atol=1e-6, rtol=0)
    np.testing.assert_array_equal(np.asarray(ra + rb), np.asarray(rw))
    assert int(rw.min()) == int(rw.max()) == base.top_k
    assert float(jnp.abs(a).max()) > 0 and float(jnp.abs(b).max()) > 0


def test_jamba_routing_softmax_then_topk():
    """Jamba: the weights are the softmax over all experts, the top k kept
    as they are (a row sums below 1).  The existing MoE configs keep the
    softmax over their top-k logits (a row sums to 1)."""
    x = jax.random.normal(jax.random.PRNGKey(4), (6, 64))
    router = jax.random.normal(jax.random.PRNGKey(5), (64, 16)) / 8.0
    cfg = get_smoke_config("jamba2-mini")
    cfg = dataclasses.replace(cfg, n_experts=16)
    assert not cfg.moe_renormalize
    comb = np.asarray(moe.route(x, router, cfg))
    p = np.asarray(jax.nn.softmax(x @ router, axis=-1))
    top = np.argsort(-p, axis=-1)[:, :2]
    want = np.zeros_like(p)
    np.put_along_axis(want, top, np.take_along_axis(p, top, -1), -1)
    np.testing.assert_allclose(comb, want, rtol=1e-6)
    assert (comb.sum(-1) < 1 - 1e-3).all()
    renorm = dataclasses.replace(cfg, moe_renormalize=True)
    np.testing.assert_allclose(np.asarray(moe.route(x, router, renorm)).sum(-1),
                               1.0, rtol=1e-6)
    for arch in ("phi3.5-moe-42b-a6.6b", "grok-1-314b",
                 "jamba-1.5-large-398b"):
        assert get_config(arch).moe_renormalize, arch


def test_bucketed_moe_prompt_first_token():
    """A prompt right-padded to its bucket gives the exact-length first-token
    logits: the per-token MoE path never lets pad rows change real rows.
    Float32; tolerance 1e-5 for matmuls over 5 against 8 rows."""
    cfg = dataclasses.replace(get_smoke_config("jamba2-mini-ep2", "kernel-q3"),
                              compute_dtype="float32")
    params = lm.init_params(KEY, cfg)
    e = eng.EpimEngine(cfg, params, capacity=1, max_len=32)
    P = 5
    L = e._bucket(P)
    assert e.bucket_prompts and L == 8
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(6), (1, L), 0,
                                           cfg.vocab))
    padded = prompt.copy()
    padded[0, P:] = 0
    state = lm.init_decode_state(cfg, 1, e.seq_len)
    exact, _ = lm.prefill(params, jnp.asarray(prompt[:, :P]), state, cfg)
    bucketed, _ = lm.prefill(params, jnp.asarray(padded), state, cfg,
                             jnp.int32(P))
    np.testing.assert_allclose(np.asarray(bucketed), np.asarray(exact),
                               atol=1e-5, rtol=0)


# smoke logits [1, -1, :8] and their sum, recorded before the
# dt/B/C norms, the no-RoPE option and stacked expert sites were added
UNCHANGED = {
    "jamba-1.5-large-398b": (
        [-0.62890625, -0.208984375, 0.1396484375, 0.392578125, 1.4140625,
         1.7578125, -0.7421875, -1.0625], -5.260265350341797),
    "rwkv6-7b": (
        [0.6640625, -0.455078125, -1.53125, 0.86328125, -0.61328125,
         -0.498046875, -0.87109375, -0.2236328125], -137.8267822265625),
}


@pytest.mark.parametrize("arch", sorted(UNCHANGED))
def test_forward_unchanged(arch):
    """Jamba 1.5 Large keeps its Mamba without the dt/B/C norms, RoPE and
    renormalised top-k routing, and its smoke forward is bit for bit what
    it was; so is RWKV-6's."""
    cfg = get_smoke_config(arch)
    assert not cfg.mamba_dtbc_norm and cfg.rope
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    y = np.asarray(lm.forward(params, toks, cfg, remat=False), np.float32)
    head, total = UNCHANGED[arch]
    np.testing.assert_array_equal(y[1, -1, :8], np.float32(head))
    assert float(y.sum()) == total


def test_share_config():
    """The share holds one whole period at published widths and half the
    experts, routing over all 16."""
    full, share = get_config("jamba2-mini"), get_config("jamba2-mini-ep2")
    assert (full.n_layers, share.n_layers) == (32, 8)
    assert share.held_experts == (0, 8) and share.n_experts == 16
    assert full.held_experts == (0, 16)
    for f in ("d_model", "d_ff", "n_heads", "n_kv_heads", "vocab", "top_k",
              "mamba_d_state", "dt_rank", "pattern", "ffn_pattern"):
        assert getattr(full, f) == getattr(share, f), f
    assert share.dt_rank == 256 and share.hd == 128
    assert share.mamba_dtbc_norm and not share.rope
    assert share.full_pattern[4] == ("attn", "dense")
    assert share.full_pattern[1] == ("mamba", "moe")


def _mamba_plain(p, x, S):
    """Mamba in plain float32, token by token as the reference's ``step``
    runs it, on h laid out (B, di, ds): (out (B, S, d), conv window, h
    (B, ds, di)) over the first ``S`` tokens of ``x``."""
    hp = jax.lax.Precision.HIGHEST
    dot = lambda a, w: jnp.matmul(a, w, precision=hp)
    norm = lambda t, w: t * jax.lax.rsqrt(
        jnp.mean(t * t, -1, keepdims=True) + 1e-6) * (1.0 + w)
    x = x[:, :S]
    B = x.shape[0]
    dc, di = p["conv_w"].shape
    ds = p["A_log"].shape[1]
    xi, z = jnp.split(dot(x, p["in_proj"]["W"]), 2, axis=-1)
    xpad = jnp.concatenate([jnp.zeros((B, dc - 1, di)), xi], axis=1)
    xc = jax.nn.silu(sum(xpad[:, i:i + S] * p["conv_w"][i] for i in range(dc))
                     + p["conv_b"])
    R = p["dt_proj"]["W"].shape[0]
    dt, Bp, Cp = jnp.split(dot(xc, p["x_proj"]["W"]), [R, R + ds], axis=-1)
    dt = jax.nn.softplus(dot(norm(dt, p["dt_norm"]), p["dt_proj"]["W"])
                         + p["dt_proj"]["b"])
    Bp, Cp = norm(Bp, p["b_norm"]), norm(Cp, p["c_norm"])
    A = -jnp.exp(p["A_log"])

    def step(h, t):
        dt_t, x_t, b_t, c_t = t
        h = jnp.exp(dt_t[..., None] * A) * h + (dt_t * x_t)[..., None] \
            * b_t[:, None]
        return h, jnp.einsum("bdn,bn->bd", h, c_t, precision=hp)

    seq = tuple(jnp.moveaxis(t, 1, 0) for t in (dt, xc, Bp, Cp))
    h, y = jax.lax.scan(step, jnp.zeros((B, di, ds)), seq)
    y = (jnp.moveaxis(y, 0, 1) + xc * p["D"]) * jax.nn.silu(z)
    return dot(y, p["out_proj"]["W"]), xpad[:, -(dc - 1):], \
        jnp.swapaxes(h, 1, 2)


@pytest.mark.parametrize("case", ["one-shot", "two-chunks", "padded-last"])
def test_mamba_scan_matches_per_token_recurrence(case):
    """``mamba_mix`` (its scan token by token on the pooled (B, d_state,
    d_inner) state, in windows of 8) against the plain float32 per-token
    recurrence: in one shot over 19 tokens (the last window padded); in
    two calls of 8 carrying ``h`` and the conv window; and with a final
    call right-padded to 8 of which 5 are real (``valid_len``).  Outputs,
    conv window and ``h`` agree within 1e-5 of their largest magnitude:
    the two sum the 16 d_state products in other orders."""
    cfg = dataclasses.replace(get_smoke_config("jamba2-mini-ep2"),
                              compute_dtype="float32", param_dtype="float32",
                              mamba_d_state=16, mamba_chunk=8)
    assert cfg.mamba_dtbc_norm and not cfg.epitome.enabled
    ks = jax.random.split(KEY, 6)
    p = ssm.init_mamba(ks[0], cfg, prefix="L0/mixer")
    p["dt_proj"]["b"] = jax.random.normal(ks[1], p["dt_proj"]["b"].shape)
    p["conv_b"] = 0.1 * jax.random.normal(ks[2], p["conv_b"].shape)
    for i, n in enumerate(("dt_norm", "b_norm", "c_norm")):
        p[n] = 0.2 * jax.random.normal(ks[3 + i], p[n].shape)
    x = 2.0 * jax.random.normal(jax.random.PRNGKey(3), (2, 19, cfg.d_model))
    mix = lambda t, state, vl=None: ssm.mamba_mix(
        p, t, cfg, state, prefix="L0/mixer", valid_len=vl)
    if case == "one-shot":
        S = 19
        out, (conv, h) = mix(x, None)
    elif case == "two-chunks":
        S = 16
        a, st = mix(x[:, :8], None)
        b, (conv, h) = mix(x[:, 8:16], st)
        out = jnp.concatenate([a, b], axis=1)
    else:
        S = 13
        a, st = mix(x[:, :8], None)
        last = jnp.concatenate([x[:, 8:13], x[:, :3]], axis=1)   # 3 pads
        b, (conv, h) = mix(last, st, jnp.int32(5))
        out = jnp.concatenate([a, b[:, :5]], axis=1)
    want = _mamba_plain(p, x, S)
    assert h.shape == (2, 16, cfg.mamba_d_inner)
    for got, ref_ in zip((out, conv, h), want):
        got, ref_ = np.asarray(got), np.asarray(ref_)
        scale = np.abs(ref_).max()
        assert scale > 0.1
        np.testing.assert_allclose(got, ref_, atol=1e-5 * scale, rtol=0)
