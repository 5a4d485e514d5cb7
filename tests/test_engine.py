"""Continuous-batching engine: request-level API acceptance tests.

The engine's contract: for any single request, its output is bit-identical
to the pre-existing one-shot path (``serve.generate`` with the same
``max_len`` and ``key=jax.random.PRNGKey(request.seed)``) — greedy and
sampled, any batch composition, any arrival order, any slot.  The
scheduler-level properties (slot reuse, bounded prefill retraces via
power-of-two prompt buckets, MoE exact-length fallback) are pinned by the
engine's ``stats`` counters.

The forced 8-device mesh test boots jax in a subprocess (slow lane), like
tests/test_sharded_plan.py, whose ``run_py`` harness it reuses.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.launch import serve
from repro.launch.engine import (
    Completion, EngineConfig, EpimEngine, Request,
)

MAX_LEN = 48


@pytest.fixture(scope="module")
def engine_factory():
    """Fresh engines over one shared (cfg, max_len): module-level jits are
    keyed on those, so every engine after the first reuses compiled code."""
    def make(capacity=3, **kw):
        kw.setdefault("arch", "rwkv6-7b")
        kw.setdefault("epitome", "kernel-q3")
        return EngineConfig(smoke=True, mesh=None, capacity=capacity,
                            max_len=MAX_LEN, **kw).build()
    return make


def _prompt(rng, n, vocab):
    return tuple(int(t) for t in rng.integers(0, vocab, size=n))


def _reference(eng, req: Request):
    """The one-shot serve path on the same params / max_len / key."""
    prompts = jnp.asarray(np.asarray(req.prompt, np.int32)[None])
    toks, _ = serve.generate(eng.serve_params, eng.cfg, prompts, eng.max_len,
                             req.max_new_tokens, temperature=req.temperature,
                             key=jax.random.PRNGKey(req.seed))
    return tuple(int(t) for t in np.asarray(toks)[0])


# ---------------------------------------------------------------------------
# Bit-identity vs the one-shot serve path
# ---------------------------------------------------------------------------
def test_engine_bit_identical_greedy(engine_factory):
    eng = engine_factory(capacity=3)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=_prompt(rng, p, eng.cfg.vocab), max_new_tokens=6)
            for p in (5, 9, 13, 21)]       # buckets 8 / 16 / 16 / 32
    handles = [eng.submit(r) for r in reqs]
    comps = eng.drain()
    assert [c.request_id for c in comps] == [h.request_id for h in handles]
    for req, comp in zip(reqs, comps):
        assert comp.tokens == _reference(eng, req)
        assert len(comp.tokens) == req.max_new_tokens
        assert comp.ttft_s > 0 and comp.latency_s >= comp.ttft_s


def test_engine_bit_identical_sampled(engine_factory):
    """Sampled decoding folds the REQUEST's key, split once per token in
    serve._select order — mixed temperatures in one decode batch included."""
    eng = engine_factory(capacity=3)
    rng = np.random.default_rng(1)
    reqs = [Request(prompt=_prompt(rng, p, eng.cfg.vocab), max_new_tokens=5,
                    temperature=t, seed=100 + i)
            for i, (p, t) in enumerate([(5, 0.7), (9, 0.0), (12, 1.3)])]
    for r in reqs:
        eng.submit(r)
    comps = eng.drain()
    for req, comp in zip(reqs, comps):
        assert comp.tokens == _reference(eng, req)


def test_engine_rng_arrival_order_invariant(engine_factory):
    """A request's sampled continuation depends only on its own seed —
    never on the order requests arrived or the slot they landed in."""
    rng = np.random.default_rng(2)
    vocab = get_smoke_config("rwkv6-7b", "kernel-q3").vocab
    reqs = [Request(prompt=_prompt(rng, 4 + 3 * i, vocab), max_new_tokens=4,
                    temperature=0.9, seed=7 + i) for i in range(4)]

    def serve_order(order):
        eng = engine_factory(capacity=2)   # forces queueing + slot reuse
        handles = {i: eng.submit(reqs[i]) for i in order}
        eng.drain()
        return {i: h.result().tokens for i, h in handles.items()}

    fwd = serve_order([0, 1, 2, 3])
    rev = serve_order([3, 1, 0, 2])
    assert fwd == rev


def test_engine_bit_identical_attention_arch(engine_factory):
    """Same contract on a pure-attention arch (per-slot KV blocks, vector
    decode positions)."""
    eng = engine_factory(capacity=2, arch="qwen2-72b", epitome="off")
    rng = np.random.default_rng(3)
    reqs = [Request(prompt=_prompt(rng, p, eng.cfg.vocab), max_new_tokens=5,
                    temperature=t, seed=50 + i)
            for i, (p, t) in enumerate([(6, 0.0), (11, 0.8), (9, 0.0)])]
    for r in reqs:
        eng.submit(r)
    comps = eng.drain()
    for req, comp in zip(reqs, comps):
        assert comp.tokens == _reference(eng, req)


# ---------------------------------------------------------------------------
# Multi-step fused decode (decode_block > 1)
# ---------------------------------------------------------------------------
def _run_reqs(eng, reqs):
    for r in reqs:
        eng.submit(r)
    return eng.drain()


@pytest.mark.parametrize("k", [4, 8])
def test_multistep_bit_identical_dense(engine_factory, k):
    """K fused micro-steps on the dense recurrent pool: greedy and
    sampled rows mixed in one macro-step, all bit-identical to the
    one-shot path (K=1 is the pre-existing tests above)."""
    eng = engine_factory(capacity=3, decode_block=k)
    rng = np.random.default_rng(10 + k)
    reqs = [Request(prompt=_prompt(rng, p, eng.cfg.vocab), max_new_tokens=m,
                    temperature=t, seed=200 + i)
            for i, (p, m, t) in enumerate(
                [(5, 6, 0.0), (9, 5, 0.9), (13, 9, 0.0), (7, 7, 1.2)])]
    comps = _run_reqs(eng, reqs)
    for req, comp in zip(reqs, comps):
        assert comp.tokens == _reference(eng, req)
        assert len(comp.tokens) == req.max_new_tokens


@pytest.mark.parametrize("k", [4, 8])
def test_multistep_bit_identical_paged_chunked(engine_factory, k):
    """Same contract on an attention arch with block-paged KV and chunked
    prefill: the page-table gather and the in-scan position advance keep
    every micro-step's KV row exactly where the one-step path wrote it."""
    eng = engine_factory(capacity=3, arch="qwen2-72b", epitome="off",
                         decode_block=k, page_size=8, prefill_chunk=8)
    rng = np.random.default_rng(20 + k)
    reqs = [Request(prompt=_prompt(rng, p, eng.cfg.vocab), max_new_tokens=m,
                    temperature=t, seed=300 + i)
            for i, (p, m, t) in enumerate(
                [(6, 6, 0.0), (11, 5, 0.8), (21, 8, 0.0)])]
    comps = _run_reqs(eng, reqs)
    assert eng.stats["prefill_chunks"] > 0        # long prompts chunked
    for req, comp in zip(reqs, comps):
        assert comp.tokens == _reference(eng, req)


def test_multistep_amortizes_dispatches(engine_factory):
    """The point of the PR: K=4 serves the same tokens in ~1/4 the device
    dispatches, and the macro-step program compiles once per K — never
    per request."""
    rng = np.random.default_rng(12)
    vocab = get_smoke_config("rwkv6-7b", "kernel-q3").vocab
    reqs = [Request(prompt=_prompt(rng, 5, vocab), max_new_tokens=9, seed=i)
            for i in range(3)]
    e1 = engine_factory(capacity=3, decode_block=1)
    e4 = engine_factory(capacity=3, decode_block=4)
    c1 = _run_reqs(e1, reqs)
    c4 = _run_reqs(e4, reqs)
    assert [c.tokens for c in c1] == [c.tokens for c in c4]
    assert e1.stats["decode_steps"] == 8           # 8 post-prefill tokens
    assert e4.stats["decode_steps"] == 2           # 2 macro-steps of 4
    assert e4.stats["decode_micro_steps"] == 8


def test_multistep_pipeline_dispatch_then_retire(engine_factory):
    """step() dispatches macro-step k+1 before blocking on k: admission
    alone never dispatches, the first tick after admission launches
    (nothing to retire yet), and the next tick retires K tokens — host
    scheduling work in between overlaps the device compute."""
    eng = engine_factory(capacity=1, decode_block=4)
    rng = np.random.default_rng(14)
    h = eng.submit(Request(prompt=_prompt(rng, 5, eng.cfg.vocab),
                           max_new_tokens=9))
    assert eng._inflight is None          # admission alone doesn't dispatch
    assert eng.step() == 0                # tick 1: dispatch only
    assert eng._inflight is not None and not h.done()
    assert eng.step() == 4                # tick 2: retire k, dispatch next
    assert eng.step() == 4
    assert eng.drain() and h.done()
    assert len(h.result().tokens) == 9


def test_midscan_termination_matches_k1(engine_factory):
    """Satellite contract: a slot whose stop fires at micro-step j < K
    (forced by pinning _pick_k above its remaining tokens) emits exactly
    max_new_tokens, bit-identical to K=1, frees its pages at the retire
    boundary of the macro-step that finished it, and its position never
    advances past the page reservation."""
    rng = np.random.default_rng(15)
    vocab = get_smoke_config("qwen2-72b").vocab
    reqs = [Request(prompt=_prompt(rng, 6, vocab), max_new_tokens=3,
                    seed=40),                      # freezes at j=2 of K=4
            Request(prompt=_prompt(rng, 9, vocab), max_new_tokens=10,
                    temperature=0.7, seed=41)]
    ref = engine_factory(capacity=2, arch="qwen2-72b", epitome="off",
                         decode_block=1, page_size=8)
    c_ref = _run_reqs(ref, reqs)

    eng = engine_factory(capacity=2, arch="qwen2-72b", epitome="off",
                         decode_block=4, page_size=8)
    eng._pick_k = lambda: 4               # force K past slot 0's remaining
    handles = [eng.submit(r) for r in reqs]
    short_pages = eng._pool.pages_needed(len(reqs[0].prompt)
                                         + reqs[0].max_new_tokens)
    assert short_pages > 0
    while not handles[0].done():
        free_before = eng._pool.pages_free
        emitted = eng.step()
        for slot, rec in eng._active.items():
            assert eng._pos[slot] <= (len(rec.request.prompt)
                                      + rec.request.max_new_tokens - 1)
        if handles[0].done():
            # pages freed at THIS retire boundary, in full, not before
            assert eng._pool.pages_free == free_before + short_pages
            assert emitted > 0
    eng.drain()
    comps = [h.result() for h in handles]
    for a, b in zip(c_ref, comps):
        assert a.tokens == b.tokens
        assert a.prompt_len == b.prompt_len
    assert len(comps[0].tokens) == reqs[0].max_new_tokens


def test_multistep_decode_traces_bounded():
    """One compiled macro-step per (cfg, K) — the auto-pick rule visits at
    most decode_block distinct K values, never one trace per request.
    A capacity no other test uses gives this engine a cold decode cache
    (the dense pool's decode shapes depend on capacity, not max_len)."""
    eng = EngineConfig(arch="rwkv6-7b", epitome="kernel-q3", smoke=True,
                       mesh=None, capacity=5, max_len=MAX_LEN,
                       decode_block=4).build()
    rng = np.random.default_rng(16)
    reqs = [Request(prompt=_prompt(rng, 5, eng.cfg.vocab),
                    max_new_tokens=3 + 2 * i, seed=i) for i in range(6)]
    _run_reqs(eng, reqs)
    assert eng.stats["completed"] == 6
    assert 1 <= eng.stats["decode_traces"] <= eng.decode_block


def test_decode_block_validation():
    with pytest.raises(ValueError, match="decode_block"):
        EpimEngine(get_smoke_config("rwkv6-7b"), None, capacity=1,
                   max_len=16, decode_block=0)


# ---------------------------------------------------------------------------
# Scheduler: slots, buckets, retraces
# ---------------------------------------------------------------------------
def test_slot_reuse_mid_flight(engine_factory):
    """5 requests through 2 slots: finished requests free their slot and
    pending requests are admitted without waiting for the batch."""
    eng = engine_factory(capacity=2)
    rng = np.random.default_rng(4)
    reqs = [Request(prompt=_prompt(rng, 5, eng.cfg.vocab),
                    max_new_tokens=2 + i) for i in range(5)]
    handles = [eng.submit(r) for r in reqs]
    assert eng.n_active == 2 and eng.n_pending == 3
    comps = eng.drain()
    assert eng.stats["completed"] == 5
    assert eng.stats["slot_reuses"] == 3   # admissions beyond capacity
    for req, h, c in zip(reqs, handles, comps):
        assert h.done() and h.result() is c
        assert len(c.tokens) == req.max_new_tokens


def test_bucketed_prefill_bounds_retraces(engine_factory):
    """Prompt lengths pad to power-of-two buckets: retraces are counted by
    DISTINCT BUCKETS, not distinct lengths.  A unique max_len gives this
    test its own jit cache entry so the counter starts cold."""
    eng = EngineConfig(arch="rwkv6-7b", epitome="kernel-q3", smoke=True,
                       mesh=None, capacity=4, max_len=40).build()
    rng = np.random.default_rng(5)
    for p in (5, 6, 8):                    # all bucket 8
        eng.submit(Request(prompt=_prompt(rng, p, eng.cfg.vocab),
                           max_new_tokens=2))
    eng.drain()
    assert eng.stats["prefill_traces"] == 1
    for p in (9, 16, 4, 7):                # bucket 16 is the only new one
        eng.submit(Request(prompt=_prompt(rng, p, eng.cfg.vocab),
                           max_new_tokens=2))
    eng.drain()
    assert eng.stats["prefill_traces"] == 2


def test_moe_arch_prefills_exact_length(monkeypatch):
    """MoE capacity routing couples batch rows (pads would consume expert
    queue ranks) — where a mesh would run it, prompts bypass bucketing.
    The per-token MoE path (one device) is row-local and buckets."""
    from repro.models import moe as moe_mod
    cfg = get_smoke_config("phi3.5-moe-42b-a6.6b")
    per_token = EpimEngine(cfg, None, capacity=1, max_len=32)
    assert per_token.bucket_prompts and per_token._bucket(5) == 8
    monkeypatch.setattr(moe_mod, "takes_dispatch",
                        lambda cfg, *a: "moe" in cfg.ffn_pattern)
    moe = EpimEngine(cfg, None, capacity=1, max_len=32)
    assert not moe.bucket_prompts
    assert moe._bucket(5) == 5
    ssm = EpimEngine(get_smoke_config("rwkv6-7b"), None,
                     capacity=1, max_len=32)
    assert ssm.bucket_prompts
    assert ssm._bucket(5) == 8 and ssm._bucket(9) == 16
    assert ssm._bucket(30) == 32 and ssm._bucket(2) == 8


def test_single_token_request_completes_at_admission(engine_factory):
    eng = engine_factory(capacity=1)
    rng = np.random.default_rng(6)
    req = Request(prompt=_prompt(rng, 5, eng.cfg.vocab), max_new_tokens=1)
    steps_before = eng.stats["decode_steps"]
    h = eng.submit(req)
    assert h.done()                        # no decode step needed
    assert eng.stats["decode_steps"] == steps_before
    assert h.result().tokens == _reference(eng, req)


def test_submit_validation():
    eng = EpimEngine(get_smoke_config("rwkv6-7b"), None,
                     capacity=1, max_len=16)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(Request(prompt=()))
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(Request(prompt=(1, 2), max_new_tokens=0))
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(Request(prompt=(1,) * 12, max_new_tokens=8))
    from repro.launch.engine import RequestHandle, _Record
    with pytest.raises(RuntimeError, match="not finished"):
        RequestHandle(_Record(0, Request(prompt=(1,)), 0.0)).result()


# ---------------------------------------------------------------------------
# EngineConfig as the one setup path
# ---------------------------------------------------------------------------
def test_engine_config_build_exposes_setup(engine_factory):
    eng = engine_factory(capacity=1)
    from repro.models import lm
    assert lm.needs_prepack(eng.cfg)
    assert eng.packed is not None and eng.serve_params is eng.packed
    assert eng.mesh is None                # mesh=None leaves the mesh alone
    assert eng.prompt_key is not None and eng.sample_key is not None
    assert eng.config.capacity == 1 and eng.config.max_len == MAX_LEN

    plain = EngineConfig(arch="rwkv6-7b", epitome="off", smoke=True,
                         mesh=None, capacity=1, max_len=MAX_LEN).build()
    assert plain.packed is None and plain.serve_params is plain.params


def test_serve_deprecated_flags_warn(monkeypatch, capsys):
    """--batch/--gen still run but warn toward the Request fields."""
    monkeypatch.setattr("sys.argv", [
        "serve", "--arch", "rwkv6-7b", "--smoke", "--batch", "1",
        "--prompt-len", "4", "--gen", "2"])
    with pytest.warns(DeprecationWarning, match="--requests"):
        with pytest.warns(DeprecationWarning, match="--max-new-tokens"):
            serve.main()
    out = capsys.readouterr().out
    assert "generated (1, 2)" in out


def test_serving_bench_smoke(engine_factory):
    """The open-loop Poisson driver completes every request and its
    replayed request is bit-identical to the one-shot path."""
    from benchmarks.serving_bench import run_serving
    m = run_serving(n_requests=3, rate_hz=200.0, max_new=3, capacity=2,
                    max_len=MAX_LEN)
    assert m["completed"] == 3
    assert m["bit_identical"] is True
    assert m["tok_s"] > 0
    assert 0 < m["p50_ttft_ms"] <= m["p99_ttft_ms"]


# ---------------------------------------------------------------------------
# Forced 8-device mesh (subprocess; slow lane)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_engine_sharded_mesh_bit_identical():
    """The engine on a (2, 4) host mesh serves requests bit-identical to
    the one-shot sharded path — slots, buckets, per-request RNG, AND the
    K=4 fused decode scan all survive sharded weight-stationary
    serving (the last cell of the decode_block acceptance matrix)."""
    from test_sharded_plan import run_py
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch import serve
        from repro.launch.engine import EngineConfig, Request

        for k in (1, 4):
            eng = EngineConfig(arch="rwkv6-7b", epitome="kernel-q3",
                               smoke=True, mesh="2,4", capacity=2,
                               max_len=32, decode_block=k).build()
            assert dict(eng.mesh.shape) == {"data": 2, "model": 4}
            r0 = np.random.default_rng(0)
            reqs = [Request(prompt=tuple(int(t) for t in
                                         r0.integers(0, eng.cfg.vocab, p)),
                            max_new_tokens=6, temperature=t, seed=5 + i)
                    for i, (p, t) in enumerate(
                        [(5, 0.0), (9, 0.8), (13, 0.0)])]
            for r in reqs:
                eng.submit(r)
            comps = eng.drain()
            assert eng.stats["slot_reuses"] == 1
            for r, c in zip(reqs, comps):
                ref, _ = serve.generate(
                    eng.serve_params, eng.cfg,
                    jnp.asarray(np.asarray(r.prompt, np.int32)[None]),
                    eng.max_len, r.max_new_tokens,
                    temperature=r.temperature,
                    key=jax.random.PRNGKey(r.seed))
                assert tuple(int(x) for x in np.asarray(ref)[0]) == c.tokens
        print("ENGINE SHARDED OK")
    """)
