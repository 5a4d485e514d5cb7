"""Block-paged KV pool + chunked prefill: state-layer acceptance tests.

The pool's contract: attention K/V lives in a global pool of fixed-size
pages behind a per-slot page table, recurrent rows stay dense per slot,
and NONE of it changes a single output bit — the engine with paging and
chunked prefill on reproduces ``serve.generate`` exactly (greedy and
sampled).  Scheduler-level properties (page reservation at admission,
FIFO deferral when the pool is dry, page reuse after mid-flight free,
one compiled program for every chunk) are pinned by pool/engine stats.

The forced 8-device mesh test boots jax in a subprocess (slow lane),
reusing the ``run_py`` harness from tests/test_sharded_plan.py.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.launch import serve
from repro.launch.engine import EngineConfig, EpimEngine, Request
from repro.models import lm
from repro.models.kv_pool import SlotStatePool, paged_leaf_paths

MAX_LEN = 48
PAGE = 16


@pytest.fixture(scope="module")
def paged_factory():
    """Fresh paged qwen2 engines over one shared (cfg, seq_len) so the
    chunk/decode programs compile once for the whole module."""
    def make(capacity=2, **kw):
        kw.setdefault("arch", "qwen2-72b")
        kw.setdefault("epitome", "off")
        kw.setdefault("max_len", MAX_LEN)
        kw.setdefault("page_size", PAGE)
        return EngineConfig(smoke=True, mesh=None, capacity=capacity,
                            **kw).build()
    return make


def _prompt(rng, n, vocab):
    return tuple(int(t) for t in rng.integers(0, vocab, size=n))


def _reference(eng, req: Request):
    """The one-shot dense path on the same params / seq_len / key."""
    prompts = jnp.asarray(np.asarray(req.prompt, np.int32)[None])
    toks, _ = serve.generate(eng.serve_params, eng.cfg, prompts, eng.seq_len,
                             req.max_new_tokens, temperature=req.temperature,
                             key=jax.random.PRNGKey(req.seed))
    return tuple(int(t) for t in np.asarray(toks)[0])


# ---------------------------------------------------------------------------
# Pool accounting (host-side, no params needed)
# ---------------------------------------------------------------------------
def test_page_accounting():
    cfg = get_smoke_config("qwen2-72b")
    pool = SlotStatePool(cfg, capacity=2, max_len=40, page_size=16)
    assert pool.paged and pool.seq_len == 48          # rounds up to pages
    assert pool.page.pages_per_slot == 3
    assert pool.stats()["pages_total"] == 6           # capacity * pages/slot

    assert pool.pages_needed(1) == 1
    assert pool.pages_needed(16) == 1
    assert pool.pages_needed(17) == 2

    pool.alloc(0, 40)                                 # 3 pages
    pool.alloc(1, 33)                                 # 3 pages -> pool dry
    assert pool.pages_used == 6 and not pool.can_admit(1)
    with pytest.raises(RuntimeError, match="KV pool dry"):
        pool.alloc(0, 16)
    row = np.asarray(pool.page_table)
    assert sorted(row.ravel().tolist()) == list(range(6))  # all mapped

    pool.free(0)                                      # mid-flight free
    assert pool.pages_free == 3 and pool.can_admit(33)
    assert np.all(np.asarray(pool.table_row(0)) == pool.page.trash)
    pool.alloc(0, 17)                                 # reuses freed pages
    st = pool.stats()
    assert st["page_reuses"] == 2 and st["pages_hwm"] == 6


def test_dense_pool_is_noop_accounting():
    cfg = get_smoke_config("rwkv6-7b")
    pool = SlotStatePool(cfg, capacity=2, max_len=40)
    assert not pool.paged and pool.seq_len == 40
    assert pool.pages_needed(40) == 0 and pool.can_admit(10 ** 9)
    assert pool.page_table is None
    assert pool.stats() == {"pages_total": 0, "pages_used": 0,
                            "pages_free": 0, "pages_hwm": 0,
                            "page_reuses": 0}


def test_paged_scatter_gather_roundtrip():
    """A batch-1 state scattered through the page table gathers back
    exactly; a short allocation's unmapped tail is trash-page-backed
    (garbage the decode-side masking never lets attention read)."""
    cfg = get_smoke_config("qwen2-72b")
    pool = SlotStatePool(cfg, capacity=2, max_len=48, page_size=16)
    one = jax.tree.map(
        lambda l: (jnp.arange(l.size) % 251).reshape(l.shape).astype(l.dtype),
        lm.init_decode_state(cfg, 1, pool.seq_len))

    pool.alloc(0, 48)                                  # fully mapped slot
    pool.scatter(0, one)
    back = pool.gather(0)
    for (pa, a), (pb, b) in zip(jax.tree_util.tree_leaves_with_path(one),
                                jax.tree_util.tree_leaves_with_path(back)):
        assert pa == pb and np.array_equal(np.asarray(a), np.asarray(b))

    pool.alloc(1, 20)                                  # 2 of 3 pages mapped
    pool.scatter(1, one)
    assert int(np.asarray(pool.table_row(1))[2]) == pool.page.trash
    kv = paged_leaf_paths(cfg)
    for lk, layer in pool.gather(1).items():
        for k, leaf in layer.items():
            if f"{lk}/{k}" not in kv:
                continue
            ref = np.asarray(one[lk][k])
            got = np.asarray(leaf)
            # the mapped 2 pages (32 rows) round-trip; rows 32+ read the
            # shared trash page — unspecified bits attention masks out
            assert np.array_equal(got[:, :, :32], ref[:, :, :32])


# ---------------------------------------------------------------------------
# Bit-identity: paging and chunked prefill change no output bits
# ---------------------------------------------------------------------------
def test_paged_engine_bit_identical(paged_factory):
    """Paged decode (KV gathered through the page table) reproduces the
    dense one-shot path, greedy and sampled, across slot reuse."""
    eng = paged_factory(capacity=2)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=_prompt(rng, p, eng.cfg.vocab), max_new_tokens=5,
                    temperature=t, seed=30 + i)
            for i, (p, t) in enumerate([(6, 0.0), (11, 0.8), (9, 0.0),
                                        (13, 1.1)])]
    handles = [eng.submit(r) for r in reqs]
    eng.drain()
    for req, h in zip(reqs, handles):
        assert h.result().tokens == _reference(eng, req)
    assert eng.stats["slot_reuses"] == 2
    assert eng.stats["page_reuses"] > 0


def test_chunked_prefill_bit_identical(paged_factory):
    """Prompts longer than the chunk prefill one chunk per step — same
    bits as the whole-prompt path, and ONE compiled program covers every
    chunk of every prompt (vs one bucket program per length class)."""
    eng = paged_factory(capacity=2, prefill_chunk=16)
    assert eng.chunk == 16                  # attention-only: alignment 1
    rng = np.random.default_rng(1)
    reqs = [Request(prompt=_prompt(rng, p, eng.cfg.vocab), max_new_tokens=4,
                    temperature=t, seed=60 + i)
            for i, (p, t) in enumerate([(20, 0.0), (35, 0.9), (44, 0.0)])]
    handles = [eng.submit(r) for r in reqs]
    eng.drain()
    for req, h in zip(reqs, handles):
        assert h.result().tokens == _reference(eng, req)
    # ceil(20/16) + ceil(35/16) + ceil(44/16) chunks, one trace for all
    assert eng.stats["prefill_chunks"] == 2 + 3 + 3
    assert eng.stats["prefill_traces"] == 1


def test_chunked_prefill_respects_recurrence_alignment():
    """Recurrent arches round the chunk up to their internal scan window
    so chunk boundaries are one-shot window boundaries — the engine still
    reproduces the one-shot bits across a boundary."""
    eng = EngineConfig(arch="rwkv6-7b", epitome="kernel-q3", smoke=True,
                       mesh=None, capacity=1, max_len=96,
                       prefill_chunk=16).build()
    assert eng.chunk == 64                  # rwkv_chunk-aligned, not 16
    rng = np.random.default_rng(2)
    req = Request(prompt=_prompt(rng, 70, eng.cfg.vocab), max_new_tokens=4,
                  temperature=0.7, seed=9)
    h = eng.submit(req)
    eng.drain()
    assert h.result().tokens == _reference(eng, req)
    assert eng.stats["prefill_chunks"] == 2


def test_chunking_disabled_where_it_would_change_bits(monkeypatch):
    """An MoE layer run as a capacity dispatch couples every token through
    its capacity routing; int8 KV caches would make chunk 2 attend
    dequantized rows the one-shot path attends fresh.  Both must fall back
    to whole-prompt prefill.  The per-token MoE path of one device is
    row-local and chunks."""
    from repro.models import moe as moe_mod
    cfg = get_smoke_config("phi3.5-moe-42b-a6.6b")
    per_token = EpimEngine(cfg, None, capacity=1, max_len=32,
                           prefill_chunk=8)
    assert per_token.chunk == 8
    monkeypatch.setattr(moe_mod, "takes_dispatch",
                        lambda cfg, *a: "moe" in cfg.ffn_pattern)
    moe = EpimEngine(cfg, None, capacity=1, max_len=32, prefill_chunk=8)
    assert moe.chunk == 0
    cfg8 = dataclasses.replace(get_smoke_config("qwen2-72b"),
                               kv_cache_bits=8)
    int8 = EpimEngine(cfg8, None, capacity=1, max_len=32, prefill_chunk=8)
    assert int8.chunk == 0


# ---------------------------------------------------------------------------
# Scheduler: oversubscription, deferral, trace attribution, validation
# ---------------------------------------------------------------------------
def test_oversubscribed_pool_defers_then_completes(paged_factory):
    """kv_pages below capacity * pages/slot: admission defers (never
    crashes) while the pool is dry, and freed pages are reused."""
    eng = paged_factory(capacity=3, kv_pages=4)
    rng = np.random.default_rng(3)
    reqs = [Request(prompt=_prompt(rng, 20, eng.cfg.vocab),
                    max_new_tokens=8, seed=i) for i in range(3)]
    handles = [eng.submit(r) for r in reqs]
    # each request pins ceil(28 / 16) = 2 pages: only 2 of 3 slots admit
    assert eng.n_active == 2 and eng.n_pending == 1
    assert eng.stats["queue_depth"] == 1
    comps = eng.drain()
    assert len(comps) == 3 and all(h.done() for h in handles)
    st = eng.stats
    assert st["pages_hwm"] <= 4 and st["page_reuses"] >= 2
    assert comps[2].queue_wait_s > 0       # the deferred one waited
    for req, h in zip(reqs, handles):
        assert h.result().tokens == _reference(eng, req)


def test_per_engine_trace_attribution():
    """Two engines sharing one jit cache: the second engine's prefills
    hit compiled code, so ITS counter stays 0 while the first engine's
    counter keeps the compile it paid for."""
    mk = lambda: EngineConfig(arch="rwkv6-7b", epitome="kernel-q3",
                              smoke=True, mesh=None, capacity=1,
                              max_len=44).build()
    a, b = mk(), mk()
    rng = np.random.default_rng(4)
    a.submit(Request(prompt=_prompt(rng, 5, a.cfg.vocab), max_new_tokens=2))
    a.drain()
    assert a.stats["prefill_traces"] == 1
    b.submit(Request(prompt=_prompt(rng, 6, b.cfg.vocab), max_new_tokens=2))
    b.drain()
    assert b.stats["prefill_traces"] == 0   # same bucket program, no trace
    assert a.stats["prefill_traces"] == 1   # untouched by b's activity


def test_submit_validation_rejects_bad_requests():
    cfg = get_smoke_config("qwen2-72b")
    eng = EpimEngine(cfg, None, capacity=1, max_len=64,
                     page_size=16, kv_pages=2)
    with pytest.raises(ValueError, match="outside the vocabulary"):
        eng.submit(Request(prompt=(cfg.vocab,), max_new_tokens=2))
    with pytest.raises(ValueError, match="max_len budget"):
        eng.submit(Request(prompt=(1,) * 70, max_new_tokens=2))
    # 30 + 10 tokens fits max_len but needs 3 pages of a 2-page pool:
    # reject at submit instead of deferring forever
    with pytest.raises(ValueError, match="could never be admitted"):
        eng.submit(Request(prompt=(1,) * 30, max_new_tokens=10))


# ---------------------------------------------------------------------------
# Forced 8-device mesh (subprocess; slow lane)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_paged_chunked_sharded_mesh_bit_identical():
    """Paged KV + chunked prefill on a (2, 4) host mesh: the page-table
    gather and the chunk-carried f32 K/V survive sharding bit-exactly."""
    from test_sharded_plan import run_py
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch import serve
        from repro.launch.engine import EngineConfig, Request

        eng = EngineConfig(arch="qwen2-72b", epitome="off", smoke=True,
                           mesh="2,4", capacity=2, max_len=48,
                           page_size=16, prefill_chunk=16).build()
        assert dict(eng.mesh.shape) == {"data": 2, "model": 4}
        rng = np.random.default_rng(0)
        reqs = [Request(prompt=tuple(int(t) for t in
                                     rng.integers(0, eng.cfg.vocab, p)),
                        max_new_tokens=5, temperature=t, seed=5 + i)
                for i, (p, t) in enumerate([(20, 0.0), (9, 0.8),
                                            (35, 0.0)])]
        handles = [eng.submit(r) for r in reqs]
        eng.drain()
        assert eng.stats["prefill_chunks"] == 2 + 3
        assert eng.stats["page_reuses"] > 0
        for r, h in zip(reqs, handles):
            ref, _ = serve.generate(
                eng.serve_params, eng.cfg,
                jnp.asarray(np.asarray(r.prompt, np.int32)[None]),
                eng.seq_len, r.max_new_tokens, temperature=r.temperature,
                key=jax.random.PRNGKey(r.seed))
            assert tuple(int(x) for x in np.asarray(ref)[0]) \\
                == h.result().tokens
        print("PAGED SHARDED OK")
    """)
