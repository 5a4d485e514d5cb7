"""Open-loop serving benchmark for the continuous-batching engine.

Drives ``launch.engine.EpimEngine`` with Poisson arrivals (open loop: the
arrival process never waits for the server, so queueing shows up in TTFT
exactly as it would under real load) and reports:

  p50/p99 TTFT       submit -> first token, milliseconds
  mean per-token     decode latency per generated token, milliseconds
  tok/s              total generated tokens / wall time
  qwait p50/p99      submit -> admission (slot + KV pages), milliseconds
  max_gap_ms         worst inter-token decode gap any request saw
  pages hwm/total    block-paged KV pool occupancy high-water mark
  bit_identical      one request replayed through the one-shot
                     ``serve.generate`` path with the same seed/max_len
                     must reproduce the engine's tokens exactly

``run_stall_probe`` is the paged-vs-dense A/B the CI row
``kernels/serving-paged-smoke`` gates: short requests decode while one
long prompt arrives mid-flight.  Dense whole-prompt prefill stalls every
decoder for the full prompt; chunked prefill bounds the stall at one
chunk — the probe reports the short requests' max inter-token gap under
both state layers (same tokens, bit-identical) plus the KV bytes each
pool holds (the paged pool is deliberately oversubscribed).

Standalone:
  PYTHONPATH=src python benchmarks/serving_bench.py --requests 6 --rate 50

or as the ``serving`` section of benchmarks.run (CI gates the emitted
``kernels/serving-smoke`` and ``kernels/serving-paged-smoke`` CSV rows).
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def run_serving(*, arch: str = "rwkv6-7b", epitome: str = "kernel-q3",
                n_requests: int = 6, rate_hz: float = 50.0,
                prompt_lens=(4, 6, 9, 12), max_new: int = 8,
                capacity: int = 4, temperature: float = 0.0, seed: int = 0,
                max_len: int = 48, mesh=None, decode_block: int = 1,
                check_bit_identity: bool = True) -> dict:
    """Run one open-loop experiment; returns the metrics dict."""
    import jax
    import jax.numpy as jnp
    from repro.launch import serve
    from repro.launch.engine import EngineConfig, Request

    eng = EngineConfig(arch=arch, epitome=epitome, smoke=True, mesh=mesh,
                       capacity=capacity, max_len=max_len, seed=seed,
                       decode_block=decode_block).build()
    rng = np.random.default_rng(seed)
    reqs = [Request(prompt=tuple(int(t) for t in
                                 rng.integers(0, eng.cfg.vocab,
                                              size=int(rng.choice(prompt_lens)))),
                    max_new_tokens=max_new, temperature=temperature,
                    seed=seed + i)
            for i in range(n_requests)]

    # warm outside the timed window: one request per prefill bucket the
    # workload will hit, plus the pooled decode program — so the timed
    # TTFTs measure serving, not XLA compiles
    for b in sorted({eng._bucket(len(r.prompt)) for r in reqs}):
        eng.submit(Request(prompt=(1,) * min(b, max_len - 2),
                           max_new_tokens=2, temperature=temperature))
    eng.drain()
    base = eng.stats

    arrivals = np.cumsum(rng.exponential(1.0 / rate_hz, size=n_requests))
    handles = []
    i = 0
    t0 = time.perf_counter()
    while i < n_requests or eng.n_active or eng.n_pending:
        now = time.perf_counter() - t0
        if i < n_requests and now >= arrivals[i]:
            handles.append(eng.submit(reqs[i]))
            i += 1
            continue
        if eng.step() == 0 and i < n_requests:
            time.sleep(max(0.0, arrivals[i] - (time.perf_counter() - t0)))
    wall = time.perf_counter() - t0

    comps = [h.result() for h in handles]
    ttfts_ms = np.array([c.ttft_s for c in comps]) * 1e3
    tok_ms = np.array([(c.latency_s - c.ttft_s)
                       / max(1, len(c.tokens) - 1) for c in comps]) * 1e3
    total = sum(len(c.tokens) for c in comps)

    bit_identical = None
    if check_bit_identity:
        r, c = reqs[0], comps[0]
        ref, _ = serve.generate(
            eng.serve_params, eng.cfg,
            jnp.asarray(np.asarray(r.prompt, np.int32)[None]), max_len,
            r.max_new_tokens, temperature=r.temperature,
            key=jax.random.PRNGKey(r.seed))
        bit_identical = tuple(int(t) for t in np.asarray(ref)[0]) == c.tokens

    stats = eng.stats
    qwaits_ms = np.array([c.queue_wait_s for c in comps]) * 1e3
    gaps = [np.diff(c.token_times) for c in comps if len(c.token_times) > 1]
    max_gap_ms = float(max((g.max() for g in gaps), default=0.0)) * 1e3
    decode_steps = stats["decode_steps"] - base["decode_steps"]
    # decode tokens = everything after each request's prefill-sampled first
    decode_tokens = total - len(comps)
    return {
        "arch": arch, "epitome": epitome, "completed": len(comps),
        "p50_ttft_ms": float(np.percentile(ttfts_ms, 50)),
        "p99_ttft_ms": float(np.percentile(ttfts_ms, 99)),
        "mean_tok_ms": float(tok_ms.mean()),
        "tok_s": total / wall, "wall_s": wall,
        "bit_identical": bit_identical,
        "prefill_traces": stats["prefill_traces"],
        "decode_block": decode_block,
        "decode_steps": decode_steps,
        "decode_micro_steps": (stats["decode_micro_steps"]
                               - base["decode_micro_steps"]),
        "tokens_per_dispatch": decode_tokens / max(1, decode_steps),
        "slot_reuses": stats["slot_reuses"] - base["slot_reuses"],
        "qwait_p50_ms": float(np.percentile(qwaits_ms, 50)),
        "qwait_p99_ms": float(np.percentile(qwaits_ms, 99)),
        "max_gap_ms": max_gap_ms,
        "pages_hwm": stats["pages_hwm"], "pages_total": stats["pages_total"],
        "page_reuses": stats["page_reuses"],
    }


def run_multistep(*, arch: str = "rwkv6-7b", epitome: str = "kernel-q3",
                  decode_block: int = 1, capacity: int = 4,
                  prompt_len: int = 8, max_new: int = 33, max_len: int = 48,
                  seed: int = 0, check_bit_identity: bool = True) -> dict:
    """Closed-loop decode-throughput probe for one ``decode_block``.

    Saturates the engine (every slot admitted before the clock starts),
    then times ONLY the decode drain — so tok/s isolates how dispatch
    amortization scales with K, with prefill cost out of the window.
    ``max_new = K*steps + 1`` keeps the steady-state K constant (the +1
    is the prefill-sampled first token), so a sweep over K measures the
    same token count through different dispatch granularities."""
    import jax
    import jax.numpy as jnp
    from repro.launch import serve
    from repro.launch.engine import EngineConfig, Request

    eng = EngineConfig(arch=arch, epitome=epitome, smoke=True, mesh=None,
                       capacity=capacity, max_len=max_len, seed=seed,
                       decode_block=decode_block).build()
    rng = np.random.default_rng(seed)
    mk = lambda i: Request(prompt=tuple(int(t) for t in
                                        rng.integers(0, eng.cfg.vocab,
                                                     size=prompt_len)),
                           max_new_tokens=max_new, seed=seed + i)
    # warm run compiles every program the timed run hits (prefill bucket
    # plus the decode macro-step at this K and its tail K's)
    for i in range(capacity):
        eng.submit(mk(100 + i))
    eng.drain()
    base = eng.stats

    reqs = [mk(i) for i in range(capacity)]
    handles = [eng.submit(r) for r in reqs]     # prefill outside the window
    t0 = time.perf_counter()
    eng.drain()
    wall = time.perf_counter() - t0
    comps = [h.result() for h in handles]

    bit_identical = None
    if check_bit_identity:
        r, c = reqs[0], comps[0]
        ref, _ = serve.generate(
            eng.serve_params, eng.cfg,
            jnp.asarray(np.asarray(r.prompt, np.int32)[None]), max_len,
            r.max_new_tokens, temperature=r.temperature,
            key=jax.random.PRNGKey(r.seed))
        bit_identical = tuple(int(t) for t in np.asarray(ref)[0]) == c.tokens

    stats = eng.stats
    decode_steps = stats["decode_steps"] - base["decode_steps"]
    decode_tokens = sum(len(c.tokens) - 1 for c in comps)
    return {
        "arch": arch, "epitome": epitome, "decode_block": decode_block,
        "completed": len(comps),
        "decode_tok_s": decode_tokens / wall,
        "decode_steps": decode_steps,
        "decode_micro_steps": (stats["decode_micro_steps"]
                               - base["decode_micro_steps"]),
        "tokens_per_dispatch": decode_tokens / max(1, decode_steps),
        "decode_traces": stats["decode_traces"],
        "bit_identical": bit_identical,
    }


def _kv_pool_bytes(eng) -> int:
    """Bytes the engine's attention KV state pins (paged pool or dense
    per-slot blocks; 0 for attention-free arches)."""
    from repro.models.kv_pool import paged_leaf_paths
    kv = paged_leaf_paths(eng.cfg)
    return sum(leaf.nbytes
               for lk, layer in eng._pool.tree.items()
               for k, leaf in layer.items() if f"{lk}/{k}" in kv)


def run_stall_probe(*, arch: str = "qwen2-72b", epitome: str = "off",
                    paged: bool = True, capacity: int = 3,
                    max_len: int = 320, page_size: int = 16,
                    kv_pages: int = 28, prefill_chunk: int = 64,
                    long_prompt: int = 256, short_prompt: int = 8,
                    short_new: int = 40, long_new: int = 8,
                    warm_ticks: int = 10, seed: int = 0) -> dict:
    """Long-prompt arrival vs active decoders, paged+chunked or dense.

    Deterministic (no Poisson): two short requests decode; after
    ``warm_ticks`` steps one ``long_prompt``-token request arrives; the
    engine drains.  Returns the short requests' max inter-token gap (the
    decode stall the prefill caused), TTFTs, KV bytes, and bit-identity
    of EVERY request vs the one-shot path."""
    import jax
    import jax.numpy as jnp
    from repro.launch import serve
    from repro.launch.engine import EngineConfig, Request

    eng = EngineConfig(
        arch=arch, epitome=epitome, smoke=True, mesh=None,
        capacity=capacity, max_len=max_len,
        page_size=page_size if paged else 0,
        kv_pages=kv_pages if paged else 0,
        prefill_chunk=prefill_chunk if paged else 0,
        seed=seed).build()
    rng = np.random.default_rng(seed)
    shorts = [Request(prompt=tuple(int(t) for t in
                                   rng.integers(0, eng.cfg.vocab,
                                                size=short_prompt)),
                      max_new_tokens=short_new, seed=seed + i)
              for i in range(2)]
    long_req = Request(prompt=tuple(int(t) for t in
                                    rng.integers(0, eng.cfg.vocab,
                                                 size=long_prompt)),
                       max_new_tokens=long_new, seed=seed + 99)

    # warm every program the timed phase hits (short bucket, long prefill
    # — chunked or bucketed — and the pooled decode), then measure
    eng.submit(Request(prompt=long_req.prompt, max_new_tokens=2))
    eng.submit(Request(prompt=shorts[0].prompt, max_new_tokens=2))
    eng.drain()

    handles = [eng.submit(r) for r in shorts]
    for _ in range(warm_ticks):
        eng.step()
    h_long = eng.submit(long_req)
    eng.drain()
    comps = [h.result() for h in handles]
    long_c = h_long.result()

    gaps = [np.diff(c.token_times) for c in comps if len(c.token_times) > 1]
    max_gap_ms = float(max(g.max() for g in gaps)) * 1e3

    identical = True
    for r, c in [(shorts[0], comps[0]), (shorts[1], comps[1]),
                 (long_req, long_c)]:
        ref, _ = serve.generate(
            eng.serve_params, eng.cfg,
            jnp.asarray(np.asarray(r.prompt, np.int32)[None]), eng.seq_len,
            r.max_new_tokens, temperature=r.temperature,
            key=jax.random.PRNGKey(r.seed))
        identical &= tuple(int(t) for t in np.asarray(ref)[0]) == c.tokens

    stats = eng.stats
    return {
        "paged": paged, "max_gap_ms": max_gap_ms,
        "long_ttft_ms": long_c.ttft_s * 1e3,
        "kv_bytes": _kv_pool_bytes(eng),
        "bit_identical": identical,
        "prefill_chunks": stats["prefill_chunks"],
        "pages_hwm": stats["pages_hwm"], "pages_total": stats["pages_total"],
    }


def serving_smoke(emit) -> None:
    """benchmarks.run section: CPU-scale smoke row CI gates on."""
    m = run_serving()
    emit("kernels/serving-smoke", m["mean_tok_ms"] * 1e3,
         f"completed={m['completed']};"
         f"p50_ttft_ms={m['p50_ttft_ms']:.1f};"
         f"p99_ttft_ms={m['p99_ttft_ms']:.1f};"
         f"tok_s={m['tok_s']:.1f};"
         f"bit_identical={m['bit_identical']};"
         f"prefill_traces={m['prefill_traces']};"
         f"slot_reuses={m['slot_reuses']};"
         f"qwait_p50_ms={m['qwait_p50_ms']:.1f};"
         f"qwait_p99_ms={m['qwait_p99_ms']:.1f};"
         f"max_gap_ms={m['max_gap_ms']:.1f};"
         f"decode_steps={m['decode_steps']};"
         f"tokens_per_dispatch={m['tokens_per_dispatch']:.2f};"
         f"pages_hwm={m['pages_hwm']};"
         f"pages_total={m['pages_total']}")


def multistep_smoke(emit) -> None:
    """benchmarks.run section: K=4 fused decode vs K=1, closed loop.  CI
    gates bit_identical=True (K=4 engine tokens == one-shot generate) and
    speedup >= 1.5 (dispatch amortization must actually pay on the smoke
    LM, where per-dispatch overhead dominates the tiny compute)."""
    one = run_multistep(decode_block=1)
    four = run_multistep(decode_block=4)
    speedup = four["decode_tok_s"] / max(one["decode_tok_s"], 1e-9)
    emit("kernels/serving-multistep-smoke",
         1e6 / max(four["decode_tok_s"], 1e-9),     # us per decode token
         f"bit_identical={bool(one['bit_identical'] and four['bit_identical'])};"
         f"tok_s_k1={one['decode_tok_s']:.1f};"
         f"tok_s_k4={four['decode_tok_s']:.1f};"
         f"speedup={speedup:.2f};"
         f"decode_steps_k1={one['decode_steps']};"
         f"decode_steps_k4={four['decode_steps']};"
         f"tokens_per_dispatch_k4={four['tokens_per_dispatch']:.2f};"
         f"decode_traces_k4={four['decode_traces']}")


def paged_smoke(emit) -> None:
    """benchmarks.run section: paged+chunked vs dense under a long-prompt
    arrival.  CI gates bit_identical=True and stall_ratio < 1 (the paged
    engine's worst decode gap must beat the dense baseline's)."""
    p = run_stall_probe(paged=True)
    d = run_stall_probe(paged=False)
    ratio = p["max_gap_ms"] / max(d["max_gap_ms"], 1e-9)
    emit("kernels/serving-paged-smoke", p["max_gap_ms"] * 1e3,
         f"bit_identical={bool(p['bit_identical'] and d['bit_identical'])};"
         f"max_gap_ms_paged={p['max_gap_ms']:.1f};"
         f"max_gap_ms_dense={d['max_gap_ms']:.1f};"
         f"stall_ratio={ratio:.3f};"
         f"long_ttft_ms_paged={p['long_ttft_ms']:.1f};"
         f"long_ttft_ms_dense={d['long_ttft_ms']:.1f};"
         f"prefill_chunks={p['prefill_chunks']};"
         f"pages_hwm={p['pages_hwm']};"
         f"pages_total={p['pages_total']};"
         f"kv_bytes_paged={p['kv_bytes']};"
         f"kv_bytes_dense={d['kv_bytes']}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="rwkv6-7b")
    ap.add_argument("--epitome", default="kernel-q3")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--rate", type=float, default=50.0,
                    help="Poisson arrival rate, requests/s")
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--capacity", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--decode-block", default="1",
                    help="decode micro-steps fused per dispatch; a comma "
                         "list (e.g. 1,2,4,8) runs the closed-loop sweep "
                         "and prints one CSV row per K")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    blocks = [int(k) for k in str(args.decode_block).split(",")]
    if len(blocks) > 1:
        print("decode_block,decode_steps,decode_micro_steps,"
              "tokens_per_dispatch,decode_tok_s,bit_identical")
        for k in blocks:
            m = run_multistep(arch=args.arch, epitome=args.epitome,
                              decode_block=k, capacity=args.capacity,
                              seed=args.seed)
            print(f"{m['decode_block']},{m['decode_steps']},"
                  f"{m['decode_micro_steps']},"
                  f"{m['tokens_per_dispatch']:.2f},"
                  f"{m['decode_tok_s']:.1f},{m['bit_identical']}")
        return
    m = run_serving(arch=args.arch, epitome=args.epitome,
                    n_requests=args.requests, rate_hz=args.rate,
                    max_new=args.max_new_tokens, capacity=args.capacity,
                    temperature=args.temperature, seed=args.seed,
                    decode_block=blocks[0])
    print(f"[serving] {m['arch']} epitome={m['epitome']}: "
          f"completed={m['completed']} in {m['wall_s']:.2f}s "
          f"({m['tok_s']:.1f} tok/s)")
    print(f"[serving] ttft p50={m['p50_ttft_ms']:.1f}ms "
          f"p99={m['p99_ttft_ms']:.1f}ms; "
          f"per-token {m['mean_tok_ms']:.2f}ms; "
          f"prefill_traces={m['prefill_traces']} "
          f"slot_reuses={m['slot_reuses']}")
    print(f"[serving] qwait p50={m['qwait_p50_ms']:.1f}ms "
          f"p99={m['qwait_p99_ms']:.1f}ms; "
          f"max inter-token gap {m['max_gap_ms']:.1f}ms; "
          f"pages hwm={m['pages_hwm']}/{m['pages_total']}")
    print(f"[serving] decode_block={m['decode_block']} "
          f"device_steps={m['decode_steps']} "
          f"tokens_per_dispatch={m['tokens_per_dispatch']:.2f}")
    print(f"[serving] bit_identical={m['bit_identical']}")


if __name__ == "__main__":
    main()
