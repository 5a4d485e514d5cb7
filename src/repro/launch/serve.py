"""Runnable serving driver: batched prefill + decode with a KV/state cache.

CPU-scale example:
  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-7b --smoke \
      --requests 4 --prompt-len 32 --max-new-tokens 16

Epitomized serving: a ``kernel`` x quant config (e.g. --epitome kernel-q3,
or --plan <lm-plan.json> for a per-layer searched design) is prepacked
after init — the epitomes quantize to int8 codes ONCE, vmapped over the
scan-over-groups param stack — so every decode step feeds the fused kernel
pure prepacked codes instead of re-quantizing inside the jitted forward.
The smoke output reports warm tok/s with and without the prepack.

All model/mesh/param setup is ``launch.engine.EngineConfig.build()`` — the
flags here are thin aliases over its fields plus the per-request knobs of
``launch.engine.Request``.  ``--engine`` additionally routes the same
prompts through the continuous-batching ``EpimEngine`` (one request per
prompt) and, when greedy, checks the engine's tokens bit-identical to the
one-shot batched path below.
"""
from __future__ import annotations

import argparse
import functools
import time
import warnings

import jax
import jax.numpy as jnp

from ..models import lm
from .compile_cache import enable_compile_cache
from .engine import EngineConfig, Request, sample_logits


def _select(logits, key, temperature, sampled: bool):
    # every position — including the first token after prefill — honors
    # the temperature; greedy only when temperature == 0.  Only the
    # greedy-vs-sampled branch is trace-static; the temperature value
    # itself stays traced so sweeping it never recompiles.  The sampled
    # branch draws its gumbel on replicated float32 logits
    # (engine.sample_logits): the bits are then identical whether this
    # runs eagerly (first token), inside the decode scan, or inside the
    # engine's pooled decode, on any mesh.
    if sampled:
        key, sub = jax.random.split(key)
        tok = jax.random.categorical(sub, sample_logits(logits) / temperature)
    else:
        tok = jnp.argmax(logits, axis=-1)
    return tok.astype(jnp.int32)[:, None], key


@functools.partial(jax.jit, static_argnames=("cfg",))
def _prefill(params, prompts, state, cfg):
    return lm.prefill(params, prompts, state, cfg)


@functools.partial(jax.jit, static_argnames=("cfg", "P", "gen", "sampled"))
def _decode_all(params, state, tok, key, temperature, *, cfg, P, gen,
                sampled):
    """The whole decode loop as one ``lax.scan`` over the decode state —
    one dispatch per generation instead of one per token (the Python loop
    paid a host round-trip per step).  Module-level jit keyed on the
    static (cfg, P, gen, sampled) so repeated generate calls reuse the
    compiled program instead of re-tracing."""
    def body(carry, i):
        state, tok, key = carry
        logits, state = lm.decode_step(params, state, tok,
                                       (P + i).astype(jnp.int32), cfg)
        tok, key = _select(logits[:, -1], key, temperature, sampled)
        return (state, tok, key), tok[:, 0]

    (state, _, _), toks = jax.lax.scan(
        body, (state, tok, key), jnp.arange(gen - 1))
    return toks, state


def generate(params, cfg, prompts, max_len: int, gen: int,
             temperature: float = 0.0, key=None):
    """prompts: (B, P) int32.  Greedy (or sampled) generation.

    Sampling semantics are bit-compatible with the old per-token Python
    loop: the key splits once per generated token in the same order, and
    every position honors the temperature (greedy when 0)."""
    B, P = prompts.shape
    sampled = temperature > 0
    temp = jnp.float32(temperature)
    state = lm.init_decode_state(cfg, B, max_len)
    logits, state = _prefill(params, prompts, state, cfg)
    tok, key = _select(logits[:, -1], key, temp, sampled)
    toks, state = _decode_all(params, state, tok, key, temp, cfg=cfg, P=P,
                              gen=gen, sampled=sampled)
    return jnp.concatenate([tok, toks.T], axis=1), state


def _warm_tok_s(params, cfg, prompts, max_len, gen, temperature, key) -> float:
    """Warm-path throughput: one compile call, then a timed repeat."""
    toks, _ = generate(params, cfg, prompts, max_len, gen,
                       temperature=temperature, key=key)
    jax.block_until_ready(toks)
    t0 = time.perf_counter()
    toks, _ = generate(params, cfg, prompts, max_len, gen,
                       temperature=temperature, key=key)
    jax.block_until_ready(toks)
    return prompts.shape[0] * gen / (time.perf_counter() - t0)


def _resolve_deprecated(args, old: str, new: str):
    """Old flag still works but warns; the new flag wins when both given."""
    old_v, new_v = getattr(args, old), getattr(args, new)
    if old_v is not None:
        warnings.warn(
            f"--{old.replace('_', '-')} is deprecated; use "
            f"--{new.replace('_', '-')} (see launch.engine.Request)",
            DeprecationWarning, stacklevel=2)
        if new_v is None:
            setattr(args, new, old_v)


def build_parser() -> argparse.ArgumentParser:
    """Flags mirror EngineConfig / Request fields; --batch and --gen are
    deprecated aliases kept for old scripts."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="rwkv6-7b")
    ap.add_argument("--epitome", default="off")
    ap.add_argument("--plan", default="",
                    help="EpitomePlan JSON driving per-layer epitome "
                         "specs/bits/mode (arch '<arch>-smoke' with --smoke)")
    ap.add_argument("--mesh", default="",
                    help="'DATA,MODEL' host mesh shape (e.g. 2,4) for "
                         "sharded serving; default: pure data parallelism "
                         "over all devices")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=None,
                    help="number of prompts to serve (default 4)")
    ap.add_argument("--batch", type=int, default=None,
                    help="deprecated alias of --requests")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new-tokens", type=int, default=None,
                    help="tokens to generate per request (default 16)")
    ap.add_argument("--gen", type=int, default=None,
                    help="deprecated alias of --max-new-tokens")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 samples every generated token")
    ap.add_argument("--engine", action="store_true",
                    help="also serve the prompts through the "
                         "continuous-batching EpimEngine (one request per "
                         "prompt) and report TTFT + agreement")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV pool page size in tokens for the engine's "
                         "attention caches (0 = dense per-slot blocks)")
    ap.add_argument("--kv-pages", type=int, default=0,
                    help="total KV pool pages (0 = capacity * pages/slot; "
                         "smaller oversubscribes and defers admissions)")
    ap.add_argument("--prefill-chunk", type=int, default=64,
                    help="engine prefill chunk in tokens, rounded up to "
                         "the arch's recurrence alignment (0 = whole-"
                         "prompt prefill)")
    ap.add_argument("--decode-block", type=int, default=1,
                    help="decode micro-steps fused into one engine "
                         "dispatch (auto-clipped per step so no request "
                         "overshoots max_new_tokens)")
    return ap


def main():
    args = build_parser().parse_args()
    enable_compile_cache()
    _resolve_deprecated(args, "batch", "requests")
    _resolve_deprecated(args, "gen", "max_new_tokens")
    n_req = 4 if args.requests is None else args.requests
    gen = 16 if args.max_new_tokens is None else args.max_new_tokens
    max_len = args.prompt_len + gen + 1

    engine = EngineConfig(
        arch=args.arch, epitome=args.epitome, plan=args.plan or None,
        mesh=args.mesh, smoke=args.smoke, capacity=n_req, max_len=max_len,
        page_size=args.page_size, kv_pages=args.kv_pages,
        prefill_chunk=args.prefill_chunk, decode_block=args.decode_block,
        seed=args.seed).build()
    cfg, packed = engine.cfg, engine.packed
    served = engine.serve_params
    # the mesh that actually runs, so the tok/s numbers below are
    # attributable
    print(f"[serve] mesh: {dict(engine.mesh.shape)} over "
          f"{len(jax.devices())} device(s)")
    prompts = jax.random.randint(engine.prompt_key, (n_req, args.prompt_len),
                                 0, cfg.vocab)
    label = args.plan if args.plan else args.epitome
    t0 = time.perf_counter()
    toks, _ = generate(served, cfg, prompts, max_len, gen,
                       temperature=args.temperature, key=engine.sample_key)
    jax.block_until_ready(toks)
    dt = time.perf_counter() - t0
    print(f"[serve] {args.arch} epitome={label}"
          f"{' (prepacked)' if packed is not None else ''}: generated "
          f"{toks.shape} in {dt:.2f}s "
          f"({n_req * gen / dt:.1f} tok/s)")
    print("[serve] sample:", jax.device_get(toks[0])[:16].tolist())
    if packed is not None:
        tw = lambda p: _warm_tok_s(p, cfg, prompts, max_len, gen,
                                   args.temperature, engine.sample_key)
        warm_packed, warm_otf = tw(packed), tw(engine.params)
        print(f"[serve] warm tok/s: prepacked={warm_packed:.1f} "
              f"on-the-fly={warm_otf:.1f} "
              f"(x{warm_packed / warm_otf:.2f}; prepack skips the per-call "
              f"epitome re-quantize)")
    if args.engine:
        host_prompts = jax.device_get(prompts)
        for row in host_prompts:
            engine.submit(Request(prompt=row, max_new_tokens=gen,
                                  temperature=args.temperature,
                                  seed=args.seed))
        comps = engine.drain()
        ttfts = sorted(c.ttft_s for c in comps)
        st = engine.stats
        line = (f"[serve] engine: completed={len(comps)} "
                f"p50_ttft={ttfts[len(ttfts) // 2] * 1e3:.1f}ms "
                f"steps={st['decode_steps']} "
                f"micro_steps={st['decode_micro_steps']} "
                f"prefill_traces={st['prefill_traces']} "
                f"prefill_chunks={st['prefill_chunks']} "
                f"pages_hwm={st['pages_hwm']}/{st['pages_total']}")
        if args.temperature == 0.0:
            # greedy: the engine rows must reproduce the one-shot batch
            ref = jax.device_get(toks)
            same = all(tuple(ref[i]) == comps[i].tokens
                       for i in range(len(comps)))
            line += f" bit_identical={same}"
        print(line)


if __name__ == "__main__":
    main()
