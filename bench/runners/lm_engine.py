"""Language-model cells: requests served by the program's continuous-
batching engine (``EngineConfig(...).build()`` and ``EpimEngine``).

The engine is driven only through its request API (``submit``, ``step``,
handles) and read only through its ``stats`` counters and the per-token
times on its completions.  Scheduling knobs are left at the program's
defaults; the configuration fixes the model, the epitome variant, the
slot capacity and the token budget.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List, Optional

import numpy as np

from bench import loadgen, work


def _pct(values, q):
    """The q-th percentile (linear interpolation), or None if empty."""
    return float(np.percentile(np.asarray(values), q)) if len(values) else None


class Sent:
    """One request as the client saw it: when it was due and its handle."""
    __slots__ = ("due", "req", "handle")

    def __init__(self, due, req, handle):
        self.due, self.req, self.handle = due, req, handle

    def done(self) -> bool:
        return self.handle is not None and self.handle.done()

    def all_times(self) -> List[float]:
        """Per-token stamps so far.  A finished request's come from its
        Completion; the engine exposes an unfinished one's only on the
        handle's record."""
        if self.handle is None:
            return []
        if self.handle.done():
            return list(self.handle.result().token_times)
        return list(self.handle._rec.token_times)

    def times(self, end: float) -> List[float]:
        return [t for t in self.all_times() if t <= end]


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = config, traffic, seed
        self.sent: List[Sent] = []
        self.failed = 0
        self.engine = None
        self.t0 = self.t_end = 0.0
        self.counters: Dict[str, Dict[str, float]] = {}

    # -- set-up ---------------------------------------------------------------
    def setup(self) -> None:
        import jax
        from repro.launch.engine import EngineConfig
        prog, eng = self.cfg["program"], self.cfg["engine"]
        self.engine = EngineConfig(
            arch=prog["arch"], epitome=prog["epitome"], smoke=prog["smoke"],
            capacity=eng["capacity"], max_len=eng["max_len"],
            seed=loadgen.key_seed(self.seed)).build()
        jax.block_until_ready(self.engine.serve_params)
        self._warm()

    def _warm(self) -> None:
        """Every shape the traffic can use: prompts at each end of its
        length range and on each side of every power of two inside it,
        with generations long enough to run full decode blocks and their
        tails."""
        from repro.launch.engine import Request
        lo, hi = self.traffic["prompt_len"]["lo"], self.traffic["prompt_len"]["hi"]
        lens = {lo, hi}
        p = 1
        while p <= hi:
            lens |= {x for x in (p, p + 1) if lo <= x <= hi}
            p *= 2
        rng = loadgen.rng_for(self.seed, "warm")
        out_lo = self.traffic["output_len"]["lo"]
        for i, n in enumerate(sorted(lens)):
            self.engine.submit(Request(
                prompt=rng.integers(0, self.cfg["vocab"], n).tolist(),
                max_new_tokens=min(out_lo, 9 + i), temperature=0.0))
        self.engine.drain()

    # -- the window -----------------------------------------------------------
    def _submit(self, req, due: float) -> None:
        from repro.launch.engine import Request
        try:
            h = self.engine.submit(Request(prompt=req.prompt,
                                           max_new_tokens=req.max_new_tokens,
                                           temperature=req.temperature))
        except ValueError:
            self.failed += 1
            h = None
        self.sent.append(Sent(due, req, h))

    def snapshot(self, tag: str) -> None:
        now = time.perf_counter()
        self.counters[tag] = {"t": now, **self.engine.stats}

    def run_window(self, seconds: float, slice_) -> None:
        import jax
        arr = self.traffic["arrival"]
        gen = loadgen.requests(self.traffic, self.cfg["vocab"], self.seed)
        self.snapshot("window_start")
        self.t0 = t0 = time.perf_counter()
        end = t0 + seconds
        slice_.open(t0)
        if arr["process"] == "closed":
            # the clients' first requests are all due at the opening; they
            # go in shortest prompt first, so the order of one instant's
            # arrivals is the same for every seed
            live = []
            first = sorted((next(gen) for _ in range(arr["clients"])),
                           key=lambda r: (len(r.prompt), r.max_new_tokens))
            for req in first:
                self._submit(req, t0)
                live.append(self.sent[-1])
            while time.perf_counter() < end:
                with jax.profiler.TraceAnnotation("bench.engine_step"):
                    self.engine.step()
                slice_.tick()
                for i, s in enumerate(live):
                    if s.handle is None or s.handle.done():
                        now = time.perf_counter()
                        if now >= end:
                            break
                        due = s.all_times()[-1] if s.handle else now
                        with jax.profiler.TraceAnnotation("bench.submit"):
                            self._submit(next(gen), due)
                        live[i] = self.sent[-1]
        else:
            dues = loadgen.arrival_offsets(self.traffic, self.seed, seconds)
            i = 0
            while time.perf_counter() < end:
                now = time.perf_counter()
                while i < len(dues) and t0 + dues[i] <= now:
                    with jax.profiler.TraceAnnotation("bench.submit"):
                        self._submit(next(gen), t0 + dues[i])
                    i += 1
                # an idle step() returns at once, so the loop polls
                with jax.profiler.TraceAnnotation("bench.engine_step"):
                    self.engine.step()
                slice_.tick()
        self.t_end = time.perf_counter()
        slice_.close()
        self.snapshot("window_end")

    # -- what the window measured ---------------------------------------------
    def end_to_end(self) -> Dict[str, Optional[float]]:
        """ttft: first token minus due, for every request sent in the
        window (a request with no token yet counts the whole wait to the
        window's end).  tpot: per request, (last - first) / (tokens - 1)
        over the tokens it had by the window's end."""
        end = self.t_end
        ttft, tpot = [], []
        for s in self.sent:
            ts = s.times(end)
            ttft.append(((ts[0] if ts else end) - s.due) * 1e3)
            if len(ts) >= 2:
                tpot.append((ts[-1] - ts[0]) / (len(ts) - 1) * 1e3)
        return {"ttft_p90_ms": _pct(ttft, 90), "tpot_p90_ms": _pct(tpot, 90),
                "requests": len(self.sent), "ttft_samples": len(ttft),
                "tpot_samples": len(tpot), "ttft_p50_ms": _pct(ttft, 50),
                "tpot_p50_ms": _pct(tpot, 50)}

    def attempted(self) -> int:
        return len(self.sent)

    def slice_work(self, t_a: float, t_b: float, peaks: dict
                   ) -> Dict[str, float]:
        """Work the program completed between two instants of the window:
        decode tokens retired (a first token comes from the prefill), the
        prompts whose prefill ended inside, their useful operations, and
        the least time the chip needs for their epitome-kernel calls (each
        decode micro-step at the live rows it served)."""
        decode_tokens, prefill_ops, prompts, least = 0, 0, 0, 0.0
        for s in self.sent:
            ts = s.all_times()
            if ts and t_a <= ts[0] <= t_b:
                P = len(s.req.prompt)
                prompts += 1
                prefill_ops += work.lm_prefill_ops(self.cfg, P)
                least += work.calls_least_seconds(
                    work.lm_epitome_calls(self.cfg, P), peaks)
            decode_tokens += sum(t_a <= t <= t_b for t in ts[1:])
        c = self.counters
        steps = (c["slice_end"]["decode_micro_steps"]
                 - c["slice_start"]["decode_micro_steps"]
                 if "slice_end" in c else 0)
        if steps and decode_tokens:
            least += steps * work.calls_least_seconds(
                work.lm_epitome_calls(self.cfg, decode_tokens / steps), peaks)
        return {"decode_tokens": decode_tokens, "prompts": prompts,
                "useful_ops": decode_tokens * work.lm_token_ops(self.cfg)
                + prefill_ops, "kernel_least_s": least}

    def release(self) -> None:
        """Drop the program's weights and state, keeping what was served."""
        self.served = [(list(s.req.prompt), list(s.handle.result().tokens))
                       for s in self.sent if s.done()]
        self.engine = None
        for s in self.sent:
            s.handle = None
        gc.collect()

    # -- correctness ----------------------------------------------------------
    def sample(self, k: int) -> List[tuple]:
        """The longest finished request and k - 1 others drawn from the
        seed."""
        done = sorted(self.served, key=lambda pt: -len(pt[1]))
        if not done:
            return []
        rest = done[1:]
        rng = loadgen.rng_for(self.seed, "check")
        pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
        return [done[0]] + [rest[i] for i in sorted(pick)]

    def check(self, control: bool = False) -> List[tuple]:
        """Gaps, over every served token of the sample, by which the
        token's reference logit lies below the reference's best at that
        position (greedy traffic only): the widest and the mean.  Those the
        configuration gives a limit are compared, the rest are printed for
        the record."""
        from bench import check
        lim = self.cfg["check"]
        sample = self.sample(lim["requests"])
        if not sample:
            return [("unchecked", 1, 0)]       # nothing finished to check
        got = check.lm_gaps(self.cfg, loadgen.key_seed(self.seed), sample,
                            control=control)
        return [(k, v, lim.get(k)) for k, v in got.items()]
