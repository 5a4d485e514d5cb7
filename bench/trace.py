"""The traced slice of a window, and the reduction from a device trace to
the numbers the per-layer metrics read.

A ``--trace 1`` run traces a slice of ``SLICE_SECONDS`` in the middle of
its window (a whole window would make the trace large and slow to read).
``reduce`` reads the ``.xplane.pb`` the JAX profiler wrote, with JAX's own
``ProfileData``:

* the device planes (``/device:TPU:<n>``) and their op line (``XLA Ops``);
* busy seconds: the union of the op events' intervals, per chip, averaged
  over the chips; the window is the slice's length on the host clock;
* device time per op name, and the Mosaic kernels among them;
* idle gaps: the longest stretches with no op on the first chip, each
  named by the benchmark's own host span (``bench.*``) that overlaps it
  most, or ``host`` where none does.
"""
from __future__ import annotations

import glob
import os
import re
import time
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

SLICE_SECONDS = 4.0


class Slice:
    """Starts and stops the profiler around the middle of a window.  The
    cell's runner calls ``open`` when its window opens, ``tick`` from its loop and
    ``close`` when the window ends.  Without a directory it only keeps
    time."""

    def __init__(self, trace_dir: Optional[str], seconds: float,
                 length: float = SLICE_SECONDS):
        self.dir, self.seconds = trace_dir, seconds
        self.length = min(length, seconds / 2)
        self.hook = None                   # called with "slice_start"/"_end"
        self.t_a = self.t_b = None
        self._a = self._b = None
        self._on = False

    @property
    def traced(self) -> bool:
        return self.t_a is not None and self.t_b is not None

    @property
    def window_s(self) -> float:
        return self.t_b - self.t_a

    def open(self, t0: float) -> None:
        self._a = t0 + (self.seconds - self.length) / 2
        self._b = self._a + self.length

    def tick(self) -> None:
        if self.dir is None:
            return
        now = time.perf_counter()
        if self.t_a is None and now >= self._a:
            import jax
            jax.profiler.start_trace(self.dir)
            self.t_a = time.perf_counter()
            self._on = True
            if self.hook:
                self.hook("slice_start")
        elif self._on and now >= self._b:
            self._stop()

    def close(self) -> None:
        if self._on:
            self._stop()

    def _stop(self) -> None:
        import jax
        if self.hook:
            self.hook("slice_end")
        self.t_b = time.perf_counter()
        jax.profiler.stop_trace()
        self._on = False


def xplane_file(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


_HLO = re.compile(r"^%?([\w.\-]+) = .*?\b([a-z][\w\-]*)\(")


def short_name(name: str) -> str:
    """``name (op)`` of an event named by its HLO instruction text, with
    the custom call's target for a Mosaic kernel; other names as they
    are."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    op = m.group(2)
    if op == "custom-call":
        t = re.search(r'custom_call_target="([^"]+)"', name)
        op = t.group(1) if t else op
    return f"{m.group(1)} ({op})"


_CONTAINERS = ("(while)", "(conditional)", "(call)")


def is_kernel(name: str) -> bool:
    """A Mosaic kernel's event: a custom call (``tpu_custom_call``, the
    only custom call these programs make on the chip)."""
    return " custom-call(" in name


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def device_planes(pd) -> list:
    return [p for p in pd.planes if p.name.startswith("/device:TPU:")
            and p.name.rsplit(":", 1)[1].isdigit()]


def _ops(plane) -> List[tuple]:
    """(name, start_ns, end_ns) of the plane's op events."""
    for line in plane.lines:
        if line.name == "XLA Ops":
            return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events]
    return []


def _host_spans(pd) -> List[tuple]:
    out = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for e in line.events:
                if e.name.startswith("bench."):
                    out.append((e.name, int(e.start_ns),
                                int(e.start_ns + e.duration_ns)))
    return out


def reduce(trace_dir: str, slice_: Slice, top: int = 10) -> Dict:
    """The numbers of a traced slice: busy and window seconds, device time
    per op, the kernels' events, and the longest idle gaps."""
    from jax.profiler import ProfileData
    if not slice_.traced:          # the window closed before the slice
        return {"busy_s": 0.0, "window_s": 0.0, "events": 0,
                "kernel_s": 0.0, "device_ops": [], "idle_gaps": []}
    pd = ProfileData.from_file(xplane_file(trace_dir))
    per_chip = [ops for ops in map(_ops, device_planes(pd)) if ops]  # used
    busy = [sum(b - a for a, b in _union([(s, e) for _, s, e in ops]))
            for ops in per_chip]
    ops0 = per_chip[0] if per_chip else []
    by_name: Dict[str, float] = defaultdict(float)
    kernel_ns = 0
    for name, s, e in ops0:
        short = short_name(name)
        if not short.endswith(_CONTAINERS):   # their bodies' ops count
            by_name[short] += (e - s) / 1e9
        if is_kernel(name):
            kernel_ns += e - s
    merged = _union([(s, e) for _, s, e in ops0])
    holes = sorted(((b - a, a, b) for (_, a), (b, _) in zip(merged, merged[1:])),
                   reverse=True)[:top]
    spans = _host_spans(pd)
    gaps = []
    for width, a, b in holes:
        best, label = 0, "host"
        for name, s, e in spans:
            o = min(b, e) - max(a, s)
            if o > best:
                best, label = o, name
        gaps.append((label, width / 1e9))
    return {
        "busy_s": sum(busy) / len(busy) / 1e9 if busy else 0.0,
        "window_s": slice_.window_s,
        "events": len(ops0),
        "kernel_s": kernel_ns / 1e9,
        "device_ops": [[n, s] for n, s in sorted(by_name.items(),
                                                  key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, s] for n, s in gaps[:top]],
    }
