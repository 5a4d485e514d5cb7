"""What the program marks in a traced slice: its host spans and the
op_name scopes of its device ops.

The engine (``launch/engine.py``) opens ``epim.*`` host spans with
``jax.profiler.TraceAnnotation``; they land in the same ``.xplane.pb`` as
the device planes, on one clock.  The layer dispatch and the fold
(``core/layers.py``, ``kernels/ops.py``) wrap their ops in
``jax.named_scope``, so each device op's op_name path names the layer it
belongs to.  The XLA Ops events carry no op_name in their text: the
paths come from the HLO the profiler embeds, through xprof's
``framework_op_stats`` (installed with the profiler's plugin).

Each reading is cached per trace directory and returns None where the
trace holds none of the program's marks, as a program without them
gives.
"""
from __future__ import annotations

import functools
import json
import shutil
import tempfile
from typing import Dict, List, Optional, Tuple

from bench import trace

SPAN_PREFIX = "epim."


def host_spans(pd, prefix: str) -> List[Tuple[int, int]]:
    """(start_ns, end_ns) of every host event whose name starts with
    ``prefix``."""
    return [(int(e.start_ns), int(e.start_ns + e.duration_ns))
            for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events
            if e.name.startswith(prefix)]


def holes(busy: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """The gaps between the merged busy intervals, first op to last."""
    merged = trace._union(busy)
    return [(b, a) for (_, b), (a, _) in zip(merged, merged[1:])]


def overlap_ns(idle: List[Tuple[int, int]],
               spans: List[Tuple[int, int]]) -> int:
    """Nanoseconds of ``idle`` (disjoint intervals) that lie inside the
    union of ``spans`` (which may overlap and nest)."""
    cover = trace._union(spans)
    total, j = 0, 0
    for a, b in sorted(idle):
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            total += min(b, cover[k][1]) - max(a, cover[k][0])
            k += 1
    return total


@functools.lru_cache(maxsize=4)
def idle(trace_dir: str) -> Optional[Dict[str, float]]:
    """Device-idle seconds on the first chip between its first and last
    op (``idle_s``), the part of them under an open ``epim.*`` span
    (``epim_s``), and the part under an ``epim.*`` or the benchmark's own
    ``bench.*`` span (``covered_s``).  None without ``epim.*`` spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(trace.xplane_file(trace_dir))
    epim = host_spans(pd, SPAN_PREFIX)
    chips = [ops for ops in map(trace._ops, trace.device_planes(pd)) if ops]
    if not epim or not chips:
        return None
    gaps = holes([(s, e) for _, s, e in chips[0]])
    return {"idle_s": sum(b - a for a, b in gaps) / 1e9,
            "epim_s": overlap_ns(gaps, epim) / 1e9,
            "covered_s": overlap_ns(gaps, epim + host_spans(pd, "bench."))
            / 1e9}


@functools.lru_cache(maxsize=4)
def op_self_us(trace_dir: str) -> Tuple[Dict[str, float], float]:
    """Device self-time in microseconds per op_name path, and the device's
    busy time (every op, idle excluded), from one ``framework_op_stats``
    reduction.  xprof saves its conversion beside the file it reads, so
    it reads a copy in a temporary directory."""
    from xprof.convert import raw_to_tool_data
    with tempfile.TemporaryDirectory() as tmp:
        path = shutil.copy(trace.xplane_file(trace_dir), tmp)
        data, _ = raw_to_tool_data.xspace_to_tool_data(
            [path], "framework_op_stats", {"use_saved_result": False})
    table = json.loads(data)[0]
    cols = [c["id"] for c in table["cols"]]
    per_path: Dict[str, float] = {}
    for row in table["rows"]:
        r = dict(zip(cols, (c.get("v") for c in row["c"])))
        if r["host_or_device"] == "Device" and r["type"] != "IDLE":
            per_path[r["operation"]] = (per_path.get(r["operation"], 0.0)
                                        + r["total_self_time"])
    return per_path, sum(per_path.values())


def scope_share(trace_dir: str, scope: str) -> Optional[float]:
    """Device self-time of the ops whose op_name path holds ``scope`` as
    one of its parts, over device busy time, in %.  None where no op
    does."""
    per_path, busy = op_self_us(trace_dir)
    mine = sum(us for path, us in per_path.items()
               if scope in path.split("/"))
    return 100.0 * mine / busy if mine > 0 else None
