"""The readers of the program's own marks (``bench/spans.py`` and the
per-layer metrics built on it): on hand-made intervals, on a slice of
``rwkv6-7b-q3.decode`` recorded on the chip with the program's spans and
scopes (``spans_data/``), on the slice recorded before the program had
them (``data/``), and in a traced run of the harness on the CPU.

The new slice lies in a directory of its own: ``trace.reduce`` reads the
newest ``.xplane.pb`` anywhere under the directory it is given, so a
second file under ``data/`` would take the place of the first in
``test_trace.py``."""
import json
import os

from bench import run as bench_run
from bench import spans
from bench.tests.test_cells import _config, _run, LM_TRAFFIC

HERE = os.path.dirname(os.path.abspath(__file__))
MARKED = os.path.join(HERE, "spans_data")
UNMARKED = os.path.join(HERE, "data")
NEW = ("prefill_ms.decode", "engine_idle_share.decode",
       "epitome_share.decode", "fold_share.decode")


class _Slice:
    traced = True

    def __init__(self, trace_dir, window_s):
        self.dir, self.window_s = trace_dir, window_s


def _read(name, ctx):
    return bench_run.reader(name).read(ctx, name)


def _counters():
    with open(os.path.join(MARKED, "decode_slice_spans.counters.json")) as f:
        return json.load(f)


def test_overlap_counts_idle_under_nested_and_overlapping_spans():
    idle = [(10, 20), (30, 40), (50, 60)]
    cover = [(0, 12), (5, 15), (18, 35), (32, 33), (70, 80)]
    assert spans.overlap_ns(idle, cover) == 5 + 2 + 5
    assert spans.overlap_ns(idle, []) == 0
    assert spans.overlap_ns(idle, [(0, 100)]) == 30
    assert spans.overlap_ns(idle, [(20, 30), (40, 50)]) == 0


def test_holes_are_the_gaps_between_merged_ops():
    assert spans.holes([(5, 9), (0, 2), (1, 3), (6, 7), (12, 13)]) == [
        (3, 5), (9, 12)]
    assert spans.holes([(0, 4)]) == []


def test_readers_on_the_marked_slice():
    """Three engine steps and one admission of ``rwkv6-7b-q3.decode`` at
    capacity 32 on a TPU v5 lite, with the program's marks."""
    got = spans.idle(MARKED)
    assert 0 < got["epim_s"] <= got["covered_s"] <= got["idle_s"]
    window = got["idle_s"] * 10            # any window longer than the idle
    ctx = {"slice": _Slice(MARKED, window), "counters": _counters()}
    v = {n: _read(n, ctx) for n in NEW}
    assert v["prefill_ms.decode"] > 0
    for n in NEW[1:]:
        assert 0 < v[n] < 100, (n, v[n])
    assert v["epitome_share.decode"] >= v["fold_share.decode"]


def test_readers_on_a_slice_without_the_marks():
    """A program without the spans, scopes and counter (the slice of
    ``data/`` was recorded from one): every reader returns None."""
    c = {k: {kk: vv for kk, vv in d.items() if kk != "prefill_s"}
         for k, d in _counters().items()}
    ctx = {"slice": _Slice(UNMARKED, 0.5), "counters": c}
    assert {n: _read(n, ctx) for n in NEW} == dict.fromkeys(NEW)


def test_the_reduction_leaves_no_file_behind():
    spans.op_self_us.cache_clear()
    before = sorted(os.listdir(MARKED))
    per_path, busy = spans.op_self_us(MARKED)
    assert busy > 0 and any("epim.fold" in p for p in per_path)
    assert sorted(os.listdir(MARKED)) == before


def test_traced_cpu_run_reports_the_counter_and_no_device_share(
        monkeypatch):
    """The CPU has no device plane: the trace-read shares stay out of the
    line, and the counter's metric is in it."""
    layer = [{"name": n, "unit": u} for n, u in zip(
        NEW, ("ms", "%", "%", "%"))]
    line = _run(monkeypatch, _config("rwkv6-smoke-q3"), LM_TRAFFIC,
                trace=1, layer=layer)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"prefill_ms.decode"}
    assert line["metrics"]["prefill_ms.decode"]["value"] > 0
