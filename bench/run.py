#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json`` per process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Run from the root of a checkout, on the machine that holds the chips the
cell asks for.  It refuses (exit code 1 or 2, no result) without a TPU,
with fewer chips than the cell needs, with a device kind that
``bench/peaks.json`` does not list, or without the program (``src/``).

A run: set-up (weights and inputs made on the device from ``--seed``, every
shape the traffic uses compiled and warmed; JAX's compile cache lives in
the checkout, so only a checkout's first run compiles), then a measured
window of ``--seconds``, then the comparison with the plain reference that
decides ``correct``.  ``--trace 1`` records a device trace of a slice in
the middle of the window and reports the per-layer metrics instead of the
end-to-end ones.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name in ``BENCHMARK.json``:
``bench/configs/<config>.json`` (its ``runner`` names
``bench/runners/<runner>.py`` and its ``reference`` names
``bench/reference/<reference>.py``), ``bench/traffic/<traffic>.json``, and
``bench/metrics/<metric>.py`` (or ``<prefix>.py`` for ``<prefix>.<part>``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit.
"""
import time

START = time.time()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Refused(Exception):
    pass


def _load_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise Refused(f"missing {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(workload: str):
    """(cell, config, traffic, per-layer and end-to-end metric entries)."""
    manifest = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = _load_json(os.path.join(ROOT, entry["file"]))
    traffic = _load_json(os.path.join(BENCH, "traffic",
                                      cell["traffic"] + ".json"))

    def mine(metrics):
        return [m for m in metrics if workload in m.get("workloads", [workload])]
    return (cell, config, traffic, mine(manifest["end_to_end"]),
            mine(manifest["per_layer"]))


def reader(metric: str):
    """bench/metrics/<name>.py, else bench/metrics/<prefix>.py."""
    for stem in (metric, metric.split(".")[0]):
        path = os.path.join(BENCH, "metrics", stem + ".py")
        if os.path.isfile(path):
            return load_module(path, f"bench_metric_{stem.replace('.', '_')}")
    raise Refused(f"no reader for per-layer metric {metric!r}")


def devices_or_refuse(chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        raise Refused(f"no TPU (platform {d.platform}); the benchmark runs "
                      "only on the chip")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, found {len(devs)}")
    peaks = _load_json(os.path.join(BENCH, "peaks.json"))
    if d.device_kind not in peaks:
        raise Refused(f"device kind {d.device_kind!r} not in bench/peaks.json")
    return devs, peaks[d.device_kind]


def compile_cache() -> None:
    """JAX's persistent cache where the program keeps it (inside the
    checkout, or ``JAX_COMPILATION_CACHE_DIR``), holding every program
    however quickly it compiles, so a warm run loads all and compiles
    none."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def count_compiles():
    """A counter of backend compilations from now on (cache hits and
    misses alike: a program compiled inside the window is a fault of the
    set-up's warm-up)."""
    import jax
    box = [0]

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            box[0] += 1
    jax.monitoring.register_event_duration_secs_listener(listen)
    return box


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        cell, config, traffic, e2e_defs, layer_defs = resolve(args.workload)
        if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
            raise Refused("the program (src/repro) is not in this checkout")
        sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
        devs, peaks = devices_or_refuse(cell["chips"])
        readers = ({m["name"]: reader(m["name"]) for m in layer_defs}
                   if args.trace else {})
    except Refused as e:
        log(f"refused: {e}")
        return 1

    from bench import trace as tracing
    compile_cache()
    compiles = count_compiles()
    runner = load_module(os.path.join(BENCH, "runners",
                                      config["runner"] + ".py"),
                         f"bench_runner_{config['runner']}")
    run = runner.Cell(config, traffic, args.seed)
    run.setup()
    log(f"set-up: {time.time() - START:.1f} s")

    trace_dir = os.path.join(TRACE_DIR, args.workload) if args.trace else None
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    slice_ = tracing.Slice(trace_dir, args.seconds)
    slice_.hook = run.snapshot
    setup_s = time.time() - START
    before = compiles[0]
    run.run_window(args.seconds, slice_)
    in_window = compiles[0] - before
    e2e = run.end_to_end()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs[:cell["chips"]])
    log(f"window: {json.dumps(e2e)}; programs compiled in the window: "
        f"{in_window}; peak_bytes_in_use {peak}")

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {}
    if args.trace:
        red = tracing.reduce(trace_dir, slice_)
        ctx = {"cell": cell, "config": config, "traffic": traffic,
               "peaks": peaks, "trace": red, "slice": slice_,
               "work": run.slice_work(slice_.t_a, slice_.t_b, peaks),
               "counters": run.counters, "peak_bytes": peak}
        metrics = {}
        for m in layer_defs:
            v = readers[m["name"]].read(ctx, m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        log(f"trace: {json.dumps({k: red[k] for k in ('busy_s', 'window_s', 'events')})}")
    else:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        for m in e2e_defs:
            if m["name"] in e2e and e2e[m["name"]] is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    run.release()
    log(f"after release: bytes_in_use "
        f"{(devs[0].memory_stats() or {}).get('bytes_in_use')}")
    t = time.time()
    checks = run.check()
    log(f"reference comparison: {time.time() - t:.1f} s")
    compared = [(n, v, lim) for n, v, lim in checks if lim is not None]
    for name, v, _ in checks:
        if (name, v, None) in checks:
            log(f"recorded {name}: {v}")
    for name, v, lim in compared:
        log(f"check {name}: {v} (limit {lim})")
    correct = bool(compared) and all(v is not None and v <= lim
                                     for _, v, lim in compared)
    line = {"correct": correct, "attempted": run.attempted(),
            "failed": run.failed, "metrics": metrics, "device": device,
            **result,
            "checks": {n: {"value": v, "limit": lim} for n, v, lim in compared}}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
