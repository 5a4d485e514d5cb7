#!/usr/bin/env python3
"""Read the numbers that decide ``correct``, and the controls', for one
seed of one cell, on the chip.

    python3 bench/control.py --workload <cell> --seed <n> --seconds 20

The cell's set-up, a window at the cell's own load, then the comparison
with the plain reference and with the controls (the reference one
precision step down, ``bench/reference/lowp.py``).  Prints one JSON line.
The limits in the configuration files were set from these readings, run
once per seed (one process each, as the benchmark runs); the benchmark's
own runs never run the controls.
"""
import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import run as bench_run
    from bench import trace
    try:
        cell, config, traffic, _, _ = bench_run.resolve(args.workload)
        bench_run.devices_or_refuse(cell["chips"])
    except bench_run.Refused as e:
        bench_run.log(f"refused: {e}")
        return 1
    bench_run.compile_cache()
    runner = bench_run.load_module(
        os.path.join(BENCH, "runners", config["runner"] + ".py"), "runner")
    t = time.time()
    run = runner.Cell(config, traffic, args.seed)
    run.setup()
    slice_ = trace.Slice(None, args.seconds)
    slice_.hook = run.snapshot
    run.run_window(args.seconds, slice_)
    e2e = run.end_to_end()
    run.release()
    checks = run.check(control=True)
    print(json.dumps({"seed": args.seed, "seconds": time.time() - t,
                      "e2e": e2e, "checks": {n: v for n, v, _ in checks}}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
