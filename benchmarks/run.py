# One function per paper table. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark harness.

  PYTHONPATH=src python -m benchmarks.run [--only table1,fig4,...]

Sections:
  table1   — EPIM Table 1 (#XB / CR / latency / energy / utilization)
  table2   — Table 2 quantization ablation (MSE proxy + tiny trained task)
  table3   — Table 3 epitome + pruning compression
  fig4     — Figure 4 uniform vs wrapping vs evo-search vs EPIM-Opt
  kernels  — epitome matmul mode timings + Pallas interpret checks
  autotune — heuristic vs measured-winner kernel blocks (tuned_us <=
             heuristic_us per row; fused-fold pipelined variant in the sweep)
  costmodel — simulator-vs-measured Spearman rank correlation over a
             k-sweep of epitomized layers + MeasuredCost memoization gate
  serving  — continuous-batching engine under open-loop Poisson load
  roofline — per (arch x shape) roofline table from the dry-run artifacts
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

ROWS = []


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    line = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(line)
    print(line, flush=True)


def roofline(emit) -> None:
    import json
    d = os.path.join(os.path.dirname(__file__), "..", "experiments", "dryrun")
    if not os.path.isdir(d):
        emit("roofline/missing", 0.0, "run repro.launch.dryrun first")
        return
    for name in sorted(os.listdir(d)):
        if not name.endswith(".json"):
            continue
        with open(os.path.join(d, name)) as f:
            r = json.load(f)
        rl = r.get("roofline")
        if rl is None:       # memory/compile-only lowering (multi-pod proof)
            emit(f"roofline/{r['arch']}/{r['shape']}/{r['epitome']}/{r['mesh']}",
                 0.0, f"memory-only;peak={r['per_device']['peak_bytes']/2**30:.1f}GiB")
            continue
        emit(f"roofline/{r['arch']}/{r['shape']}/{r['epitome']}/{r['mesh']}",
             rl["bound_s"] * 1e6,
             f"dom={rl['dominant']};comp={rl['t_compute_s']*1e3:.1f}ms;"
             f"mem={rl['t_memory_s']*1e3:.1f}ms;"
             f"coll={rl['t_collective_s']*1e3:.1f}ms;"
             f"useful={rl.get('useful_ratio', 0):.2f};"
             f"roofline={rl.get('roofline_fraction', 0)*100:.0f}%;"
             f"peak={r['per_device']['peak_bytes']/2**30:.1f}GiB")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--csv", default="",
                    help="also write the emitted rows to this CSV file "
                         "(CI uploads it as an artifact)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import paper_tables, kernels_bench, serving_bench
    sections = {
        "table1": paper_tables.table1,
        "table2": paper_tables.table2,
        "table3": paper_tables.table3,
        "fig4": paper_tables.fig4,
        "tiny": paper_tables.tiny,
        "kernels": lambda e: (kernels_bench.epitome_modes(e),
                              kernels_bench.pallas_interpret_correctness(e),
                              kernels_bench.quant_epitome(e),
                              kernels_bench.conv_quant_epitome(e),
                              kernels_bench.legalized_plan(e),
                              kernels_bench.lm_plan(e)),
        # heuristic-vs-tuned block shapes on conv + LM decode geometry
        "autotune": kernels_bench.autotune_blocks,
        # simulator-vs-measured rank correlation + MeasuredCost memoization
        # over a k-sweep of epitomized-layer count
        "costmodel": kernels_bench.costmodel_smoke,
        # sharded serving smoke: meaningful when the process has > 1
        # device (CI forces 8 CPU host devices via XLA_FLAGS)
        "sharded": kernels_bench.sharded_plan,
        # continuous-batching engine under Poisson load (TTFT / tok/s),
        # the paged+chunked vs dense long-prompt stall probe, and the
        # K-fused decode dispatch-amortization A/B
        "serving": lambda e: (serving_bench.serving_smoke(e),
                              serving_bench.paged_smoke(e),
                              serving_bench.multistep_smoke(e)),
        "roofline": roofline,
    }
    only = set(args.only.split(",")) if args.only else set(sections)
    print("name,us_per_call,derived")
    for name, fn in sections.items():
        if name in only:
            fn(emit)
    if args.csv:
        with open(args.csv, "w") as f:
            f.write("name,us_per_call,derived\n")
            for row in ROWS:
                f.write(row + "\n")


if __name__ == "__main__":
    main()
