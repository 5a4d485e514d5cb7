# One function per paper table. Prints ``name,us_per_call,derived`` CSV.
"""The paper's tables, reproduced by the PIM simulator.

  PYTHONPATH=src python -m benchmarks.run [--only table1,fig4,...]

Sections:
  table1   — EPIM Table 1 (#XB / CR / latency / energy / utilization)
  table2   — Table 2 quantization ablation (MSE proxy + tiny trained task)
  table3   — Table 3 epitome + pruning compression
  fig4     — Figure 4 uniform vs wrapping vs evo-search vs EPIM-Opt
  tiny     — calibrated tiny-ResNet predicted-vs-measured rows

Speed on the chip is measured by ``bench/run.py`` (BENCHMARK.json), not
here.
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


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--csv", default="",
                    help="also write the emitted rows to this CSV file")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import paper_tables
    sections = {
        "table1": paper_tables.table1,
        "table2": paper_tables.table2,
        "table3": paper_tables.table3,
        "fig4": paper_tables.fig4,
        "tiny": paper_tables.tiny,
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
