"""Benchmark harness — one section per paper table/figure + system benches.

  PYTHONPATH=src python -m benchmarks.run [--scale 1.0] [--only tables|ncf|system]

Emits ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).
"""
from __future__ import annotations

import argparse
import sys
import traceback

from repro import compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--only", default="")
    args = ap.parse_args()

    print("name,us_per_call,derived")
    sections = []
    if args.only in ("", "tables"):
        from benchmarks import bench_paper_tables
        sections.append(("tables",
                         lambda: bench_paper_tables.run_all(args.scale)))
    if args.only in ("", "ncf"):
        from benchmarks import bench_ncf
        sections.append(("ncf", bench_ncf.run_all))
    if args.only in ("", "system"):
        from benchmarks import bench_system
        sections.append(("system", bench_system.run_all))

    failed = 0
    for name, fn in sections:
        try:
            fn()
        except Exception:
            failed += 1
            print(f"SECTION-FAILED,{name},", file=sys.stderr)
            traceback.print_exc()
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    compile_cache.enable()
    main()
