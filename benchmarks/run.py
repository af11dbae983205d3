"""Benchmark harness — one module per paper table/figure plus the
framework-side reports. Prints ``name,us_per_call,derived`` CSV.

  table1   — paper Table 1 proxy (4 methods x synthetic datasets)
  fig4     — paper Fig. 4 proxy (convergence curves, rounds-to-90%)
  netchange— NetChange transform cost (the method's overhead)
  kernels  — kernel micro-benchmarks + interpret-mode correctness
  roofline — per (arch x shape) roofline terms from the dry-run artifacts

Env: FEDADP_BENCH_FULL=1 for the paper-scale protocol;
     FEDADP_BENCH_ONLY=<name>[,name] to select sections.

A section that raises still prints its ``section/<name>,...,ERROR=`` row
and the others still run, but the harness then exits 1.
"""
from __future__ import annotations

import os
import sys
import time


def main() -> int:
    from repro import compile_cache
    compile_cache.enable()
    only = os.environ.get("FEDADP_BENCH_ONLY")
    sections = only.split(",") if only else [
        "kernels", "netchange", "unified", "roofline", "fig4", "table1"]
    csv = ["name,us_per_call,derived"]
    failed = []
    for name in sections:
        t0 = time.time()
        n0 = len(csv)
        try:
            if name == "table1":
                from benchmarks.table1 import main as m
            elif name == "fig4":
                from benchmarks.fig4 import main as m
            elif name == "kernels":
                from benchmarks.kernels import main as m
            elif name == "netchange":
                from benchmarks.netchange_bench import main as m
            elif name == "unified":
                from benchmarks.unified_bench import main as m
            elif name == "roofline":
                from benchmarks.roofline_report import main as m
            elif name == "ablations":
                from benchmarks.ablations import main as m
            else:
                raise KeyError(name)
            csv = m(csv)
            csv.append(f"section/{name},{(time.time()-t0)*1e6:.0f},ok")
        except Exception as e:  # report, keep going, fail at the end
            failed.append(name)
            csv.append(f"section/{name},{(time.time()-t0)*1e6:.0f},"
                       f"ERROR={type(e).__name__}:{str(e)[:80]}")
        print("\n".join(csv[n0:]), file=sys.stderr, flush=True)
    print("\n".join(csv))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
