#!/usr/bin/env python3
"""Record the small chip trace that ``test_bench_tracing.py`` reduces.

    python3 bench/tests/record_trace.py [--workload vgg-depth20.one_step]

On the chip: builds the cell's system from seed 1, runs its checked
rounds (which compile every program), then traces one round under the
benchmark's own capture and round span, and writes the ``.xplane.pb``
gzipped to ``bench/tests/data/<workload>.xplane.pb.gz``. It also prints
the reduction, so the committed file can be checked against it.
"""
from __future__ import annotations

import argparse
import gzip
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="vgg-depth20.one_step")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "bench"))
    import harness
    harness.setup_env(ROOT)
    import jax
    import tracing
    from registry import load_cell
    cell = load_cell(args.workload)
    harness.device_info(cell.chips)
    prog = harness.Program(cell, 1)
    state = harness.init_weights(cell, 1)
    state, _ = harness.run_checked_rounds(prog, state)
    tdir = ROOT / "bench" / "out" / "record_trace"
    shutil.rmtree(tdir, ignore_errors=True)
    with tracing.capture(str(tdir)) as found:
        with jax.profiler.TraceAnnotation(tracing.SPAN):
            state = prog.round(state, harness.CHECKED_ROUNDS)
            jax.block_until_ready(state)
    out = ROOT / "bench" / "tests" / "data" / f"{args.workload}.xplane.pb.gz"
    out.parent.mkdir(parents=True, exist_ok=True)
    with open(found[0], "rb") as src, gzip.open(out, "wb") as dst:
        shutil.copyfileobj(src, dst)
    red = tracing.reduce_trace(found[0])
    print(json.dumps({"file": str(out.relative_to(ROOT)),
                      "bytes": out.stat().st_size,
                      **{k: red[k] for k in ("window_s", "busy_s", "rounds",
                                             "devices", "modules")}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
