#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The last line of stdout is the result object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``[, ``breakdown``],
``checks``); the numbers compared with the reference, each beside its
limit, are also the last lines of stderr. With no TPU, or fewer chips
than the cell needs, it exits non-zero and prints no result.

JAX's persistent compilation cache lives at ``<checkout>/.jax_cache``
(``harness.setup_env``), so only a cell's first run in a checkout
compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "bench"))
    import harness
    harness.setup_env(ROOT)
    try:
        result = harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            t_start=T_START,
            out_dir=str(ROOT / "bench" / "out" / args.workload))
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    for k, v in result["checks"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(harness.order(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
