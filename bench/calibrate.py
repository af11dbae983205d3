#!/usr/bin/env python3
"""Readings for the limits of a cell's output check, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 101,102,... \
        [--control-seeds 101,102,103] [--out readings.jsonl]

In one process, for each seed: the system's first rounds through the
window's own call, then the plain reference's, compared by
``harness.check``. For the control seeds also:

  fault_half_batch      the system with half of each batch left out
                        (the mean taken over the rest);
  fault_altered_answer  the system with its global model altered where
                        the engine produces it;
  control_bf16          the reference in the system's place, local
                        training computed in bfloat16 (parameters and
                        momentum float32);
  control_bf16_store    the same with parameters and momentum kept in
                        bfloat16 too.

A step that returns its state unchanged reads 1 by construction and is
not run here. One JSON line per (seed, variant) goes to stdout and to
``--out``. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def unchanged_step(prog):
    """Fault: local training returns the round-start state unchanged."""
    prog.backend.engine._train_packed = lambda sp, *a, **k: sp


def half_batch(prog):
    """Fault: the backend trains on the first half of every batch."""
    backend = prog.backend
    stack = backend._stacked_round_batches

    def halved(selected):
        out = stack(selected)
        return [{k: v[:, : max(1, v.shape[1] // 2)] for k, v in b.items()}
                for b in out]
    backend._stacked_round_batches = halved


def altered_answer(prog):
    """Fault: the round's global model is altered where the engine
    produces it (the output layer's first bias moves by 1)."""
    engine = prog.backend.engine
    run_round = engine.run_round

    def altered(*a, **k):
        out = run_round(*a, **k)
        out["out"]["b"] = out["out"]["b"].at[0].add(1.0)
        return out
    engine.run_round = altered


FAULTS = {"unchanged_step": unchanged_step, "half_batch": half_batch,
          "altered_answer": altered_answer}
RUN_FAULTS = ("half_batch", "altered_answer")
# control name -> (compute dtype, store dtype) of the reference
CONTROLS = {"control_bf16": ("bfloat16", "float32"),
            "control_bf16_store": ("bfloat16", "bfloat16")}


def emit(out, rec: dict) -> None:
    print(json.dumps(rec), flush=True)
    if out:
        out.write(json.dumps(rec) + "\n")
        out.flush()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "bench"))
    import harness
    harness.setup_env(ROOT)
    import jax.numpy as jnp
    from registry import load_cell
    cell = load_cell(args.workload)
    harness.device_info(cell.chips)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    out = open(args.out, "a") if args.out else None
    for seed in seeds:
        variants = ["system"]
        if seed in controls:
            variants += [f"fault_{f}" for f in RUN_FAULTS] + list(CONTROLS)
        ref = data = parts = None
        for v in variants:
            if v in CONTROLS:
                dt, st = (getattr(jnp, n) for n in CONTROLS[v])
                t = time.perf_counter()
                ctl = harness.reference_models(cell, seed, data, parts,
                                               dtype=dt, store=st)
                nums = harness.check(cell, seed, data, parts, ctl[1:], ref)
                emit(out, {"seed": seed, "variant": v,
                           "t_control_s": time.perf_counter() - t,
                           **{k: n["value"] for k, n in nums.items()},
                           "leaves": {k: n.get("leaf")
                                      for k, n in nums.items()}})
                continue
            t = time.perf_counter()
            prog = harness.Program(cell, seed)
            if v != "system":
                FAULTS[v[len("fault_"):]](prog)
            state = harness.init_weights(cell, seed)
            try:
                state, snaps = harness.run_checked_rounds(prog, state)
                err = None
            except Exception as e:            # a fault that crashes
                snaps, err = None, f"{type(e).__name__}: {e}"
            data, parts = prog.data, prog.parts
            del state
            prog.close()
            del prog
            gc.collect()
            t_sys = time.perf_counter() - t
            if ref is None:
                t = time.perf_counter()
                ref = harness.reference_models(cell, seed, data, parts)
                t_ref = time.perf_counter() - t
            if snaps is None:
                rec = {"seed": seed, "variant": v, "error": err}
            else:
                nums = harness.check(cell, seed, data, parts, snaps, ref)
                rec = {"seed": seed, "variant": v, "t_system_s": t_sys,
                       "t_reference_s": t_ref,
                       **{k: n["value"] for k, n in nums.items()},
                       "leaves": {k: n.get("leaf") for k, n in nums.items()},
                       "skipped": nums["d1_gap"]["skipped"]}
            emit(out, rec)
        del ref, data, parts
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
