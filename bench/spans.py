#!/usr/bin/env python3
"""Program spans in a profiler trace -> time, self time, counts and the
device idle time under each span.

The program marks its round with host spans named ``fedadp.*``
(``src/repro/fl/spans.py``), each with counts as event stats.
``load(path)`` reads the ``.xplane.pb`` with JAX alone, keeping those
stats (``tracing.load`` drops them), the benchmark's round spans
(``tracing.SPAN``) and the device-op intervals. ``reduce_events``
clips everything to the window the round spans mark (first start to
last end) and gives, per span name:

  count    spans that overlap the window
  total_s  their summed duration inside the window
  self_s   the same less the time of the program spans nested in them
  stats    each numeric stat summed over those spans
  idle_s   device idle time (no op running on the device) under the
           span, i.e. where it is the innermost program span; idle time
           under no program span is reported apart

and, for the whole window, the device idle time during which the
runtime relayouts host input for the device (``RELAYOUT`` events, on
its worker threads): a numpy argument's copy to the device runs after
the call that passed it has returned, so such idle time lies under
whatever span the round's thread has reached by then.

Spans nest within one host thread; the program opens them on the thread
that runs the round. Device times are averaged over the devices that
ran any op in the window.

The per-layer readers get the reduction from ``from_ctx(ctx)``, which
finds the traced run's file where ``bench/run.py`` has the harness write
it (``bench/out/<cell>/trace``), once per context.

    python3 bench/spans.py <trace dir or .xplane.pb>

prints the reduction per traced round as JSON.
"""
from __future__ import annotations

import glob
import json
import os
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import tracing

PREFIX = "fedadp."
RELAYOUT = "Transpose"


def load(path: str) -> dict:
    """``{"rounds": [(start, end)], "spans": [(name, start, end, stats,
    thread)], "devices": {plane: [(start, end)]}, "relayout": [(start,
    end)]}`` in nanoseconds."""
    from jax.profiler import ProfileData
    rounds, spans, relayout = [], [], []
    devices: Dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == tracing.OPS_LINE:
                    ops = [(float(e.start_ns), float(e.end_ns))
                           for e in line.events]
                    if ops:
                        devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name == tracing.SPAN:
                        rounds.append((float(e.start_ns), float(e.end_ns)))
                    elif e.name == RELAYOUT:
                        relayout.append((float(e.start_ns), float(e.end_ns)))
                    elif e.name.startswith(PREFIX):
                        spans.append((e.name, float(e.start_ns),
                                      float(e.end_ns), dict(e.stats),
                                      f"{plane.name}#{i}"))
    return {"rounds": rounds, "spans": spans, "devices": devices,
            "relayout": relayout}


def _self_pieces(spans) -> List[Tuple[float, float, str]]:
    """Each span's interval less its children's, as ``(start, end,
    name)``; spans must nest (one thread) and lie inside the window."""
    pieces = []
    stack: list = []                   # [name, end, cursor]

    def close(top):
        if top[2] < top[1]:
            pieces.append((top[2], top[1], top[0]))

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            parent = stack[-1]
            if parent[2] < s:
                pieces.append((parent[2], s, parent[0]))
            parent[2] = e
        stack.append([name, e, s])
    while stack:
        close(stack.pop())
    return sorted(pieces)


def _overlap(pieces, idle) -> Dict[str, float]:
    """Length of ``idle`` (sorted, disjoint) under each piece's name."""
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for s, e, name in pieces:
        while j < len(idle) and idle[j][1] <= s:
            j += 1
        k = j
        while k < len(idle) and idle[k][0] < e:
            out[name] += min(e, idle[k][1]) - max(s, idle[k][0])
            k += 1
    return out


def _idle(ops, lo: float, hi: float) -> List[Tuple[float, float]]:
    merged = tracing._union([(max(a, lo), min(b, hi)) for a, b in ops
                             if min(b, hi) > max(a, lo)])
    edges = [lo] + [x for ab in merged for x in ab] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def reduce_events(trace: dict) -> Optional[dict]:
    """Reduce ``load``'s events; ``None`` without a round span."""
    if not trace["rounds"]:
        return None
    lo = min(s for s, _ in trace["rounds"])
    hi = max(e for _, e in trace["rounds"])
    per: Dict[str, dict] = {}
    threads: Dict[str, list] = defaultdict(list)
    for name, s, e, stats, thread in trace["spans"]:
        a, b = max(s, lo), min(e, hi)
        if b <= a:
            continue
        d = per.setdefault(name, {"count": 0, "total_s": 0.0,
                                  "self_s": 0.0, "stats": {}, "idle_s": 0.0})
        d["count"] += 1
        d["total_s"] += (b - a) * 1e-9
        for k, v in stats.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                d["stats"][k] = d["stats"].get(k, 0) + v
        threads[thread].append((name, a, b))
    pieces = sorted(p for t in threads.values() for p in _self_pieces(t))
    for s, e, name in pieces:
        per[name]["self_s"] += (e - s) * 1e-9
    idle_by: Dict[str, float] = defaultdict(float)
    idle_total = relayout_idle = 0.0
    relayout = [(a, b, RELAYOUT) for a, b in tracing._union(
        [(max(a, lo), min(b, hi)) for a, b in trace.get("relayout", [])
         if min(b, hi) > max(a, lo)])]
    busy_devices = [ops for ops in trace["devices"].values()
                    if any(min(b, hi) > max(a, lo) for a, b in ops)]
    for ops in busy_devices:
        idle = _idle(ops, lo, hi)
        idle_total += sum(b - a for a, b in idle)
        for name, t in _overlap(pieces, idle).items():
            idle_by[name] += t
        relayout_idle += _overlap(relayout, idle)[RELAYOUT]
    n_dev = len(busy_devices)

    def per_device(t):
        return t / n_dev * 1e-9 if n_dev else None

    for name, t in idle_by.items():
        per[name]["idle_s"] = per_device(t)
    return {"rounds": len(trace["rounds"]), "window_s": (hi - lo) * 1e-9,
            "devices": n_dev, "idle_s": per_device(idle_total),
            "idle_outside_s": per_device(idle_total - sum(idle_by.values())),
            "idle_relayout_s": per_device(relayout_idle), "spans": per}


def reduce_trace(path: str) -> Optional[dict]:
    return reduce_events(load(path))


def _newest(directory: Path) -> Optional[str]:
    files = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def _same_window(red: dict, trace: Optional[dict]) -> bool:
    """Whether ``red`` reduces the trace ``tracing.reduce_trace`` gave."""
    if not trace:
        return True
    return (red["rounds"] == trace["rounds"]
            and abs(red["window_s"] - trace["window_s"]) <= 1e-6)


def from_ctx(ctx: dict) -> Optional[dict]:
    """The span reduction of the traced run the readers' context
    describes, computed once and kept as ``ctx["spans"]``; ``None`` when
    the run left no trace there or it holds no program span."""
    if "spans" not in ctx:
        ctx["spans"] = None
        cell = ctx.get("cell")
        path = (_newest(cell.root / "bench" / "out" / cell.name / "trace")
                if cell is not None else None)
        try:
            red = reduce_trace(path) if path else None
        except Exception as e:    # the reader reports nothing, the run goes on
            print(f"spans: {path} not reduced: {type(e).__name__}: {e}",
                  file=sys.stderr, flush=True)
            red = None
        if red and red["spans"] and _same_window(red, ctx.get("trace")):
            ctx["spans"] = red
    return ctx["spans"]


def per_round(ctx: dict, name: str,
              stat: Optional[str] = None) -> Optional[float]:
    """Span ``name``'s seconds (or its summed ``stat``) per traced
    round; ``None`` when the trace holds no such span."""
    red = from_ctx(ctx)
    if not red or name not in red["spans"]:
        return None
    d = red["spans"][name]
    v = d["total_s"] if stat is None else d["stats"].get(stat)
    return None if v is None else v / red["rounds"]


def summary(red: dict) -> dict:
    """Per traced round, in ms: each span's time, self time and the
    device idle time under it, and the share of the window's idle time
    that falls under the spans nested in ``fedadp.round``."""
    n = red["rounds"]
    out = {"rounds": n, "window_ms_per_round": 1e3 * red["window_s"] / n,
           "spans": {}}
    for name, d in sorted(red["spans"].items()):
        out["spans"][name] = {
            "count_per_round": d["count"] / n,
            "ms_per_round": 1e3 * d["total_s"] / n,
            "self_ms_per_round": 1e3 * d["self_s"] / n,
            "idle_ms_per_round": 1e3 * d["idle_s"] / n,
            "stats_per_round": {k: v / n for k, v in d["stats"].items()}}
    if red["idle_s"]:
        below = sum(d["idle_s"] for name, d in red["spans"].items()
                    if name != PREFIX + "round")
        out["idle_ms_per_round"] = 1e3 * red["idle_s"] / n
        out["idle_outside_ms_per_round"] = 1e3 * red["idle_outside_s"] / n
        out["idle_relayout_ms_per_round"] = 1e3 * red["idle_relayout_s"] / n
        out["idle_below_round_share"] = below / red["idle_s"]
    return out


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print("usage: python3 bench/spans.py <trace dir or .xplane.pb>",
              file=sys.stderr)
        return 2
    path = args[0] if args[0].endswith(".pb") else _newest(Path(args[0]))
    red = reduce_trace(path) if path else None
    if not red:
        print(f"no round span in {args[0]}", file=sys.stderr)
        return 1
    print(json.dumps({"file": path, **summary(red)}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
