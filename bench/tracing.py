"""Profiler trace -> device busy time, per-op device time and idle gaps.

``capture(dir)`` wraps a region in ``jax.profiler`` tracing; the
benchmark marks each traced round with a host span named ``SPAN``.
``reduce_trace(path)`` reads the ``.xplane.pb`` with JAX alone:

  window   from the first ``SPAN`` start to the last ``SPAN`` end;
  busy     the union of the device-op intervals inside the window,
           averaged over the devices that ran any op;
  ops      device seconds per op ("XLA Ops" lines, named
           ``<program>/<instruction>``) and per program ("XLA Modules"
           lines), inside the window;
  gaps     the idle intervals between device ops, each named by the
           innermost host event (other than ``SPAN``) that covers at
           least half of it.

The trace is taken without the Python tracer and without HLO protos:
host events are the runtime's own (dispatch of each jitted program,
transfers) and the benchmark's spans, which keeps a traced round's
overhead and the file small.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
from collections import defaultdict
from typing import Dict, List, Tuple

SPAN = "bench_round"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@contextlib.contextmanager
def capture(directory: str):
    """Trace the enclosed region into ``directory``; yields a list that
    holds the ``.xplane.pb`` path once the region closes."""
    import jax
    out: List[str] = []
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(directory, profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
    files = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if files:
        out.append(files[-1])


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def short_op(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def _name_ops(ops, modules):
    """Prefix each op with the program whose interval holds its start."""
    mods = sorted((s, s + d, n) for n, s, d in modules)
    starts = [m[0] for m in mods]
    out = []
    for name, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        prog = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
        out.append((f"{prog}/{short_op(name)}", s, d))
    return out


def load(path: str) -> dict:
    """``{"devices": {plane: {line: events}}, "host": [events]}`` with
    events as ``(name, start_ns, duration_ns)``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, list]] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: _events(line) for line in plane.lines}
            if OPS_LINE in lines:
                lines[OPS_LINE] = _name_ops(lines[OPS_LINE],
                                            lines.get(MODULES_LINE, []))
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += _events(line)
    return {"devices": devices, "host": host}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def _clip(events, lo: float, hi: float):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def reduce_events(trace: dict, top: int = 10) -> dict:
    """Reduce ``load``'s events; ``None`` when the trace holds no
    ``SPAN`` or no device op."""
    spans = [(s, s + d) for name, s, d in trace["host"] if name == SPAN]
    if not spans:
        return None
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    busy, per_op, per_module = [], defaultdict(float), defaultdict(float)
    idle_all: List[Tuple[float, float]] = []
    for lines in trace["devices"].values():
        ops = list(_clip(lines.get(OPS_LINE, []), lo, hi))
        if not ops:
            continue
        merged = _union([(a, b) for _, a, b in ops])
        busy.append(sum(b - a for a, b in merged))
        for name, a, b in ops:
            per_op[name] += (b - a) * 1e-9
        for name, a, b in _clip(lines.get(MODULES_LINE, []), lo, hi):
            per_module[name] += (b - a) * 1e-9
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        idle_all += [(edges[i], edges[i + 1])
                     for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]]
    if not busy:
        return None
    host = [(n, s, s + d) for n, s, d in trace["host"] if n != SPAN and d > 0]
    gaps = []
    for a, b in sorted(idle_all, key=lambda ab: ab[0] - ab[1])[:top]:
        best, length = "unattributed", float("inf")
        for n, s, e in host:
            if min(b, e) - max(a, s) >= 0.5 * (b - a) and e - s < length:
                best, length = n, e - s
        gaps.append([best, (b - a) * 1e-9])
    n_dev = len(busy)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n_dev * 1e-9,
        "devices": n_dev,
        "rounds": len(spans),
        "ops": dict(per_op),
        "modules": dict(per_module),
        "device_ops": sorted(([n, t / n_dev] for n, t in per_op.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": gaps,
    }


def program_seconds(reduced: dict, pattern) -> float:
    """Device seconds per device of the programs whose "XLA Modules"
    name matches the compiled regex ``pattern``."""
    return sum(t for name, t in reduced["modules"].items()
               if pattern.search(name)) / reduced["devices"]


def reduce_trace(path: str, top: int = 10) -> dict:
    return reduce_events(load(path), top=top)
