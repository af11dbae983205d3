"""agg_ms (ms): device time of the streaming aggregation programs per
traced round — the summed device durations of the programs that run the
``plane_accum`` / ``plane_finish`` kernels (``kernels/fedavg/ops.py``:
``_accum_step``, ``_accum_finish``), as the trace's "XLA Modules" lines
name them."""
import re

from tracing import program_seconds

PROGRAMS = re.compile(r"_accum_(step|finish)")


def read(ctx):
    t = ctx.get("trace")
    if not t or not t["rounds"]:
        return None
    s = program_seconds(t, PROGRAMS)
    return 1e3 * s / t["rounds"] if s > 0 else None
