"""agg_roofline (%): the least bytes a round's aggregation must move
(``roofline.agg_bytes``: every chunk, weight and running buffer read
once, every output written once) over the chip's HBM bandwidth, as a
share of the device time of ``agg_ms``'s programs. The bound is
bandwidth: the kernels do one multiply-add per four bytes."""
import re

from tracing import program_seconds

PROGRAMS = re.compile(r"_accum_(step|finish)")


def read(ctx):
    t = ctx.get("trace")
    if not t or not t["rounds"]:
        return None
    s = program_seconds(t, PROGRAMS) / t["rounds"]
    if s <= 0:
        return None
    least = ctx["agg_bytes_per_round"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / s
