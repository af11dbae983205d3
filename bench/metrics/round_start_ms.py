"""round_start_ms (ms): round start per traced round — the summed
duration of the program's ``fedadp.round_start`` spans (each chunk's
mask rows, segment matrices and ``up(down(g))`` on the width path or
the fused start on depth-only cohorts), inside the traced window
(``spans.from_ctx``)."""
from spans import per_round


def read(ctx):
    s = per_round(ctx, "fedadp.round_start")
    return None if s is None else 1e3 * s
