"""h2d_mb (MB): local-training input copied host to device per traced
round — the ``bytes`` counts of the program's ``fedadp.step`` spans
(numpy bytes passed with each call of the jitted training step), summed
inside the traced window (``spans.from_ctx``), over 1e6."""
from spans import per_round


def read(ctx):
    b = per_round(ctx, "fedadp.step", stat="bytes")
    return None if b is None else b / 1e6
