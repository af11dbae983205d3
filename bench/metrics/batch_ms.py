"""batch_ms (ms): host batch stacking per traced round — the summed
duration of the program's ``fedadp.batches`` spans
(``UnifiedBackend._stacked_round_batches``: every participant's round of
batches drawn and stacked on the client axis), inside the traced
window (``spans.from_ctx``)."""
from spans import per_round


def read(ctx):
    s = per_round(ctx, "fedadp.batches")
    return None if s is None else 1e3 * s
