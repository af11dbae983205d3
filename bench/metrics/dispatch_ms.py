"""dispatch_ms (ms): the host side of local training per traced round —
the summed duration of the program's ``fedadp.step`` spans, each one
call of the jitted training step: the enqueue of the program and of the
copy of its numpy batch to the device, inside the traced window
(``spans.from_ctx``). The relayout of that batch for the device runs
after the call has returned, on the runtime's threads, so it is not in
this time; ``bench/spans.py`` reports the device idle time it covers."""
from spans import per_round


def read(ctx):
    s = per_round(ctx, "fedadp.step")
    return None if s is None else 1e3 * s
