"""train_device_ms (ms): device time of local training per traced round
— the summed device durations of the programs named ``train_step``
(the engine's jitted training step, ``jit_train_step`` in the trace's
"XLA Modules" lines)."""
import re

from tracing import program_seconds

PROGRAMS = re.compile(r"^jit_train_step")


def read(ctx):
    t = ctx.get("trace")
    if not t or not t["rounds"]:
        return None
    s = program_seconds(t, PROGRAMS)
    return 1e3 * s / t["rounds"] if s > 0 else None
