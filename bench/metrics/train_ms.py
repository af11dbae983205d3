"""train_ms (ms): local training per round — the engine's own host-clock
span around its training steps (``UnifiedEngine.phase_stats()["train"]``,
which syncs the device at the end of each chunk's steps), over the
rounds run with that span on."""


def read(ctx):
    timed = ctx.get("timed")
    if not timed or not timed["rounds"] or timed["train_s"] <= 0:
        return None
    return 1e3 * timed["train_s"] / timed["rounds"]
