"""Named host spans of the federated round, on the profiler's clock.

Every span of the round path is made here, by ``span(name, **counts)``,
a thin wrapper over ``jax.profiler.TraceAnnotation``: inside a
``jax.profiler.trace`` the span is a host event on the same clock as the
device ops, its keyword arguments become the event's stats, and with no
profiler running it costs under a microsecond. The spans, nested as
listed, and what each argument counts:

  fedadp.round        ``UnifiedBackend.run_round``: ``round`` (index),
                      ``clients`` (participants)
  fedadp.batches      host batch stacking: ``steps`` (stacked batches),
                      ``bytes`` (bytes of the stacked numpy arrays)
  fedadp.round_start  a chunk's round start (mask rows, segment
                      matrices, the width ``up(down(g))`` or the fused
                      depth-only start): ``rows``, ``path`` ("width" or
                      "fused"), and on the width path ``bytes`` (the
                      NetChange mappings, row indices and segment ids
                      sent from the host); in coverage and global-filler
                      modes the chunk's coverage rows open a second one
                      after training, so they are not held through it
  fedadp.train        local training of a chunk (optimizer init and the
                      step loop): ``rows``, ``steps``
  fedadp.step         one call of the jitted training step: ``bytes``
                      (numpy bytes passed with the call, i.e. copied
                      host to device; the runtime relayouts them for
                      the device after the call returns)
  fedadp.aggregate    one accumulate call, the closing finish, or a
                      whole-plane aggregation: ``rows``

``PhaseClock`` keeps the engine's host-clock phase seconds
(``UnifiedEngine.timing`` / ``phase_stats()``) on the same spans.
"""
from __future__ import annotations

import contextlib
import time

import jax
import numpy as np

ROUND = "fedadp.round"
BATCHES = "fedadp.batches"
ROUND_START = "fedadp.round_start"
TRAIN = "fedadp.train"
STEP = "fedadp.step"
AGGREGATE = "fedadp.aggregate"
NAMES = (ROUND, BATCHES, ROUND_START, TRAIN, STEP, AGGREGATE)


def span(name: str, **counts) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` with ``counts`` as its stats. Counts known
    only at the end are added with the span's ``set_metadata``."""
    return jax.profiler.TraceAnnotation(name, **counts)


def host_bytes(tree) -> int:
    """Bytes of the numpy leaves of ``tree``: what a jitted call copies
    from the host."""
    return sum(x.nbytes for x in jax.tree.leaves(tree)
               if isinstance(x, np.ndarray))


class PhaseClock:
    """Host seconds of named round phases, summed over timed spans."""

    def __init__(self, *phases: str):
        self._s = dict.fromkeys(phases, 0.0)

    @contextlib.contextmanager
    def span(self, name: str, phase: str, timed: bool, **counts):
        """``span(name, **counts)`` around the body. When ``timed``, the
        arrays the body appends to the yielded list are waited for at
        exit, and the span's host seconds are added to ``phase``."""
        sync: list = []
        with span(name, **counts):
            t0 = time.perf_counter()
            yield sync
            if timed:
                jax.block_until_ready(sync)
                self._s[phase] += time.perf_counter() - t0

    def stats(self, reset: bool = False) -> dict:
        out = dict(self._s)
        if reset:
            self._s = dict.fromkeys(self._s, 0.0)
        return out
