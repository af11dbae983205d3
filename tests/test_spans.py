"""The round's named spans (``repro.fl.spans``): what a profiler trace of
one fedadp round holds, what each span's arguments count, and that the
spans change neither the result nor the engine's phase clock.

The cohort mixes depth and width (``_COHORT``), so round start takes the
width path; four clients at ``k_chunk`` 2 stream as two chunks.
"""
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs.vgg_family import VGGConfig
from repro.core import VGGFamily
from repro.core import segments as sg
from repro.data import EASY, ClientSampler, image_classification, iid_partition
from repro.fl import FedADPStrategy, UnifiedBackend, spans

FAMILY = VGGFamily()
SIZE, CLASSES, BATCH, EPOCHS, PER_CLIENT = 8, 4, 8, 2, 16
SAMPLE_BYTES = SIZE * SIZE * 3 * 4 + 4      # f32 image + int32 label


def _tiny(name, stages):
    return VGGConfig(name=name, stages=stages, classifier=(16,),
                     n_classes=CLASSES, image_size=SIZE)


_COHORT = [_tiny("w1", ((8,), (8,))), _tiny("w2", ((8,), (12, 8))),
           _tiny("w3", ((12, 8), (12, 8))), _tiny("w1", ((8,), (8,)))]


def _backend(layout="stream", k_chunk=2):
    spec = dataclasses.replace(EASY, image_size=SIZE, n_classes=CLASSES)
    n = PER_CLIENT * len(_COHORT)
    data = image_classification(spec, n, seed=0)
    samplers = [ClientSampler(data, p, round_fraction=0.5, batch_size=BATCH,
                              seed=i)
                for i, p in enumerate(iid_partition(n, len(_COHORT), seed=0))]
    be = UnifiedBackend(FAMILY, _COHORT, samplers, local_epochs=EPOCHS,
                        lr=0.05, momentum=0.9, agg_layout=layout,
                        k_chunk=k_chunk)
    be.bind(FedADPStrategy(FAMILY, _COHORT, [s.n_samples for s in samplers]))
    return be


def _round(be, state, r):
    out = be.run_round(state, r, range(len(_COHORT)))
    return jax.block_until_ready(out)


def _traced_round(be, state, r, tmp):
    """Round ``r`` under the profiler; returns the new state and the
    trace's fedadp spans as ``(name, start, end, stats)``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp), profiler_options=opts):
        state = _round(be, state, r)
    path, = glob.glob(os.path.join(str(tmp), "**", "*.xplane.pb"),
                      recursive=True)
    found = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in spans.NAMES:
                    found.append((e.name, e.start_ns, e.end_ns,
                                  dict(e.stats)))
    return state, found


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


# each participant's round: PER_CLIENT * 0.5 images, one batch an epoch
STEPS = EPOCHS
HAND_BYTES = len(_COHORT) * STEPS * BATCH * SAMPLE_BYTES


@pytest.mark.parametrize("layout,k_chunk,chunks", [("stream", 2, 2),
                                                   ("plane", None, 1)])
def test_span_tree_of_one_round(tmp_path, layout, k_chunk, chunks):
    be = _backend(layout, k_chunk)
    state = _round(be, be.init_state(jax.random.PRNGKey(0)), 0)
    _, found = _traced_round(be, state, 1, tmp_path)
    by = {n: [s for s in found if s[0] == n] for n in spans.NAMES}

    rnd, = by[spans.ROUND]
    assert rnd[3] == {"round": 1, "clients": len(_COHORT)}
    assert all(_inside(s, rnd) for s in found)
    batches, = by[spans.BATCHES]
    assert batches[3] == {"steps": STEPS, "bytes": HAND_BYTES}
    rows = len(_COHORT) // chunks
    starts = [s[3] for s in by[spans.ROUND_START]]
    assert [{k: v for k, v in st.items() if k != "bytes"}
            for st in starts] == [{"rows": rows, "path": "width"}] * chunks
    # the width round start sends mappings and segment ids, a few KB a
    # row at most — never the dense E Eᵀ matrices it used to push
    eng = be.engine
    dense = sum(4 * sg.leaf_shape(eng._gshapes, p)[ax] ** 2
                for p, axes in eng._axes_map.items() for ax in axes)
    assert all(0 < st["bytes"] <= min(4096, dense) * st["rows"]
               for st in starts), (starts, dense)
    assert [s[3] for s in by[spans.TRAIN]] == \
        [{"rows": rows, "steps": STEPS}] * chunks
    # one aggregate per chunk, and the streaming round's closing finish
    aggs = [s[3]["rows"] for s in by[spans.AGGREGATE]]
    assert aggs == ([rows] * chunks + [len(_COHORT)] if layout == "stream"
                    else [len(_COHORT)])
    # every step sits in a training span; they carry the batches' bytes
    assert len(by[spans.STEP]) == chunks * STEPS
    assert all(any(_inside(s, t) for t in by[spans.TRAIN])
               for s in by[spans.STEP])
    assert sum(s[3]["bytes"] for s in by[spans.STEP]) == HAND_BYTES
    # the phases follow one another inside the round
    order = sorted((s for s in found
                    if s[0] not in (spans.ROUND, spans.STEP)),
                   key=lambda s: s[1])
    assert order[0][0] == spans.BATCHES
    assert [s[0] for s in order[1:4]] == [spans.ROUND_START, spans.TRAIN,
                                          spans.AGGREGATE]


def test_spans_leave_the_round_bit_identical(tmp_path):
    plain, traced = _backend(), _backend()
    key = jax.random.PRNGKey(0)
    a = _round(plain, _round(plain, plain.init_state(key), 0), 1)
    b = _round(traced, traced.init_state(key), 0)  # fedlint: ignore[FDL001] one model, two runs
    b, _ = _traced_round(traced, b, 1, tmp_path)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_phase_clock_counts_training_only_when_timing():
    be = _backend()
    state = _round(be, be.init_state(jax.random.PRNGKey(0)), 0)
    eng = be.engine
    eng.timing = True
    eng.phase_stats(reset=True)
    state = _round(be, state, 1)
    timed = eng.phase_stats(reset=True)
    assert timed["train"] > 0.0
    assert eng.phase_stats() == {"train": 0.0}
    eng.timing = False
    _round(be, state, 2)
    assert eng.phase_stats() == {"train": 0.0}


def test_host_bytes_counts_numpy_leaves_only():
    tree = {"x": np.zeros((2, 3), np.float32), "y": np.zeros(5, np.int32),
            "d": jax.numpy.zeros(7)}
    assert spans.host_bytes(tree) == 2 * 3 * 4 + 5 * 4
