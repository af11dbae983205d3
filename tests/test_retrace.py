"""Retrace regression (analysis.retrace; ISSUE 7): ``Federation.run``
on the unified backend compiles everything in round 1 and NOTHING after
— across full and sampled participation. The known hazard is the
engine's per-subset-size jit cache (``UnifiedEngine._steps``): a
weak-typed scalar or re-built closure would silently turn one compile
into a compile per round, which no accuracy test can see.
"""
import jax
import numpy as np
import pytest

from repro.analysis.retrace import RetraceDetector
from repro.configs.vgg_family import scaled, vgg
from repro.core import VGGFamily
from repro.data import EASY, ClientSampler, image_classification, iid_partition
from repro.fl import (Federation, FedADPStrategy, Participation,
                      UnifiedBackend)

FAMILY = VGGFamily()


def test_detector_counts_jit_cache_misses():
    """Sanity: a fresh jit compiles once; the cache hit is silent; a new
    input shape is a new compile."""
    @jax.jit
    def f(x):
        return x * 2 + 1

    with RetraceDetector() as det:
        f(np.ones((3,), np.float32)).block_until_ready()
        first = det.compiles
        assert first >= 1
        det.checkpoint()
        f(np.full((3,), 2.0, np.float32)).block_until_ready()   # cache hit
        assert det.since_checkpoint == 0
        f(np.ones((5,), np.float32)).block_until_ready()        # new shape
        assert det.since_checkpoint >= 1
    assert det.events                     # raw names kept for diagnostics


def _setup():
    cfgs = [scaled(vgg(a), 0.125, 32) for a in ("vgg13", "vgg16")]
    n = 160
    data = image_classification(EASY, n, seed=0)
    test = image_classification(EASY, 80, seed=9)
    parts = iid_partition(n, len(cfgs), seed=0)
    samplers = [ClientSampler(data, p, round_fraction=0.5, batch_size=16,
                              seed=i) for i, p in enumerate(parts)]
    return cfgs, samplers, test


@pytest.mark.parametrize("pname,participation", [
    ("full", Participation()),
    ("sample", Participation.sample(0.5, seed=2)),
])
def test_federation_compiles_nothing_after_round_one(pname, participation):
    """Rounds >= 2 hit the round-1 jit caches: zero backend_compile
    events after the first round's record is emitted (training step,
    eval step, and every embedding/aggregation helper included).
    Sampled participation keeps the subset SIZE constant, so it must
    not mint new entries in the per-size step cache either."""
    cfgs, samplers, test = _setup()
    backend = UnifiedBackend(FAMILY, cfgs, samplers, local_epochs=1,
                             lr=0.05, momentum=0.9)
    strategy = FedADPStrategy(FAMILY, cfgs,
                              [s.n_samples for s in samplers])
    det = RetraceDetector()
    rounds_seen = []
    traces_after_r1 = {}

    def after_round(rec):
        rounds_seen.append(rec["round"])
        if len(rounds_seen) == 1:
            det.checkpoint()              # everything up to here may compile
            traces_after_r1.update(backend.engine.step_stats()["traces"])

    fed = Federation(strategy, backend, rounds=3, eval_batch=test,
                     eval_every=1, participation=participation,
                     callbacks=[after_round])
    with det:
        res = fed.run(jax.random.PRNGKey(0))

    assert len(res["history"]) == 3
    assert det.compiles > 0, "round 1 must have compiled the step"
    assert det.since_checkpoint == 0, (
        f"{pname}: {det.since_checkpoint} compile(s) AFTER round 1: "
        f"{det.events[det._mark:]}")
    # the per-size step cache stops growing after round 1 (round 1 may
    # hold >1 entry: the sampler's merged tail batch is a second shape)
    stats = backend.engine.step_stats()
    assert stats["traces"] == traces_after_r1, stats
    assert stats["cache_sizes"] == stats["traces"], (
        "jax compiled entries the wrapper never saw", stats)
    sizes = {2} if pname == "full" else {1}
    assert set(stats["subset_sizes"]) == sizes


def test_chunked_streaming_rounds_compile_nothing_after_round_one():
    """ISSUE 8: the streaming layout (``agg_layout="stream"`` with a
    pinned ``k_chunk``) trains and accumulates chunk-by-chunk — every
    chunk after round 1 must hit the SAME per-size jitted step and the
    SAME donated accumulate step. k_chunk=1 divides the K=2 subset, so
    each round runs 2 equal-size chunks; rounds >= 2 compile nothing."""
    cfgs, samplers, test = _setup()
    backend = UnifiedBackend(FAMILY, cfgs, samplers, local_epochs=1,
                             lr=0.05, momentum=0.9, agg_layout="stream",
                             k_chunk=1)
    strategy = FedADPStrategy(FAMILY, cfgs,
                              [s.n_samples for s in samplers])
    det = RetraceDetector()
    rounds_seen = []

    def after_round(rec):
        rounds_seen.append(rec["round"])
        if len(rounds_seen) == 1:
            det.checkpoint()

    fed = Federation(strategy, backend, rounds=3, eval_batch=test,
                     eval_every=1, callbacks=[after_round])
    with det:
        res = fed.run(jax.random.PRNGKey(0))

    assert len(res["history"]) == 3
    assert backend.engine.agg_stats()["layout"] == "stream"
    assert backend.engine.agg_stats()["k_chunk"] == 1
    assert det.compiles > 0, "round 1 must have compiled the step"
    assert det.since_checkpoint == 0, (
        f"{det.since_checkpoint} compile(s) AFTER round 1 on the "
        f"chunked path: {det.events[det._mark:]}")
    # chunking must not mint per-chunk step entries: every chunk is the
    # same size, so ONE subset-size bucket serves all of them
    assert set(backend.engine.step_stats()["subset_sizes"]) == {1}


def test_width_streaming_rounds_compile_nothing_after_round_one():
    """A width cohort (VGG-16-Wider beside VGG-13) starts each
    round with the compiled width round start — one program per client
    architecture, the round's To-Wider mappings passed in as data. Every
    round draws new mappings, yet rounds >= 2 compile nothing, and the
    round start's trace counter holds one entry per architecture and
    stops growing."""
    cfgs = [scaled(vgg(a), 0.125, 32) for a in ("vgg13", "vgg16-wider")]
    _, samplers, test = _setup()
    backend = UnifiedBackend(FAMILY, cfgs, samplers, local_epochs=1,
                             lr=0.05, momentum=0.9, agg_layout="stream",
                             k_chunk=1)
    strategy = FedADPStrategy(FAMILY, cfgs,
                              [s.n_samples for s in samplers])
    det = RetraceDetector()
    starts_after_r1 = {}

    def after_round(rec):
        if not starts_after_r1:
            det.checkpoint()
            starts_after_r1.update(
                backend.engine.step_stats()["round_start"]["traces"])

    fed = Federation(strategy, backend, rounds=3, eval_batch=test,
                     eval_every=1, callbacks=[after_round])
    with det:
        res = fed.run(jax.random.PRNGKey(0))

    assert len(res["history"]) == 3
    assert not backend.engine._depth_only
    assert det.compiles > 0, "round 1 must have compiled the step"
    assert det.since_checkpoint == 0, (
        f"{det.since_checkpoint} compile(s) AFTER round 1 on the width "
        f"round start: {det.events[det._mark:]}")
    stats = backend.engine.step_stats()["round_start"]
    assert stats["traces"] == starts_after_r1 == {0: 1, 1: 1}, stats
    # three rounds' training rows, plus the evaluation views' rows
    assert stats["rows"] >= 3 * len(cfgs)


def test_flash_bf16_rounds_compile_nothing_after_round_one():
    """ISSUE 10: the flash-attention training path (``attn_backend=
    "flash"``) plus mixed precision (``compute_dtype="bf16"``) ride the
    same per-size jitted step — the custom_vjp kernels, the bf16
    param/grad casts, and the bf16-dtype model config are all bound at
    trace time, so rounds >= 2 compile NOTHING new."""
    from repro.configs import get_config, reduced
    from repro.core import TransformerFamily, tfamily

    base = reduced(get_config("glm4-9b"), n_units=2, d_model=64)
    cfgs = [tfamily.make_variant(base, ffn_scale=0.5),
            tfamily.make_variant(base)]
    family = TransformerFamily()
    rng = np.random.default_rng(0)
    toks = rng.integers(0, base.vocab_size, size=(32, 17)).astype(np.int32)
    data = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    samplers = [ClientSampler(data, p, round_fraction=0.5, batch_size=8,
                              seed=i)
                for i, p in enumerate((np.arange(0, 16),
                                       np.arange(16, 32)))]
    test = {"tokens": toks[:8, :-1], "labels": toks[:8, 1:]}

    backend = UnifiedBackend(family, cfgs, samplers, local_epochs=1,
                             lr=0.05, momentum=0.9, compute_dtype="bf16",
                             attn_backend="flash")
    strategy = FedADPStrategy(family, cfgs,
                              [s.n_samples for s in samplers])
    det = RetraceDetector()
    rounds_seen = []

    def after_round(rec):
        rounds_seen.append(rec["round"])
        if len(rounds_seen) == 1:
            det.checkpoint()

    fed = Federation(strategy, backend, rounds=3, eval_batch=test,
                     eval_every=1, callbacks=[after_round])
    with det:
        res = fed.run(jax.random.PRNGKey(0))

    assert len(res["history"]) == 3
    assert det.compiles > 0, "round 1 must have compiled the step"
    assert det.since_checkpoint == 0, (
        f"{det.since_checkpoint} compile(s) AFTER round 1 on the "
        f"flash+bf16 path: {det.events[det._mark:]}")


def test_compressed_wire_rounds_compile_nothing_after_round_one():
    """ISSUE 9: the int8 wire path adds an encode jit (core.quant via
    ``engine._wire_encode``), a residual gather/scatter, and the fused
    dequantize-accumulate step. All of it must compile in round 1 only:
    the encode jit is keyed on static (fmt, tile), the residual ops are
    shape-stable, and the payload byte accounting is cached — so rounds
    >= 2 on the compressed path compile NOTHING and sync nothing new."""
    cfgs, samplers, test = _setup()
    backend = UnifiedBackend(FAMILY, cfgs, samplers, local_epochs=1,
                             lr=0.05, momentum=0.9, k_chunk=1,
                             wire="int8")
    strategy = FedADPStrategy(FAMILY, cfgs,
                              [s.n_samples for s in samplers])
    det = RetraceDetector()
    rounds_seen = []

    def after_round(rec):
        rounds_seen.append(rec["round"])
        if len(rounds_seen) == 1:
            det.checkpoint()

    fed = Federation(strategy, backend, rounds=3, eval_batch=test,
                     eval_every=1, callbacks=[after_round])
    with det:
        res = fed.run(jax.random.PRNGKey(0))

    assert len(res["history"]) == 3
    assert backend.wire_stats()["wire"] == "int8"
    assert backend.wire_stats()["bytes_per_round"] > 0
    assert det.compiles > 0, "round 1 must have compiled the step"
    assert det.since_checkpoint == 0, (
        f"{det.since_checkpoint} compile(s) AFTER round 1 on the "
        f"compressed wire path: {det.events[det._mark:]}")
