"""The span reduction (``bench/spans.py``): time, self time and summed
counts of the program's ``fedadp.*`` spans inside the window the
benchmark's round spans mark, the device idle time under each span, the
five readers built on it, and a whole traced run of a tiny cell on the
CPU whose ``h2d_mb`` is the hand count of its batches, and one round
recorded on the chip."""
import gzip
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(Path(__file__).resolve().parent)]

import harness  # noqa: E402
import roofline  # noqa: E402
import spans  # noqa: E402
import tiny  # noqa: E402
from registry import load_module  # noqa: E402


def ms(x):
    return x * 1e6


def synthetic():
    """Two rounds 0-10 and 10-16 ms. The program's spans, one thread:
    round 0-9 holds batches 0-2, round_start 2-4 and train 4-8 with two
    steps 4-5 and 6-7; a second round 10-18 runs past the window with a
    step 11-12. Device ops 0-1, 3-6 and 13-14; the runtime relayouts
    input 5-8, 7-9 and 15-20."""
    t = "/host:CPU#0"
    sp = [("fedadp.round", 0, 9, {"round": 3, "clients": 2}),
          ("fedadp.batches", 0, 2, {"steps": 2, "bytes": 100}),
          ("fedadp.round_start", 2, 4, {"rows": 2, "path": "width"}),
          ("fedadp.train", 4, 8, {"rows": 2, "steps": 2}),
          ("fedadp.step", 4, 5, {"bytes": 40}),
          ("fedadp.step", 6, 7, {"bytes": 60}),
          ("fedadp.round", 10, 18, {"round": 4, "clients": 2}),
          ("fedadp.step", 11, 12, {"bytes": 60}),
          ("fedadp.step", 30, 31, {"bytes": 999})]       # after the window
    return {"rounds": [(0.0, ms(10)), (ms(10), ms(16))],
            "spans": [(n, ms(s), ms(e), st, t) for n, s, e, st in sp],
            "devices": {"/device:TPU:0": [(0.0, ms(1)), (ms(3), ms(6)),
                                          (ms(13), ms(14))]},
            "relayout": [(ms(5), ms(8)), (ms(7), ms(9)), (ms(15), ms(20))]}


def test_reduce_synthetic():
    r = spans.reduce_events(synthetic())
    assert r["rounds"] == 2 and r["devices"] == 1
    assert r["window_s"] == pytest.approx(16e-3)
    s = r["spans"]
    # the second round is clipped to the window's end, the late step gone
    assert s["fedadp.round"]["count"] == 2
    assert s["fedadp.round"]["total_s"] == pytest.approx(15e-3)
    assert s["fedadp.step"]["count"] == 3
    assert s["fedadp.step"]["stats"] == {"bytes": 160}
    assert s["fedadp.round_start"]["stats"] == {"rows": 2}   # "path" dropped
    # self time: round 9 - (2 + 2 + 4) = 1 ms, plus 6 - 1 ms of round two
    assert s["fedadp.round"]["self_s"] == pytest.approx(6e-3)
    assert s["fedadp.train"]["self_s"] == pytest.approx(2e-3)
    assert s["fedadp.batches"]["self_s"] == pytest.approx(2e-3)
    # idle 1-3, 6-13 and 14-16 ms (11 ms) under the innermost span:
    # batches 1-2, round_start 2-3, train 7-8, step 6-7 and 11-12,
    # round 8-9, 10-11 and 12-13, 14-16; nothing 9-10
    idle = {n: d["idle_s"] * 1e3 for n, d in s.items()}
    assert idle == pytest.approx({
        "fedadp.round": 5.0, "fedadp.batches": 1.0,
        "fedadp.round_start": 1.0, "fedadp.train": 1.0,
        "fedadp.step": 2.0})
    assert r["idle_s"] == pytest.approx(11e-3)
    assert r["idle_outside_s"] == pytest.approx(1e-3)
    assert r["idle_relayout_s"] == pytest.approx(4e-3)     # 6-9, 15-16
    summ = spans.summary(r)
    assert summ["idle_below_round_share"] == pytest.approx(5 / 11)
    assert summ["spans"]["fedadp.step"]["stats_per_round"] == {"bytes": 80}


def test_no_round_span_or_no_device():
    t = synthetic()
    t["rounds"] = []
    assert spans.reduce_events(t) is None
    t = synthetic()
    t["devices"] = {}
    r = spans.reduce_events(t)
    assert r["devices"] == 0 and r["idle_s"] is None
    assert r["idle_relayout_s"] is None
    assert r["spans"]["fedadp.step"]["stats"] == {"bytes": 160}


def _reader(name):
    return load_module(BENCH / "metrics" / f"{name}.py", f"m_{name}").read


def test_readers_per_traced_round():
    ctx = {"spans": spans.reduce_events(synthetic())}
    assert _reader("batch_ms")(ctx) == pytest.approx(1.0)
    assert _reader("round_start_ms")(ctx) == pytest.approx(1.0)
    assert _reader("dispatch_ms")(ctx) == pytest.approx(1.5)
    assert _reader("h2d_mb")(ctx) == pytest.approx(80e-6)
    mods = {"jit_train_step(17311479620831735169)": 0.004,
            "jit_fn(9)": 0.5, "jit__accum_step(1)": 0.001}
    trace = {"rounds": 2, "devices": 1, "modules": mods}
    assert _reader("train_device_ms")({"trace": trace}) == pytest.approx(2.0)


def test_readers_read_nothing_without_program_spans():
    """What the parent program gives: no ``fedadp.*`` span, its step
    program under a hash name."""
    t = synthetic()
    t["spans"] = []
    ctx = {"spans": spans.reduce_events(t)}
    for name in ("batch_ms", "round_start_ms", "dispatch_ms", "h2d_mb"):
        assert _reader(name)(ctx) is None
        assert _reader(name)({}) is None
    trace = {"rounds": 2, "devices": 1, "modules": {"jit_fn(9)": 0.5}}
    assert _reader("train_device_ms")({"trace": trace}) is None
    assert _reader("train_device_ms")({}) is None


def test_traced_tiny_run_counts_its_batches(tmp_path, monkeypatch):
    # the traced run's context carries the chip's peaks; the CPU has none
    # in the table, and no share of them is read here
    monkeypatch.setitem(roofline.PEAKS, "cpu", roofline.PEAKS["TPU v5 lite"])
    root = tiny.make_root(tmp_path)
    cell = "tiny-mixed.tiny"
    res = harness.run_cell(cell, 2 ** 31 + 9, 0.5, True, root=root,
                           require_tpu=False,
                           out_dir=str(root / "bench" / "out" / cell))
    assert res["correct"], res["checks"]
    m = res["metrics"]
    # 4 clients x 16 images a round x 2 epochs, each a 32x32x3 f32 image
    # and an int32 label
    hand = 4 * 16 * 2 * (32 * 32 * 3 * 4 + 4) / 1e6
    assert m["h2d_mb"]["value"] == pytest.approx(hand, rel=1e-12)
    for name in ("batch_ms", "round_start_ms", "dispatch_ms"):
        assert m[name]["value"] > 0 and m[name]["unit"] == "ms"
    assert "train_device_ms" not in m          # no device trace on the CPU


RECORDED = BENCH / "tests" / "data" / "vgg-depth20.one_step.events.json.gz"


def test_recorded_chip_round():
    """One traced round of ``vgg-depth20.one_step`` on a TPU v5e
    (``bench/tests/record_trace.py``, seed 1), kept as the events
    ``spans.load`` returns (the ``.xplane.pb`` is 9 MB gzipped), the
    relayout intervals merged."""
    with gzip.open(RECORDED, "rt") as f:
        r = spans.reduce_events(json.load(f))
    s = r["spans"]
    assert r["rounds"] == 1 and r["devices"] == 1
    assert s["fedadp.round"]["count"] == 1
    assert s["fedadp.round"]["stats"] == {"round": 3, "clients": 20}
    # 20 clients x 64 images, each a 32x32x3 f32 image and an int32 label
    assert s["fedadp.batches"]["stats"] == {"steps": 1, "bytes": 15_733_760}
    assert s["fedadp.step"]["count"] == 3                 # chunks 8, 8, 4
    assert s["fedadp.step"]["stats"] == {"bytes": 15_733_760}
    assert [s[n]["count"] for n in ("fedadp.round_start", "fedadp.train",
                                    "fedadp.aggregate")] == [3, 3, 4]
    # the program's spans cover the device's idle time; most of it passes
    # while the runtime relayouts the steps' input
    assert r["idle_outside_s"] < 0.01 * r["idle_s"]
    assert sum(d["idle_s"] for d in s.values()) == pytest.approx(
        r["idle_s"] - r["idle_outside_s"])
    assert r["idle_relayout_s"] > 0.5 * r["idle_s"]
