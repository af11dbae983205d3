"""The trace reduction: busy time as the union of device-op intervals
inside the window the benchmark's round spans mark, per-op and
per-program device time, and idle gaps named by the innermost host
event that covers half of them."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import tracing  # noqa: E402
from registry import load_module  # noqa: E402


def ev(name, start_ms, dur_ms):
    return (name, start_ms * 1e6, dur_ms * 1e6)


def synthetic():
    ops = [ev("fusion.1", 0, 4), ev("conv.2", 2, 4),      # overlap: 0-6
           ev("custom-call.3", 8, 1), ev("fusion.1", 12, 2),
           ev("outside", 30, 5)]                        # after the window
    mods = [ev("jit__accum_step(1)", 8, 1), ev("jit_step(2)", 0, 6)]
    host = [ev(tracing.SPAN, 0, 10), ev(tracing.SPAN, 10, 6),
            ev("stack_batches", 6, 2), ev("round_start", 9, 3)]
    return {"devices": {"/device:TPU:0": {tracing.OPS_LINE: ops,
                                         tracing.MODULES_LINE: mods}},
            "host": host}


def test_reduce_synthetic():
    r = tracing.reduce_events(synthetic())
    assert r["window_s"] == pytest.approx(16e-3)
    assert r["busy_s"] == pytest.approx(9e-3)          # 6 + 1 + 2 ms
    assert r["rounds"] == 2 and r["devices"] == 1
    assert r["ops"]["fusion.1"] == pytest.approx(6e-3)
    assert "outside" not in r["ops"]
    assert r["modules"]["jit__accum_step(1)"] == pytest.approx(1e-3)
    # idle: 6-8 (under stack_batches), 9-12 (under round_start) and
    # 14-16 (only the round span), longest first
    assert [[n, round(t * 1e3, 9)] for n, t in r["idle_gaps"]] == [
        ["round_start", 3.0], ["stack_batches", 2.0], ["unattributed", 2.0]]


def test_no_span_or_no_device_reads_nothing():
    t = synthetic()
    t["host"] = [e for e in t["host"] if e[0] != tracing.SPAN]
    assert tracing.reduce_events(t) is None
    t = synthetic()
    t["devices"] = {}
    assert tracing.reduce_events(t) is None


def test_readers_on_a_reduced_trace():
    r = tracing.reduce_events(synthetic())
    idle = load_module(BENCH / "metrics" / "device_idle_share.py", "m_idle")
    agg = load_module(BENCH / "metrics" / "agg_ms.py", "m_agg")
    roof = load_module(BENCH / "metrics" / "agg_roofline.py", "m_roof")
    assert idle.read({"trace": r}) == pytest.approx(100 * (1 - 9 / 16))
    assert agg.read({"trace": r}) == pytest.approx(0.5)   # 1 ms / 2 rounds
    ctx = {"trace": r, "agg_bytes_per_round": 819e9 * 0.25e-3,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    assert roof.read(ctx) == pytest.approx(50.0)
    assert idle.read({}) is None and agg.read({}) is None
