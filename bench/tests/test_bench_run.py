"""A whole run of the harness at a tiny size on the CPU, past its look
for a chip: the system agrees with the plain reference on a cohort that
mixes depth and width, and the result line has every key the
benchmark's contract asks for."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(Path(__file__).resolve().parent)]

import harness  # noqa: E402
import tiny  # noqa: E402


def test_sound_run_is_correct(tmp_path):
    root = tiny.make_root(tmp_path)
    res = harness.run_cell("tiny-mixed.tiny", 2 ** 31 + 5, 0.5, False,
                           root=root, require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > harness.CHECKED_ROUNDS
    assert set(res["metrics"]) == {"round_s", "peak_hbm_gb", "setup_s"}
    assert all(v["value"] <= v["limit"] for v in res["checks"].values())
    assert list(harness.order(res))[-1] == "checks"
    assert res["device"]["platform"] == "cpu"
