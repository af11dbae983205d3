"""A run of the harness with the timed path broken underneath must come
out not correct: once per fault a one-chip training cell can have, at
the tiny cell's limits and at the committed cells' loosest."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(Path(__file__).resolve().parent)]

import calibrate  # noqa: E402
import harness  # noqa: E402
import tiny  # noqa: E402
from check import verdict  # noqa: E402


@pytest.mark.parametrize("fault", sorted(calibrate.FAULTS))
def test_fault_is_not_correct(tmp_path, fault):
    root = tiny.make_root(tmp_path)
    res = harness.run_cell("tiny-depth.tiny", 11, 0.2, False, root=root,
                           require_tpu=False, patch=calibrate.FAULTS[fault])
    assert not res["correct"], res["checks"]
    assert not verdict(res["checks"], tiny.committed_limits()), res["checks"]
