"""The controls: the plain reference put in the system's place with its
local training computed in bfloat16 (float32 weights and momentum), or
with its parameters and momentum kept in bfloat16 too, must fail the
output check that the system passes, at the tiny cells' limits and at
the committed cells' loosest."""
import sys
from pathlib import Path

import jax.numpy as jnp

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(Path(__file__).resolve().parent)]

import harness  # noqa: E402
import tiny  # noqa: E402
import traffic  # noqa: E402
from check import verdict  # noqa: E402
from registry import load_cell  # noqa: E402


def test_bf16_control_is_not_correct(tmp_path):
    root = tiny.make_root(tmp_path)
    cell = load_cell("tiny-depth.tiny", root)
    seed = 3
    clients = cell.family().client_dicts(cell.config)
    data = traffic.make_data(cell.mix, clients[0], seed)
    parts = traffic.partition(len(data["y"]), len(clients), seed)
    ref = harness.reference_models(cell, seed, data, parts)
    ctl = harness.reference_models(cell, seed, data, parts,
                                   dtype=jnp.bfloat16)
    nums = harness.check(cell, seed, data, parts, ctl[1:], ref)
    assert not verdict(nums, cell.limits), nums
    assert not verdict(nums, tiny.committed_limits()), nums
    same = harness.check(cell, seed, data, parts, ref[1:], ref)
    assert verdict(same, cell.limits)
    assert all(v["value"] == 0.0 for v in same.values())


def test_bf16_store_control_is_not_correct(tmp_path):
    """The same with parameters and momentum kept in bfloat16 too."""
    root = tiny.make_root(tmp_path)
    cell = load_cell("tiny-mixed.tiny", root)
    seed = 4
    clients = cell.family().client_dicts(cell.config)
    data = traffic.make_data(cell.mix, clients[0], seed)
    parts = traffic.partition(len(data["y"]), len(clients), seed)
    ref = harness.reference_models(cell, seed, data, parts)
    ctl = harness.reference_models(cell, seed, data, parts,
                                   dtype=jnp.bfloat16, store=jnp.bfloat16)
    nums = harness.check(cell, seed, data, parts, ctl[1:], ref)
    assert not verdict(nums, cell.limits), nums
    assert not verdict(nums, tiny.committed_limits()), nums
