"""The yardstick's counts against hand counts: VGG training FLOPs, the
aggregation's least bytes, the peaks table."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import roofline  # noqa: E402
from registry import load_json, load_module  # noqa: E402

vgg = load_module(BENCH / "families" / "vgg.py", "bench_families_vgg")
PAPER = load_json(BENCH / "configs" / "vgg-paper20.json")


def arch(name):
    return {"name": name, "stages": PAPER["archs"][name],
            "classifier": [4096, 4096], "n_classes": 10, "in_channels": 3,
            "image_size": 32}


def hand_macs(stages):
    """Conv MACs stage by stage at 32, 16, 8, 4, 2 pixels, then the
    classifier 512 -> 4096 -> 4096 -> 10 (one pixel left after 5 pools)."""
    macs, cin = [], 3
    for hw, ws in zip((32, 16, 8, 4, 2), stages):
        for w in ws:
            macs.append(hw * hw * 9 * cin * w)
            cin = w
    return macs + [512 * 4096, 4096 * 4096, 4096 * 10]


def test_vgg13_flops_by_hand():
    # VGG-13: 2 convs in each of 5 stages
    m = [32 * 32 * 9 * 3 * 64, 32 * 32 * 9 * 64 * 64,
         16 * 16 * 9 * 64 * 128, 16 * 16 * 9 * 128 * 128,
         8 * 8 * 9 * 128 * 256, 8 * 8 * 9 * 256 * 256,
         4 * 4 * 9 * 256 * 512, 4 * 4 * 9 * 512 * 512,
         2 * 2 * 9 * 512 * 512, 2 * 2 * 9 * 512 * 512,
         512 * 4096, 4096 * 4096, 4096 * 10]
    assert vgg.layer_macs(arch("vgg13")) == m
    assert sum(m) == 247_177_216
    assert vgg.train_flops_per_sample(arch("vgg13")) == \
        6 * 247_177_216 - 2 * 1_769_472


def test_vgg19_wider_flops_by_hand():
    # VGG-19-Wider: stage 4 opens with 768 channels, then three of 512
    st = PAPER["archs"]["vgg19-wider"]
    assert st[3] == [768, 512, 512, 512]
    m = hand_macs(st)
    assert m[8] == 4 * 4 * 9 * 256 * 768 and m[9] == 4 * 4 * 9 * 768 * 512
    assert vgg.layer_macs(arch("vgg19-wider")) == m
    assert sum(m) == 445_358_080
    assert vgg.train_flops_per_sample(arch("vgg19-wider")) == \
        6 * 445_358_080 - 2 * 1_769_472


def test_agg_bytes_by_hand():
    n = 40_717_642
    # an 8-row chunk: 8 rows + 8 weights read, 3 buffers read and written
    eight = 8 * n * 4 + 8 * 4 + 6 * n * 4
    four = 4 * n * 4 + 4 * 4 + 6 * n * 4
    finish = 4 * n * 4
    assert roofline.agg_bytes([8], n) == eight + finish
    assert roofline.agg_bytes([4], n) == four + finish
    assert roofline.stream_chunks(20, 8) == [8, 8, 4]
    assert roofline.agg_bytes([8, 8, 4], n) == 2 * eight + four + finish


def test_peaks_table():
    p = roofline.peaks("TPU v5 lite")
    assert (p["bf16_flops"], p["hbm_bytes_per_s"]) == (197e12, 819e9)
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
