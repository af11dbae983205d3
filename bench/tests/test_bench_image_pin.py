"""The image traffic, pinned byte for byte: ``make_data``, ``padded_round``
for two clients and two rounds, and one round of ``Sampler`` batches,
hashed for the tiny mix and the committed mixes' parameters at
``n_train`` 2,000, two seeds each. The digests are what the generator
gave before the data became generic across kinds; a change to the image
path's draws, their order, dtypes or shapes fails here."""
import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(Path(__file__).resolve().parent)]

import tiny  # noqa: E402
import traffic  # noqa: E402

CFG = {"image_size": 32, "in_channels": 3, "n_classes": 10}
BIG = 2 ** 31 + 11
SEEDS = (7, BIG)
CLIENTS = 4


def _mix(name):
    if name == "tiny":
        return dict(tiny.MIX)
    mix = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    return dict(mix, n_train=2000)


def digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


def _data(mix, seed):
    data = traffic.make_data(mix, CFG, seed)
    return data, traffic.partition(len(data["y"]), CLIENTS, seed)


def _make_data(mix, seed):
    data, _ = _data(mix, seed)
    return digest(data[k] for k in sorted(data))


def _padded(mix, seed):
    data, parts = _data(mix, seed)
    return digest(a for k in (0, 1) for r in (0, 1)
                  for a in traffic.padded_round(data, mix, parts[k], seed, k,
                                                r))


def _sampler(mix, seed):
    data, parts = _data(mix, seed)
    out = []
    for k in (0, 1):
        s = traffic.Sampler(data, parts[k], mix, seed, k)
        for b in s.round_batches(int(mix["local_epochs"])):
            out += [b[key] for key in sorted(b)]
    return digest(out)


WHAT = {"make_data": _make_data, "padded_round": _padded,
        "sampler": _sampler}

PINNED = {
    ("tiny", 7, "make_data"): "b3f3ad59fe5085cc",
    ("tiny", 7, "padded_round"): "ebe27df85c3d518d",
    ("tiny", 7, "sampler"): "b9caa174b1910426",
    ("tiny", BIG, "make_data"): "fe7b78d1305a282c",
    ("tiny", BIG, "padded_round"): "378ad4df4a1d6480",
    ("tiny", BIG, "sampler"): "39517a91c59572bb",
    ("cifar_round", 7, "make_data"): "948c58414726fa62",
    ("cifar_round", 7, "padded_round"): "47e47c211a82125e",
    ("cifar_round", 7, "sampler"): "3f9fc3bd909bc54e",
    ("cifar_round", BIG, "make_data"): "38c1a5c3ac6f4525",
    ("cifar_round", BIG, "padded_round"): "cb10ab100faa72b3",
    ("cifar_round", BIG, "sampler"): "75b7efdca206d2be",
    ("one_step", 7, "make_data"): "948c58414726fa62",
    ("one_step", 7, "padded_round"): "4c0f2cea47ee3fbe",
    ("one_step", 7, "sampler"): "32b42ed2f9dc62ec",
    ("one_step", BIG, "make_data"): "38c1a5c3ac6f4525",
    ("one_step", BIG, "padded_round"): "4e26a1445dae7acd",
    ("one_step", BIG, "sampler"): "f7d5bf99532ddf26",
}


@pytest.mark.parametrize("what", sorted(WHAT))
@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("mix", ["tiny", "cifar_round", "one_step"])
def test_image_traffic_pinned(mix, seed, what):
    assert WHAT[what](_mix(mix), seed) == PINNED[(mix, seed, what)]
