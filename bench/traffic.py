"""The one traffic generator: a mix's parameters + a seed -> the data and
every client's batches, round by round.

A mix (``bench/traffic/<name>.json``) states:

  n_train         images in the federation (split IID over the clients)
  round_fraction  share of a client's images it trains on each round
  batch_size      local batch; the last batch of a round takes the rest
  local_epochs    passes over the round's images
  class_signal    weight of the class prototype in an image (the rest is
                  uniform noise in [-1, 1])

Images are ``(image_size, image_size, in_channels)`` float32 from the
configuration. Everything is a pure function of ``(seed, client, round)``,
so the reference replays the exact batches the system trained on.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2 ** 64, *stream])


def derived_seed(seed: int, stream: int) -> int:
    """A 31-bit integer for consumers that take a small seed."""
    return int(np.random.SeedSequence([int(seed) % 2 ** 64, stream])
               .generate_state(1)[0]) & 0x7FFFFFFF


def make_data(mix: dict, cfg: dict, seed: int) -> Dict[str, np.ndarray]:
    """``{"x": (n, S, S, C) float32, "y": (n,) int32}`` from the seed."""
    n, nc = int(mix["n_train"]), int(cfg["n_classes"])
    s, c = int(cfg["image_size"]), int(cfg["in_channels"])
    rng = _rng(seed, 0)
    y = rng.integers(0, nc, n).astype(np.int32)
    proto = rng.uniform(-1.0, 1.0, (nc, s * s * c)).astype(np.float32)
    a = np.float32(mix["class_signal"])
    x = rng.random((n, s * s * c), dtype=np.float32)
    x *= 2.0 * (1.0 - a)
    x += a * proto[y] - (1.0 - a)
    return {"x": x.reshape(n, s, s, c), "y": y}


def partition(n: int, k: int, seed: int) -> List[np.ndarray]:
    """IID split of ``range(n)`` into ``k`` near-equal index sets."""
    idx = _rng(seed, 1).permutation(n)
    return [np.sort(p) for p in np.array_split(idx, k)]


def round_take(mix: dict, n_client: int) -> int:
    return min(n_client, max(1, int(round(n_client * mix["round_fraction"]))))


def batch_bounds(take: int, batch_size: int):
    return [(lo, min(lo + batch_size, take))
            for lo in range(0, take, batch_size)]


def client_round(mix: dict, indices: np.ndarray, seed: int, k: int,
                 round_idx: int) -> List[np.ndarray]:
    """Client ``k``'s batches of round ``round_idx`` as index arrays, in
    training order (every epoch a fresh order of the same draw)."""
    rng = _rng(seed, 2, k, round_idx)
    take = round_take(mix, len(indices))
    sel = rng.choice(indices, size=take, replace=False)
    out = []
    for _ in range(int(mix["local_epochs"])):
        order = sel[rng.permutation(take)]
        out += [order[lo:hi] for lo, hi in
                batch_bounds(take, int(mix["batch_size"]))]
    return out


def steps_per_round(mix: dict, n_client: int) -> int:
    take = round_take(mix, n_client)
    return int(mix["local_epochs"]) * len(batch_bounds(take,
                                                       int(mix["batch_size"])))


class Sampler:
    """One client's batch stream in the shape the system's backends read
    (``round_batches(epochs)`` yielding ``{"x", "y"}`` numpy batches,
    ``n_samples``, ``batch_size``, ``round_fraction``). Each call draws
    the next round."""

    def __init__(self, data, indices, mix: dict, seed: int, k: int):
        self.data, self.indices, self.mix = data, np.asarray(indices), mix
        self.seed, self.k = seed, k
        self.batch_size = int(mix["batch_size"])
        self.round_fraction = float(mix["round_fraction"])
        self.round = 0

    @property
    def n_samples(self) -> int:
        return len(self.indices)

    def round_batches(self, epochs: int):
        if epochs != int(self.mix["local_epochs"]):
            raise ValueError(f"the mix trains {self.mix['local_epochs']} "
                             f"local epochs, the system asked for {epochs}")
        idx = client_round(self.mix, self.indices, self.seed, self.k,
                           self.round)
        self.round += 1
        for b in idx:
            yield {"x": self.data["x"][b], "y": self.data["y"][b]}


def padded_round(data, mix: dict, indices, seed: int, k: int,
                 round_idx: int):
    """Client ``k``'s round as fixed-shape stacks ``(xs, ys, valid)`` of
    ``(steps, batch_size, ...)``: a short last batch is padded with
    zero rows whose ``valid`` is 0 (the reference's mean skips them)."""
    bsz = int(mix["batch_size"])
    batches = client_round(mix, indices, seed, k, round_idx)
    s = len(batches)
    xs = np.zeros((s, bsz) + data["x"].shape[1:], np.float32)
    ys = np.zeros((s, bsz), np.int32)
    vs = np.zeros((s, bsz), np.float32)
    for i, b in enumerate(batches):
        xs[i, :len(b)] = data["x"][b]
        ys[i, :len(b)] = data["y"][b]
        vs[i, :len(b)] = 1.0
    return xs, ys, vs
