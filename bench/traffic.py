"""The one traffic generator: a mix's parameters + a seed -> the data and
every client's batches, round by round.

A mix (``bench/traffic/<name>.json``) names its kind of data with
``"data"``: ``"images"`` (the default when the key is absent) or
``"tokens"``. Every kind reads:

  n_train         rows in the federation (images or sequences), split
                  IID over the clients
  round_fraction  share of a client's rows it trains on each round
  batch_size      local batch; the last batch of a round takes the rest
  local_epochs    passes over the round's rows
  probe           rows the loss comparison scores (default
                  ``harness.PROBE``, 256)

``"images"`` adds

  class_signal    weight of the class prototype in an image (the rest is
                  uniform noise in [-1, 1])

and takes ``image_size``, ``in_channels`` and ``n_classes`` from the
client's dict: images are ``(image_size, image_size, in_channels)``
float32, labels int32.

``"tokens"`` adds (documents, packed into sequences; ``token_data``)

  seq_len         tokens a sequence trains on
  doc_len_median  median document length in tokens, its end id included
  doc_len_sigma   sigma of the lognormal document length (log scale)
  n_topics        topics a document draws one of
  topic_vocab     ids each topic owns, drawn once from the seed
  signal          chance that a document's token is one of its topic's
                  ids (otherwise uniform over the vocabulary)

and takes ``vocab_size`` from the client's dict.

Data is a dict of arrays of equal length, keyed by the system's batch
keys; ``KEYS`` names each kind's input key and target key, and the
harness and the reference address the arrays by position: input,
target and, in ``padded_round``, ``valid``. Everything is a pure
function of ``(mix, cfg, seed)`` and ``(client, round)``, so the
reference replays the exact batches the system trained on.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

# each kind's (input key, target key): the batch keys its models read
KEYS = {"images": ("x", "y"), "tokens": ("tokens", "labels")}
EOD = 0               # the end-of-document id of token data
BLOCK = 1 << 22       # tokens drawn per block (fixes the random streams)


def kind(mix: dict) -> str:
    k = mix.get("data", "images")
    if k not in KEYS:
        raise ValueError(f"mix data {k!r}: the generator makes "
                         f"{sorted(KEYS)}")
    return k


def keys(mix: dict) -> Tuple[str, str]:
    """The mix's ``(input key, target key)``."""
    return KEYS[kind(mix)]


def n_rows(data: Dict[str, np.ndarray]) -> int:
    """Rows of the data: the arrays' common leading axis."""
    return len(next(iter(data.values())))


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2 ** 64, *stream])


def derived_seed(seed: int, stream: int) -> int:
    """A 31-bit integer for consumers that take a small seed."""
    return int(np.random.SeedSequence([int(seed) % 2 ** 64, stream])
               .generate_state(1)[0]) & 0x7FFFFFFF


def make_data(mix: dict, cfg: dict, seed: int) -> Dict[str, np.ndarray]:
    """The mix's data from the seed (``cfg``: a client's dict)."""
    return {"images": image_data, "tokens": token_data}[kind(mix)](
        mix, cfg, seed)


def image_data(mix: dict, cfg: dict, seed: int) -> Dict[str, np.ndarray]:
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


def doc_lengths(mix: dict, seed: int, total: int) -> np.ndarray:
    """Document lengths (int64, each at least 1) until they cover
    ``total`` tokens: lognormal around ``doc_len_median`` with
    ``doc_len_sigma``, rounded. The last document is cut by the end."""
    rng = _rng(seed, 6)
    mu, sigma = np.log(float(mix["doc_len_median"])), float(
        mix["doc_len_sigma"])
    per = max(1024, int(total / np.exp(mu + sigma ** 2 / 2)) + 1)
    parts, have = [], 0
    while have < total:
        lens = np.maximum(1, np.rint(rng.lognormal(mu, sigma, per))
                          ).astype(np.int64)
        parts.append(lens)
        have += int(lens.sum())
    lens = np.concatenate(parts)
    return lens[:int(np.searchsorted(np.cumsum(lens), total)) + 1]


def token_data(mix: dict, cfg: dict, seed: int) -> Dict[str, np.ndarray]:
    """``{"tokens": (n, seq_len), "labels": (n, seq_len)}`` int32 from
    the seed: documents packed back to back into ``n_train`` rows of
    ``seq_len + 1`` ids; ``tokens`` is ``rows[:, :-1]`` and ``labels``
    ``rows[:, 1:]``, two views of one array.

    A document of ``L`` tokens (``doc_lengths``) is ``L - 1`` content
    ids and one end-of-document id ``EOD`` (0). It draws one of
    ``n_topics`` topics; each content id is, with chance ``signal``, one
    of that topic's ``topic_vocab`` ids, else uniform, both over
    ``[1, vocab_size)`` (``EOD`` marks document ends alone), so the loss
    falls as a model learns the topics. Documents run across row
    boundaries, and the system and any reference attend across document
    ends alike: the program has no document mask."""
    n, s = int(mix["n_train"]), int(mix["seq_len"])
    vocab, signal = int(cfg["vocab_size"]), float(mix["signal"])
    n_topics, topic_vocab = int(mix["n_topics"]), int(mix["topic_vocab"])
    if vocab < 2:
        raise ValueError(f"vocab_size {vocab}: token data needs an end id "
                         "and at least one other")
    total = n * (s + 1)
    ends = np.cumsum(doc_lengths(mix, seed, total))
    rng = _rng(seed, 7)
    row0 = rng.integers(0, n_topics, len(ends)) * topic_vocab
    table = rng.integers(1, vocab, n_topics * topic_vocab, dtype=np.int32)
    flat = np.empty(total, np.int32)
    for b, lo in enumerate(range(0, total, BLOCK)):
        hi = min(lo + BLOCK, total)
        r = _rng(seed, 8, b)
        ids = r.integers(1, vocab, hi - lo, dtype=np.int32)
        on = r.random(hi - lo, dtype=np.float32) < signal
        pick = r.integers(0, topic_vocab, hi - lo)
        # documents d0..d1 meet the block; ends[d0:d1] fall inside it
        d0, d1 = np.searchsorted(ends, [lo, hi - 1], side="right")
        segs = np.diff(np.concatenate(([lo], ends[d0:d1], [hi])))
        ids = np.where(on, table[np.repeat(row0[d0:d1 + 1], segs) + pick],
                       ids)
        last = ends[d0:d1 + 1] - 1
        ids[last[last < hi] - lo] = EOD
        flat[lo:hi] = ids
    rows = flat.reshape(n, s + 1)
    return {"tokens": rows[:, :-1], "labels": rows[:, 1:]}


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
    (``round_batches(epochs)`` yielding a numpy batch of every key of the
    data, ``n_samples``, ``batch_size``, ``round_fraction``). Each call
    draws the next round."""

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
            yield {key: a[b] for key, a in self.data.items()}


def padded_round(data, mix: dict, indices, seed: int, k: int,
                 round_idx: int):
    """Client ``k``'s round as fixed-shape stacks ``(inputs, targets,
    valid)``: each array ``(steps, batch_size, ...)`` with its own
    trailing shape and dtype, ``valid`` ``(steps, batch_size)`` float32.
    A short last batch is padded with zero rows whose ``valid`` is 0
    (the reference's mean skips them)."""
    bsz = int(mix["batch_size"])
    batches = client_round(mix, indices, seed, k, round_idx)
    arrays = [data[key] for key in keys(mix)]
    out = [np.zeros((len(batches), bsz) + a.shape[1:], a.dtype)
           for a in arrays]
    valid = np.zeros((len(batches), bsz), np.float32)
    for i, b in enumerate(batches):
        for o, a in zip(out, arrays):
            o[i, :len(b)] = a[b]
        valid[i, :len(b)] = 1.0
    return (*out, valid)
