"""NetChange — FedADP's structure-transformation primitives (paper §III.B).

Four transforms move a model between architectures of the same family:

  To-Wider   (Alg. 2)  new neurons duplicate randomly-chosen existing ones;
                       each duplicate group's OUTGOING weights are divided
                       by the group size  => function preserving (Net2Net).
  To-Deeper            insert missing layers initialized to identity
                       (diagonal 1 / zero elsewhere for plain stacks;
                       zero-output-projection for pre-norm residual blocks).
  To-Narrower (Alg. 3) delete neurons beyond N_tar; the summed outgoing
                       weights of deleted neurons are redistributed evenly
                       (s / N_tar added to each survivor)  => lossy.
  To-Shallower         drop the layers the target doesn't have.

Interpretation notes (recorded for faithfulness):
  * Alg. 2's "value v_i" division is applied to outgoing weights — the
    Net2Net semantics the paper extends and whose function preservation
    the paper asserts ("the output of the expanded layer remains
    unchanged").
  * Alg. 3's redistribution is applied to outgoing weight rows ("their
    associated weights are evenly redistributed among the remaining
    neurons"); incoming columns of deleted neurons are removed.

Beyond paper: ``narrow_fold`` — the exact inverse of To-Wider given the
expansion mapping (mean incoming copies, sum outgoing splits). Function
preserving when duplicate groups stayed identical; compared against
Alg. 3 in ablations (EXPERIMENTS.md).

Mappings are deterministic in (tag, old_width, new_width, seed) so the
server and clients derive identical expansions without communication.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


# ----------------------------------------------------------------- mappings

NARROW_MODES = ("paper", "fold")


def round_embed_seed(base_seed: int, round_idx: int, k: int) -> int:
    """The per-(round, client) NetChange seed — ONE formula shared by the
    per-client loop (``FedADP._seed``) and the unified engine, so both
    paths draw identical To-Wider duplication mappings. The distribute
    fold and collect widen of a round are mutual inverses because they
    share this seed."""
    return (base_seed * 1_000_003 + round_idx * 997 + k) % (2 ** 31)


class KeyedCache:
    """Bounded get-or-build LRU for seed-keyed embedding artifacts
    (coverage masks, segment matrices, packed coverage/multiplicity
    rows): per-round seeds are unbounded over a run's lifetime, so the
    maps must evict. ONE cache class, one sizing knob — ``max(128,
    4·n_clients)`` entries by default, so one round of a big cohort
    never evicts itself — shared by ``FedADP`` and ``UnifiedEngine``
    (keys are namespaced tuples, e.g. ``("cov", k, seed)``), so the
    loop and engine seed caches cannot diverge. ``stats()`` exposes
    hit/miss/size/bound counters for tests and ops dashboards."""

    def __init__(self, *, n_clients: int = 0, bound: Optional[int] = None):
        self.bound = bound if bound is not None else max(128, 4 * n_clients)
        self._d: "OrderedDict" = OrderedDict()
        self.hits = 0
        self.misses = 0

    def get(self, key, build):
        if key in self._d:
            self.hits += 1
            self._d.move_to_end(key)
            return self._d[key]
        self.misses += 1
        val = self._d[key] = build()
        while len(self._d) > self.bound:
            self._d.popitem(last=False)
        return val

    def stats(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "size": len(self._d), "bound": self.bound}

    def __len__(self) -> int:
        return len(self._d)

    def __contains__(self, key) -> bool:
        return key in self._d


def dup_mapping(old: int, new: int, *, tag: str = "", seed: int = 0) -> np.ndarray:
    """Mapping m: [new] -> [old]. First ``old`` slots are the identity; the
    remaining ``new - old`` duplicate sources are chosen uniformly (Alg. 2
    line 6, "randomly select neuron j") from a deterministic stream."""
    assert new >= old > 0, (old, new)
    h = int.from_bytes(hashlib.sha256(f"{tag}:{old}:{new}:{seed}".encode())
                       .digest()[:8], "big")
    rng = np.random.default_rng(h)
    extra = rng.integers(0, old, size=new - old)
    return np.concatenate([np.arange(old), extra]).astype(np.int32)


def mapping_counts(mapping, old: int) -> jnp.ndarray:
    """Duplicate-group sizes of a mapping, as int32 per old index; the
    mapping may be a traced array (shapes depend on ``old`` alone)."""
    return jnp.bincount(jnp.asarray(mapping), length=old).astype(jnp.int32)


def head_to_unit_mapping(head_map: np.ndarray, unit: int) -> np.ndarray:
    """Lift a mapping over groups (heads/experts) to element granularity."""
    return (head_map[:, None] * unit + np.arange(unit)[None, :]).reshape(-1)


# -------------------------------------------------------------- To-Wider
# The width primitives take the mapping as numpy (``dup_mapping``) or as
# a traced int32 array, so one compiled program serves every seed.

def widen_in(w, mapping, axis: int = -1):
    """Incoming weights: duplicate columns per ``mapping`` (Alg. 2 l.7-8)."""
    return jnp.take(w, jnp.asarray(mapping), axis=axis)


def widen_out(w, mapping, old: int, axis: int = 0):
    """Outgoing weights: duplicate rows and divide each duplicate group by
    its size (Alg. 2 l.11-14)."""
    m = jnp.asarray(mapping)
    scale = 1.0 / mapping_counts(m, old)[m].astype(jnp.float32)
    out = jnp.take(w, m, axis=axis)
    shape = [1] * out.ndim
    shape[axis] = -1
    return out * scale.reshape(shape).astype(out.dtype)


# ------------------------------------------------------------- To-Narrower

def narrow_in(w, n_tar: int, axis: int = -1):
    """Incoming weights: drop columns of deleted neurons (> N_tar)."""
    return jax.lax.slice_in_dim(w, 0, n_tar, axis=axis)


def narrow_out_paper(w, n_tar: int, axis: int = 0):
    """Alg. 3: s = sum of deleted rows; survivors += s / N_tar."""
    kept = jax.lax.slice_in_dim(w, 0, n_tar, axis=axis)
    dropped = jax.lax.slice_in_dim(w, n_tar, w.shape[axis], axis=axis)
    s = dropped.sum(axis=axis, keepdims=True)
    return kept + (s / n_tar).astype(kept.dtype)


def narrow_fold_in(w, mapping, old: int, axis: int = -1):
    """Beyond-paper inverse of ``widen_in``: mean over each duplicate group."""
    m = jnp.asarray(mapping)
    counts = mapping_counts(m, old)
    w_moved = jnp.moveaxis(w, axis, 0)
    summed = jax.ops.segment_sum(w_moved, m, num_segments=old)
    mean = summed / counts.reshape((-1,) + (1,) * (summed.ndim - 1)).astype(w.dtype)
    return jnp.moveaxis(mean, 0, axis)


def narrow_fold_out(w, mapping, old: int, axis: int = 0):
    """Beyond-paper inverse of ``widen_out``: sum over each duplicate group."""
    m = jnp.asarray(mapping)
    w_moved = jnp.moveaxis(w, axis, 0)
    summed = jax.ops.segment_sum(w_moved, m, num_segments=old)
    return jnp.moveaxis(summed, 0, axis)


# ----------------------------------------------------- To-Deeper (identity)

def identity_conv(channels: int, ksize: int = 3, dtype=jnp.float32):
    """3x3 conv kernel acting as identity (center tap = channel diagonal).
    Exact identity after ReLU since preceding activations are >= 0."""
    w = jnp.zeros((ksize, ksize, channels, channels), dtype)
    c = ksize // 2
    return w.at[c, c].set(jnp.eye(channels, dtype=dtype))


def identity_fc(width: int, dtype=jnp.float32):
    return jnp.eye(width, dtype=dtype)


def zero_like_output_proj(params, out_proj_keys: Sequence[str]):
    """Pre-norm residual identity insert: zero the block's output
    projections so the residual branch contributes nothing."""
    def fix(path, leaf):
        names = [getattr(k, "key", None) for k in path]
        return jnp.zeros_like(leaf) if any(n in out_proj_keys for n in names) else leaf
    return jax.tree_util.tree_map_with_path(fix, params)
