"""Segment operators — the width embedding as an explicit linear map.

NetChange's To-Wider is deterministic in ``(tag, old, new, seed)``
(``netchange.dup_mapping``), so a client's place in the union
architecture is a *linear operator*: ``up(p) = E p + filler`` where E
duplicates client coordinates into union *segments* (the union channels
that copy one client channel) and scales outgoing duplicates by the
inverse group size (Net2Net split). This module makes E's structure
first-class:

  * a family's ``segment_spec(client_cfg, global_cfg, seed)`` names, per
    union-tree leaf, the widened axes and the segment id of every union
    index along them (``AxisSeg``);
  * ``grad_matrix`` builds the axis factor of ``E Eᵀ`` — the operator
    that makes union-space SGD *equal* client-space SGD: the loop
    reference trains ``p ← p − lr ∇L(p)`` and ``∇_p L(E p) = Eᵀ g``, so
    the stacked engine must step ``u ← u − lr (E Eᵀ) g`` to keep
    ``u = E p`` exactly. Per axis that is segment-sum (duplicated axes)
    with a ``1/c²`` scale on split (outgoing) axes;
  * ``mean_matrix`` builds the axis factor of the *idempotent* projector
    ``E (EᵀE)⁻¹ Eᵀ`` onto image(E) — the segment mean, which for both
    axis roles is also exactly ``up(down(·))`` under
    ``narrow_mode="fold"``;
  * ``multiplicity_tree`` gives per-coordinate duplication counts
    ``m_kj`` for the multiplicity-aware coverage average (a client
    channel duplicated m times contributes weight ``W_k/m`` per copy, so
    its total stays ``W_k`` — ``core.aggregation``).

Everything here is plain data (numpy matrices keyed by tree paths) —
the reference — except ``grad_matrices``, which builds the same stacked
``E Eᵀ`` factors on the device from a chunk's stacked segment ids
(``segment_ids``: a few KB a client, where the dense matrices are
megabytes); the engine applies them inside its jitted step
(``project_stacked``).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Path = Tuple[str, ...]


@dataclass(frozen=True)
class AxisSeg:
    """One widened axis of a union-shaped leaf: ``ids[j]`` labels the
    client coordinate union index ``j`` duplicates (equal ids = one
    segment). ``out_role`` marks the Net2Net *split* side (outgoing
    weights divided by the group size)."""
    axis: int
    ids: np.ndarray
    out_role: bool = False

    @property
    def counts(self) -> np.ndarray:
        """Per-position segment sizes c_j (length = union extent)."""
        _, inv, cnt = np.unique(np.asarray(self.ids), return_inverse=True,
                                return_counts=True)
        return cnt[inv].astype(np.int32)


def _same(seg: AxisSeg) -> np.ndarray:
    ids = np.asarray(seg.ids)
    return (ids[:, None] == ids[None, :]).astype(np.float32)


def grad_matrix(seg: AxisSeg) -> np.ndarray:
    """Axis factor of ``E Eᵀ``: segment-sum, with 1/c² on split axes
    (E = D diag(1/c) there, so E Eᵀ = D diag(1/c²) Dᵀ)."""
    b = _same(seg)
    if not seg.out_role:
        return b
    r = 1.0 / seg.counts.astype(np.float32)
    return b * r[:, None] * r[None, :]


def mean_matrix(seg: AxisSeg) -> np.ndarray:
    """Axis factor of the orthogonal projector onto image(E): the
    segment mean ``P[v, u] = [same segment] / c_v`` — identical for both
    axis roles (``E (EᵀE)⁻¹ Eᵀ = D diag(1/c) Dᵀ`` either way)."""
    return _same(seg) / seg.counts.astype(np.float32)[:, None]


# ------------------------------------------------------------- tree plumbing

def path_keys(path) -> Path:
    """jax tree_util key path -> plain string tuple."""
    return tuple(str(getattr(p, "key", p)) for p in path)


def path_str(path) -> str:
    return "/".join(path_keys(path))


def leaf_shape(shapes, path: Path):
    node = shapes
    for k in path:
        node = node[k]
    return tuple(node.shape)


def union_axes(specs: Sequence[Dict[Path, List[AxisSeg]]],
               shapes) -> Dict[Path, Tuple[int, ...]]:
    """Union over clients of (leaf path -> widened axes), axes
    canonicalized to non-negative leaf axes — the seed-invariant static
    structure the engine's jitted step closes over."""
    out: Dict[Path, set] = {}
    for spec in specs:
        for path, segs in spec.items():
            nd = len(leaf_shape(shapes, path))
            out.setdefault(path, set()).update(s.axis % nd for s in segs)
    return {p: tuple(sorted(a)) for p, a in sorted(out.items())}


def client_matrices(spec: Dict[Path, List[AxisSeg]],
                    axes_map: Dict[Path, Tuple[int, ...]], shapes, *,
                    kind: str = "grad") -> Dict[Path, List[np.ndarray]]:
    """Per-leaf, per-axis matrices for one client, aligned with the
    cohort's ``axes_map``; identity where this client has no widening
    (so every client shares one static structure and the matrices stack
    on a leading K axis)."""
    build = grad_matrix if kind == "grad" else mean_matrix
    out: Dict[Path, List[np.ndarray]] = {}
    for path, axes in axes_map.items():
        shape = leaf_shape(shapes, path)
        by_axis = {s.axis % len(shape): s for s in spec.get(path, [])}
        mats = []
        for ax in axes:
            s = by_axis.get(ax)
            mats.append(np.eye(shape[ax], dtype=np.float32) if s is None
                        else build(s))
        out[path] = mats
    return out


def stack_matrices(per_client: Sequence[Dict[Path, List[np.ndarray]]]
                   ) -> Dict[str, List[jnp.ndarray]]:
    """Stack aligned per-client matrix dicts into the ``{path-str:
    [(K, U, U), ...]}`` pytree the jitted step consumes — the numpy
    reference of ``grad_matrices``."""
    if not per_client:
        return {}
    out: Dict[str, List[jnp.ndarray]] = {}
    for path in per_client[0]:
        out["/".join(path)] = [
            jnp.asarray(np.stack([c[path][i] for c in per_client]))
            for i in range(len(per_client[0][path]))]
    return out


def segment_ids(spec: Dict[Path, List[AxisSeg]],
                axes_map: Dict[Path, Tuple[int, ...]], shapes
                ) -> Dict[str, List[np.ndarray]]:
    """One client's segment ids aligned with the cohort's ``axes_map``,
    as ``{path-str: [int32 ids per axis]}``; ``arange`` (the identity)
    where this client has no widening — ``client_matrices``' input as
    plain ids."""
    out: Dict[str, List[np.ndarray]] = {}
    for path, axes in axes_map.items():
        shape = leaf_shape(shapes, path)
        by_axis = {s.axis % len(shape): s for s in spec.get(path, [])}
        out["/".join(path)] = [
            np.asarray(by_axis[ax].ids, np.int32) if ax in by_axis
            else np.arange(shape[ax], dtype=np.int32) for ax in axes]
    return out


def axis_roles(specs: Sequence[Dict[Path, List[AxisSeg]]],
               axes_map: Dict[Path, Tuple[int, ...]], shapes
               ) -> Tuple[Tuple[str, Tuple[bool, ...]], ...]:
    """The seed-invariant ``out_role`` of every widened axis in
    ``axes_map`` — hashable, ``grad_matrices``' static argument. Raises
    if two clients give one axis different roles."""
    roles: Dict[Tuple[Path, int], bool] = {}
    for spec in specs:
        for path, segs in spec.items():
            nd = len(leaf_shape(shapes, path))
            for s in segs:
                if roles.setdefault((path, s.axis % nd),
                                    s.out_role) != s.out_role:
                    raise ValueError(f"axis {s.axis} of leaf "
                                     f"{'/'.join(path)} has both roles")
    return tuple(("/".join(p), tuple(roles.get((p, ax), False)
                                     for ax in axes))
                 for p, axes in axes_map.items())


@functools.partial(jax.jit, static_argnames=("roles",))
def grad_matrices(ids: Dict[str, List[jnp.ndarray]],
                  roles: Tuple[Tuple[str, Tuple[bool, ...]], ...]
                  ) -> Dict[str, List[jnp.ndarray]]:
    """``stack_matrices`` of every client's ``client_matrices(kind=
    "grad")``, built on the device from the stacked ids ``(k, U)`` of
    ``segment_ids``: ``grad_matrix`` row by row, identity rows from
    ``arange`` ids."""
    out: Dict[str, List[jnp.ndarray]] = {}
    for path, rs in roles:
        mats = []
        for x, out_role in zip(ids[path], rs):
            same = (x[:, :, None] == x[:, None, :]).astype(jnp.float32)
            if out_role:
                r = 1.0 / same.sum(axis=-1)
                same = same * r[:, :, None] * r[:, None, :]
            mats.append(same)
        out[path] = mats
    return out


def apply_leaf(x, axes: Tuple[int, ...], mats: Sequence, *, stacked: bool):
    """Apply per-axis matrices ``out[v] = Σ_u M[v,u] x[u]`` along each
    widened axis. ``stacked`` marks a leading K axis on ``x`` (and on
    every matrix)."""
    out = x.astype(jnp.float32)
    for ax, m in zip(axes, mats):
        a = ax + 1 if stacked else ax
        moved = jnp.moveaxis(out, a, -1)
        eq = "kvu,k...u->k...v" if stacked else "vu,...u->...v"
        moved = jnp.einsum(eq, m, moved)
        out = jnp.moveaxis(moved, -1, a)
    return out.astype(x.dtype)


def project_stacked(tree, axes_map: Dict[str, Tuple[int, ...]],
                    mats: Dict[str, List[jnp.ndarray]]):
    """Apply the stacked per-client segment operators to a stacked tree
    (no-op on leaves without widened axes). Used on gradients inside the
    engine's step: masks handle depth, this handles width."""
    if not axes_map:
        return tree

    def fix(path, g):
        axes = axes_map.get(path_str(path))
        if not axes:
            return g
        return apply_leaf(g, axes, mats[path_str(path)], stacked=True)

    return jax.tree_util.tree_map_with_path(fix, tree)


def project_client(tree, spec: Dict[Path, List[AxisSeg]], *,
                   kind: str = "mean"):
    """Apply one client's segment operator (mean projector by default)
    to an un-stacked union-shaped tree — the reference/test-side
    counterpart of ``project_stacked``."""

    def fix(path, g):
        segs = spec.get(path_keys(path))
        if not segs:
            return g
        build = grad_matrix if kind == "grad" else mean_matrix
        nd = g.ndim
        return apply_leaf(g, tuple(s.axis % nd for s in segs),
                          [jnp.asarray(build(s)) for s in segs],
                          stacked=False)

    return jax.tree_util.tree_map_with_path(fix, tree)


def multiplicity_tree(spec: Dict[Path, List[AxisSeg]], shapes):
    """Per-coordinate duplication counts m_kj of one client's embedding:
    the product over widened axes of the segment size (1 everywhere for
    depth-only embeddings). Feeds the multiplicity-aware coverage
    average (``core.aggregation``)."""

    def build(path, s):
        arr = np.ones(s.shape, np.float32)
        for seg in spec.get(path_keys(path), []):
            shape = [1] * len(s.shape)
            shape[seg.axis % len(s.shape)] = -1
            arr = arr * seg.counts.astype(np.float32).reshape(shape)
        return jnp.asarray(arr)

    return jax.tree_util.tree_map_with_path(build, shapes)
