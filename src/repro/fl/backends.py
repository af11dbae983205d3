"""Pluggable execution backends: who actually runs a federated round.

A backend takes a bound ``Strategy`` and executes ``distribute -> local
train -> collect -> aggregate`` for one round:

  * ``LoopBackend``     — the reference path: a Python loop over the
                          participating clients, each trained in its OWN
                          architecture with a per-config jitted grad fn.
                          Supports every strategy and any participation
                          subset.
  * ``UnifiedBackend``  — the cohort-parallel path: wraps
                          ``fl/engine.py``'s ``UnifiedEngine`` so the
                          whole round runs as one stacked vmapped XLA
                          program in the union architecture (shard_map
                          over the client axis when a mesh is given).
                          The round is routed through the PACKED
                          parameter plane (``core.plane``): state packs
                          to a contiguous ``(K, P)`` buffer on round
                          entry, participant gathers are row slices,
                          aggregation is one fused kernel pass, and the
                          jitted step donates the plane buffers — while
                          the Federation-facing state (init_state /
                          run_round results, checkpoints, client_views)
                          stays the tree-shaped layout the loop
                          reference owns, so the two backends remain
                          interchangeable and checkpoint-compatible.
                          Partial participation gathers the selected
                          rows of the packed cohort and draws batches
                          from the participants' samplers only, so both
                          backends consume identical data streams
                          (DESIGN.md §7). Requires aligned client batch
                          streams.

Both expose the same surface to ``Federation``:
  bind(strategy) / init_state(key) / run_round(state, r, selected) /
  evaluate(state, r, batch) / client_views(state, r) / samplers.

``unified_eligible`` is the ``engine="auto"`` rule: unified when the
strategy supports it, the cohort's embedding is segment-representable
(depth AND width heterogeneity — the old ``depth_only`` gate is gone),
and the client batch streams are guaranteed to align. Participation and
FedADP-U no longer keep the loop — both paths read coverage from
``core.aggregation``. ``unified_ineligible_reason`` names the first
failing condition so an ``engine="auto"`` fallback is diagnosable
instead of silent.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.core.aggregation import default_k_chunk
from repro.core.plane import chunk_bounds
from repro.fl import spans
from repro.fl.engine import UnifiedEngine
from repro.fl.strategy import METHODS, Strategy
from repro.optim import sgd


class LoopBackend:
    """Per-client reference execution (exactly the paper's protocol)."""
    name = "loop"

    def __init__(self, family, client_cfgs: Sequence, samplers: List, *,
                 local_epochs: int = 1, lr: float = 0.01,
                 momentum: float = 0.0):
        self.family = family
        self.client_cfgs = list(client_cfgs)
        self.samplers = samplers
        self.local_epochs = local_epochs
        self._opt = sgd(lr, momentum)
        self._grad_fns: Dict[str, Callable] = {}
        self.strategy: Optional[Strategy] = None

    def bind(self, strategy: Strategy) -> "LoopBackend":
        self.strategy = strategy
        return self

    # ---------------------------------------------------------- training
    def _grad_fn(self, cfg):
        if cfg.name not in self._grad_fns:
            self._grad_fns[cfg.name] = jax.jit(self.family.loss_and_grad(cfg))
        return self._grad_fns[cfg.name]

    def _local_train(self, k: int, params):
        gf = self._grad_fn(self.client_cfgs[k])
        opt_state = self._opt.init(params)   # fresh momentum every round
        step = 0
        for batch in self.samplers[k].round_batches(self.local_epochs):
            (_, _), grads = gf(params, batch)
            params, opt_state = self._opt.update(grads, opt_state, params,
                                                 step)
            step += 1
        return params

    # ----------------------------------------------------------- surface
    def init_state(self, key):
        return self.strategy.init_state(key)

    def run_round(self, state, round_idx: int, selected: Sequence[int]):
        s = self.strategy
        updates = []
        for k in selected:
            trained = self._local_train(k, s.distribute(state, round_idx, k))
            updates.append((k, s.collect(state, round_idx, k, trained)))
        return s.aggregate(state, round_idx, updates)

    def client_views(self, state, round_idx: int) -> List:
        return [self.strategy.client_view(state, k, round_idx)
                for k in range(len(self.client_cfgs))]

    def evaluate(self, state, round_idx: int, eval_batch) -> float:
        accs = [self.family.evaluate(p, c, eval_batch)
                for p, c in zip(self.client_views(state, round_idx),
                                self.client_cfgs)]
        return float(np.mean(accs))


class UnifiedBackend:
    """Cohort-parallel execution through ``UnifiedEngine`` (one stacked
    program over the packed parameter plane; loop-equivalent on
    segment-representable depth- and width-heterogeneous cohorts —
    fl/engine.py docstring)."""
    name = "unified"

    def __init__(self, family, client_cfgs: Sequence, samplers: List, *,
                 local_epochs: int = 1, lr: float = 0.01,
                 momentum: float = 0.0, use_kernel: Optional[bool] = None,
                 mesh=None, seed: int = 0, agg_layout: str = "auto",
                 k_chunk: Optional[int] = None, wire: str = "f32",
                 wire_tile: int = 256, wire_sparse: bool = False,
                 compute_dtype: str = "f32", attn_backend: str = "auto"):
        self.family = family
        self.client_cfgs = list(client_cfgs)
        self.samplers = samplers
        self.local_epochs = local_epochs
        self.lr, self.momentum = lr, momentum
        self.use_kernel, self.mesh, self.seed = use_kernel, mesh, seed
        self.agg_layout, self.k_chunk = agg_layout, k_chunk
        self.wire, self.wire_tile = wire, wire_tile
        self.wire_sparse = wire_sparse
        self.compute_dtype = compute_dtype
        self.attn_backend = attn_backend
        self.strategy: Optional[Strategy] = None
        self.engine: Optional[UnifiedEngine] = None
        self._engine_key = None

    def bind(self, strategy: Strategy) -> "UnifiedBackend":
        if strategy.name not in METHODS:
            raise ValueError(
                f"unified backend does not support {strategy.name!r}")
        self.strategy = strategy
        # aggregation weights come from the STRATEGY's n_samples (the same
        # numbers strategy.aggregate would use on the loop backend), not
        # from whatever samplers the backend currently holds
        n_samples = [int(n) for n in strategy.n_samples]
        # keep the engine (and its jitted steps) across rebinds of the SAME
        # method/coverage-knobs/weights; rebuild when the strategy's math
        # changes
        # the NetChange seed comes from the STRATEGY when it has one
        # (FedADP.base_seed — the loop derives its per-round To-Wider
        # mappings from it, so the engine must too; backend `seed` is the
        # fallback for per-client-state strategies, which only embed once)
        embed_seed = getattr(strategy, "base_seed", self.seed)
        # the aggregation layout / streaming chunk: an EXPLICIT strategy
        # setting wins (the strategy's aggregate must match the engine's),
        # otherwise the backend's knob (itself defaulting to "auto" —
        # core.aggregation.resolve_agg_layout picks per cohort shape)
        agg_layout = getattr(strategy, "agg_layout", None)
        if agg_layout in (None, "auto", "leaf"):
            # "leaf" is a loop-side reference layout; the engine has no
            # per-leaf path, so it falls through to the backend's knob
            agg_layout = self.agg_layout
        k_chunk = getattr(strategy, "k_chunk", None)
        if k_chunk is None:
            k_chunk = self.k_chunk
        # the wire format follows the same rule: a strategy that carries
        # the knobs (FedADPStrategy) wins over the backend defaults —
        # "f32" on the strategy means uncompressed only when the backend
        # agrees (backend-level wire is the deployment-wide default)
        wire = getattr(strategy, "wire", None)
        if wire in (None, "f32"):
            wire = self.wire
        wire_tile = getattr(strategy, "wire_tile", None) or self.wire_tile
        wire_sparse = (getattr(strategy, "wire_sparse", False)
                       or self.wire_sparse)
        # the local-training compute policy rides the same precedence:
        # a strategy carrying non-default knobs wins over the backend
        compute_dtype = getattr(strategy, "compute_dtype", None)
        if compute_dtype in (None, "f32"):
            compute_dtype = self.compute_dtype
        attn_backend = getattr(strategy, "attn_backend", None)
        if attn_backend in (None, "auto"):
            attn_backend = self.attn_backend
        key = (strategy.name, getattr(strategy, "filler", "zero"),
               getattr(strategy, "agg_mode", "filler"),
               getattr(strategy, "coverage", "loose"),
               getattr(strategy, "narrow_mode", "paper"), embed_seed,
               tuple(n_samples), agg_layout, k_chunk, wire, wire_tile,
               wire_sparse, compute_dtype, attn_backend)
        if self.engine is None or self._engine_key != key:
            self._engine_key = key
            self.engine = UnifiedEngine(
                self.family, self.client_cfgs, n_samples,
                lr=self.lr, momentum=self.momentum, method=strategy.name,
                filler_mode=getattr(strategy, "filler", "zero"),
                agg_mode=getattr(strategy, "agg_mode", "filler"),
                coverage=getattr(strategy, "coverage", "loose"),
                narrow_mode=getattr(strategy, "narrow_mode", "paper"),
                use_kernel=self.use_kernel, mesh=self.mesh,
                embed_seed=embed_seed, agg_layout=agg_layout,
                k_chunk=k_chunk, wire=wire, wire_tile=wire_tile,
                wire_sparse=wire_sparse, compute_dtype=compute_dtype,
                attn_backend=attn_backend)
        return self

    @property
    def plane_spec(self):
        """The engine's packed layout (``core.plane.PlaneSpec``) — the
        spec a deployment would hand to ``checkpoint.save_plane`` or a
        wire-format encoder. ``None`` before ``bind``."""
        return self.engine.plane_spec if self.engine is not None else None

    # ------------------------------------------------------- wire format
    def wire_stats(self) -> Optional[dict]:
        """Byte accounting of the engine's last compressed round (empty
        when ``wire="f32"``, None before ``bind``)."""
        return self.engine.wire_stats() if self.engine is not None else None

    def wire_residuals(self):
        """The engine's per-client error-feedback residual plane
        ``(K, P)`` f32, or None when no compressed round has run — what
        the Federation checkpoints next to the round state."""
        return (self.engine.wire_residuals() if self.engine is not None
                else None)

    def load_wire_residuals(self, arr):
        """Restore a checkpointed residual plane into the bound engine
        (the compressed-run resume path)."""
        if self.engine is None:
            raise ValueError("load_wire_residuals needs a bound engine "
                             "(Federation binds before resuming)")
        self.engine.load_wire_residuals(arr)

    # ------------------------------------------------------- batch stream
    def _stacked_round_batches(self, selected: Sequence[int]
                               ) -> List[Dict[str, np.ndarray]]:
        """Draw one round of local batches from the PARTICIPATING
        samplers and stack them on a leading axis (``selected`` order).
        Consumes the SAME rng stream per sampler as the loop path — and
        none at all for non-participants — so the two paths see identical
        data under any participation schedule."""
        with spans.span(spans.BATCHES) as sp:
            per = [list(self.samplers[k].round_batches(self.local_epochs))
                   for k in selected]
            counts = {len(b) for b in per}
            if len(counts) != 1:
                raise ValueError(
                    "unified backend needs aligned client batch streams "
                    f"(got per-client step counts {sorted(counts)}); "
                    "use the loop backend for ragged cohorts")
            out = []
            for t in range(counts.pop()):
                shapes = {tuple((k, v.shape) for k, v in sorted(b[t].items()))
                          for b in per}
                if len(shapes) != 1:
                    raise ValueError(
                        "unified backend needs identical batch shapes "
                        "across clients; use the loop backend")
                out.append({k: np.stack([b[t][k] for b in per])
                            for k in per[0][t]})
            sp.set_metadata(steps=len(out), bytes=spans.host_bytes(out))
        return out

    # ----------------------------------------------------------- surface
    def init_state(self, key):
        if self.strategy.kind == "global":
            return self.engine.init_global(key)
        return self.engine.embed(self.strategy.init_state(key))

    def run_round(self, state, round_idx: int, selected: Sequence[int]):
        sel = list(selected)
        with spans.span(spans.ROUND, round=round_idx, clients=len(sel)):
            return self.engine.run_round(
                state, self._stacked_round_batches(sel), selected=sel,
                round_idx=round_idx)

    def _iter_client_views(self, state, round_idx: int):
        """Each client's union-space view in client order. A global
        state is distributed one ``k_chunk`` of clients at a time, so
        evaluation never holds more than one chunk of union-sized views
        (the whole cohort is several GB at published widths)."""
        n = len(self.client_cfgs)
        if self.strategy.kind != "global":
            for k in range(n):
                yield self.engine.client_view(state, k)
            return
        kc = default_k_chunk(n, self.engine.k_chunk)
        for lo, hi in chunk_bounds(n, kc):
            stacked = self.engine.round_start(state, selected=range(lo, hi),
                                              round_idx=round_idx)
            for j in range(hi - lo):
                yield self.engine.client_view(stacked, j)

    def client_views(self, state, round_idx: int) -> List:
        return list(self._iter_client_views(state, round_idx))

    def evaluate(self, state, round_idx: int, eval_batch) -> float:
        gcfg = self.engine.global_cfg
        accs = [self.family.evaluate(p, gcfg, eval_batch)
                for p in self._iter_client_views(state, round_idx)]
        return float(np.mean(accs))


def unified_ineligible_reason(strategy: Strategy, family, client_cfgs,
                              samplers) -> Optional[str]:
    """Why ``engine="auto"`` would keep the loop for this run — None when
    the unified engine applies. The conditions: a unified-engine method,
    a segment-representable cohort embedding (depth and width both
    qualify; the old ``depth_only`` gate is deleted), and aligned client
    batch streams (equal n_samples + batch_size + round_fraction means
    every sampler draws the same per-round take). Neither FedADP-U nor
    partial participation keeps the loop anymore: both paths read
    coverage from ``core.aggregation`` and the engine runs
    selected-subset rounds."""
    if strategy.name not in METHODS:
        return (f"strategy {strategy.name!r} is not a unified-engine "
                f"method (supported: {', '.join(METHODS)})")
    cfgs = list(client_cfgs)
    rep = getattr(family, "segment_representable", None)
    representable = rep(cfgs) if rep is not None else family.depth_only(cfgs)
    if not representable:
        return ("cohort embedding is not segment-representable (only "
                "depth and supported width dimensions may vary — "
                "family.segment_representable)")
    if len({s.n_samples for s in samplers}) != 1:
        return ("ragged client datasets (unequal n_samples) — stacked "
                "batch streams would not align")
    if len({s.batch_size for s in samplers}) != 1:
        return "unequal client batch sizes — stacked batches must align"
    if len({getattr(s, "round_fraction", None) for s in samplers}) != 1:
        return ("unequal per-round data fractions — stacked batch "
                "streams would not align")
    return None


def unified_eligible(strategy: Strategy, family, client_cfgs,
                     samplers) -> bool:
    """The ``engine="auto"`` rule — see ``unified_ineligible_reason``."""
    return unified_ineligible_reason(strategy, family, client_cfgs,
                                     samplers) is None
