"""Back-compat facade: ``Simulator``/``FLRunConfig`` over ``Federation``.

The orchestration API is the Strategy protocol + Federation orchestrator
(fl/strategy.py, fl/federation.py, fl/backends.py — DESIGN.md §7); this
module keeps the original entry point working unchanged:

    Simulator(family, client_cfgs, samplers, FLRunConfig(...), eval_batch)
        .run() -> {"history", "final_acc", "client_params",
                   "global_params", "wall_s"}

Methods: fedadp | flexifed | clustered | standalone  (Section IV).

Protocol knobs follow Section IV.A.4: K clients, local epochs E over 20%
of the client's data per round, SGD(lr). ``participation`` (beyond-paper)
selects a seeded per-round client subset when < 1 (both engines).

Execution backends (EXPERIMENTS.md §Perf):
  * engine="loop"     — reference path: a Python loop over clients, each
                        trained in its own architecture (LoopBackend).
  * engine="unified"  — cohort-parallel path (UnifiedBackend around
                        fl/engine.py): one stacked vmapped program in the
                        union architecture, shard_map-able over a device
                        mesh. Loop-equivalent on segment-representable
                        cohorts — depth AND width heterogeneity
                        (DESIGN.md §2).
  * engine="auto"     — unified when eligible (backends.unified_eligible),
                        loop otherwise; the fallback reason is logged once
                        (logger "repro.fl",
                        backends.unified_ineligible_reason).

Beyond-paper knobs (ablations in EXPERIMENTS.md):
  * narrow_mode:  "paper" (Alg. 3) | "fold" (function-preserving inverse)
  * filler:       "zero" (paper) | "global" (FedADP-U) — a FedADP
                  strategy option (fl/strategy.py).
  * coverage:     "loose" (reference reading: identity-conv filler taps
                  count as covered) | "strict" (parameter landing sites
                  only) — core.aggregation's single coverage semantics.
  * agg_mode:     "filler" (Eq. 1 verbatim) | "coverage" (HeteroFL-style
                  per-coordinate renormalized average over covering
                  clients; uncovered coordinates keep server values;
                  multiplicity-aware on width-heterogeneous cohorts).
  * embed_seed:   base seed of the NetChange To-Wider mappings (None =
                  follow `seed`); both engines derive identical
                  per-(round, client) mappings from it.
  * agg_layout:   "auto" (default: resolve_agg_layout picks "plane" at
                  small K and "stream" past K=32 / 256 MiB cohorts,
                  logged once per backend) | "plane" | "stream" — the
                  streaming layout aggregates in O(P·k_chunk) memory
                  (DESIGN.md §9).
  * k_chunk:      streaming chunk rows (None = auto, 16); pinning it
                  implies "stream" under agg_layout="auto".

All config values are validated eagerly at ``FLRunConfig`` construction.
"""
from __future__ import annotations

import logging
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.core import AGG_MODES, COVERAGE_POLICIES, WIRE_FORMATS
from repro.core.quant import validate_tile
from repro.data.federated import ClientSampler
from repro.fl.backends import (LoopBackend, UnifiedBackend,
                               unified_ineligible_reason)
from repro.fl.federation import Federation, Participation
from repro.fl.strategy import FILLERS, METHODS, NARROW_MODES, make_strategy

_ENGINES = ("loop", "unified", "auto")

_log = logging.getLogger("repro.fl")


@dataclass
class FLRunConfig:
    method: str = "fedadp"
    rounds: int = 20
    local_epochs: int = 2
    lr: float = 0.01
    momentum: float = 0.0
    narrow_mode: str = "paper"
    filler: str = "zero"
    coverage: str = "loose"
    agg_mode: str = "filler"
    seed: int = 0
    embed_seed: Optional[int] = None     # NetChange embedding base seed
                                         # (To-Wider mappings); None =
                                         # follow `seed`. Loop and unified
                                         # engines derive IDENTICAL
                                         # per-(round, client) mappings
                                         # from it (round_embed_seed) —
                                         # a user-settable contract
    eval_every: int = 1
    engine: str = "auto"                 # loop | unified | auto
    use_kernel: Optional[bool] = None    # unified path: None = auto (TPU)
    participation: float = 1.0           # client fraction per round
    participation_seed: int = 0          # per-round sampling seed
    agg_layout: str = "auto"             # aggregation layout: auto (pick
                                         # per backend + cohort shape,
                                         # logged once) | plane | stream
    k_chunk: Optional[int] = None        # streaming chunk rows; pinning
                                         # it implies layout "stream"
                                         # under "auto"
    wire: str = "f32"                    # client->server payload encoding
                                         # (core.quant): "f32" (none) |
                                         # "bf16" | "int8"+error feedback;
                                         # non-f32 needs method="fedadp"
                                         # on the unified engine and
                                         # rides the streaming layout
    wire_tile: int = 256                 # int8 scale tile (lane multiple)
    wire_sparse: bool = False            # ship covered coordinates only;
                                         # needs agg_mode="coverage"
    compute_dtype: str = "f32"           # local-training compute: "f32" |
                                         # "bf16" (mixed precision — the
                                         # packed plane and optimizer
                                         # state stay f32 master copies;
                                         # unified engine only)
    attn_backend: str = "auto"           # attention backend of the local
                                         # step: "auto" (flash Pallas on
                                         # TPU, blockwise XLA elsewhere) |
                                         # "flash" | "blockwise" (forced
                                         # values: unified engine only)

    def __post_init__(self):
        # fail at construction, not after `rounds` of work mid-run
        if self.method not in METHODS:
            raise ValueError(
                f"method={self.method!r}, expected one of {METHODS}")
        if self.filler not in FILLERS:
            raise ValueError(
                f"filler={self.filler!r}, expected one of {FILLERS}")
        if self.narrow_mode not in NARROW_MODES:
            raise ValueError(f"narrow_mode={self.narrow_mode!r}, expected "
                             f"one of {NARROW_MODES}")
        if self.coverage not in COVERAGE_POLICIES:
            raise ValueError(f"coverage={self.coverage!r}, expected one of "
                             f"{COVERAGE_POLICIES}")
        if self.agg_mode not in AGG_MODES:
            raise ValueError(f"agg_mode={self.agg_mode!r}, expected one of "
                             f"{AGG_MODES}")
        if self.engine not in _ENGINES:
            raise ValueError(
                f"engine={self.engine!r}, expected one of {_ENGINES}")
        if not (0.0 < self.participation <= 1.0):
            raise ValueError(f"participation={self.participation!r} must "
                             "be in (0, 1]")
        if self.rounds < 0:
            raise ValueError(f"rounds={self.rounds!r} must be >= 0")
        if self.eval_every < 1:
            raise ValueError(f"eval_every={self.eval_every!r} must be >= 1")
        if self.local_epochs < 1:
            raise ValueError(
                f"local_epochs={self.local_epochs!r} must be >= 1")
        if self.embed_seed is not None and (
                isinstance(self.embed_seed, bool)
                or not isinstance(self.embed_seed, int)):
            raise ValueError(f"embed_seed={self.embed_seed!r} must be an "
                             "int (or None to follow `seed`)")
        if self.agg_layout not in ("auto", "plane", "stream"):
            raise ValueError(
                f"agg_layout={self.agg_layout!r}, expected 'auto', "
                "'plane' or 'stream' ('leaf' is the per-leaf reference "
                "layout of core.aggregation, not a run option)")
        if self.k_chunk is not None and (
                isinstance(self.k_chunk, bool)
                or not isinstance(self.k_chunk, int) or self.k_chunk < 1):
            raise ValueError(f"k_chunk={self.k_chunk!r} must be a "
                             "positive int (or None for auto)")
        if self.wire not in WIRE_FORMATS:
            raise ValueError(f"wire={self.wire!r}, expected one of "
                             f"{WIRE_FORMATS}")
        validate_tile(self.wire_tile)
        if self.wire != "f32":
            if self.method != "fedadp":
                raise ValueError(
                    f"wire={self.wire!r} compresses fedadp round "
                    f"payloads; method={self.method!r} has no wire layer")
            if self.engine == "loop":
                raise ValueError(
                    "wire compression needs the unified engine (the "
                    "fused dequantize-accumulate streaming kernel); "
                    "engine='loop' cannot honor it")
            if self.agg_layout == "plane":
                raise ValueError(
                    "wire compression aggregates on the streaming "
                    "layout; agg_layout='plane' contradicts it — use "
                    "'auto' or 'stream'")
        if self.wire_sparse:
            if self.wire == "f32":
                raise ValueError("wire_sparse needs a compressed wire "
                                 "(wire='bf16' or 'int8')")
            if self.agg_mode != "coverage":
                raise ValueError(
                    'wire_sparse is exact only under agg_mode="coverage"'
                    " (only covered coordinates enter the average); "
                    f"agg_mode={self.agg_mode!r} averages uncovered "
                    "coordinates too")
        if self.compute_dtype not in ("f32", "bf16"):
            raise ValueError(f"compute_dtype={self.compute_dtype!r}, "
                             "expected 'f32' or 'bf16'")
        if self.compute_dtype != "f32" and self.engine == "loop":
            raise ValueError(
                "compute_dtype='bf16' is the unified engine's cast-at-"
                "unpack policy (f32 master plane, bf16 step); "
                "engine='loop' cannot honor it")
        if self.attn_backend not in ("auto", "flash", "blockwise"):
            raise ValueError(f"attn_backend={self.attn_backend!r}, "
                             "expected 'auto', 'flash' or 'blockwise'")
        if self.attn_backend != "auto" and self.engine == "loop":
            raise ValueError(
                "a forced attn_backend threads through the unified "
                "engine's training step; engine='loop' cannot honor it")

    @property
    def resolved_embed_seed(self) -> int:
        return self.seed if self.embed_seed is None else self.embed_seed


class Simulator:
    """Thin shim: builds (strategy, backend, Federation) from the config
    once, then delegates ``run()``. Kept so every existing test, example
    and benchmark works unchanged on top of the new API."""

    def __init__(self, family, client_cfgs: Sequence,
                 samplers: List[ClientSampler], run_cfg: FLRunConfig,
                 eval_batch: Dict[str, np.ndarray], mesh=None):
        self.family = family
        self.client_cfgs = list(client_cfgs)
        self.samplers = samplers
        self.cfg = run_cfg
        self.eval_batch = eval_batch
        self.mesh = mesh
        self.n_samples = [s.n_samples for s in samplers]
        # backends (grad fns / the engine's jitted step) are cached across
        # run()s keyed by the cfg fields they depend on; the Federation
        # itself is rebuilt per run so `sim.cfg` mutations (e.g. replacing
        # `rounds` between a warmup and a timed run) take effect.
        self._backends: Dict[tuple, Any] = {}
        self._fallback_logged = False
        self.backend = None              # the backend of the last run
                                         # ("loop" | "unified" by .name)

    # ------------------------------------------------------ engine choice
    def _resolve_engine(self, strategy=None) -> str:
        if self.cfg.engine != "auto":
            return self.cfg.engine
        strategy = strategy if strategy is not None else self._strategy()
        reason = unified_ineligible_reason(
            strategy, self.family, self.client_cfgs, self.samplers)
        if reason is None:
            return "unified"
        if self.cfg.wire != "f32":
            # the loop backend has no wire layer — a silent fallback would
            # run uncompressed while reporting wire=... in the config
            raise ValueError(
                f"wire={self.cfg.wire!r} needs the unified engine, but "
                f"this run is unified-ineligible: {reason}")
        if self.cfg.compute_dtype != "f32":
            raise ValueError(
                f"compute_dtype={self.cfg.compute_dtype!r} needs the "
                f"unified engine, but this run is unified-ineligible: "
                f"{reason}")
        if self.cfg.attn_backend != "auto":
            raise ValueError(
                f"attn_backend={self.cfg.attn_backend!r} needs the "
                f"unified engine, but this run is unified-ineligible: "
                f"{reason}")
        if not self._fallback_logged:
            # once per Simulator: the auto fallback used to be silent and
            # undiagnosable
            _log.info("engine='auto' falls back to the loop backend: %s",
                      reason)
            self._fallback_logged = True
        return "loop"

    def _strategy(self):
        return make_strategy(
            self.cfg.method, self.family, self.client_cfgs, self.n_samples,
            narrow_mode=self.cfg.narrow_mode, filler=self.cfg.filler,
            coverage=self.cfg.coverage, agg_mode=self.cfg.agg_mode,
            base_seed=self.cfg.resolved_embed_seed,
            agg_layout=self.cfg.agg_layout, k_chunk=self.cfg.k_chunk,
            wire=self.cfg.wire, wire_tile=self.cfg.wire_tile,
            wire_sparse=self.cfg.wire_sparse,
            compute_dtype=self.cfg.compute_dtype,
            attn_backend=self.cfg.attn_backend)

    def _backend(self, kind: str):
        cfg = self.cfg
        # key only on what each backend actually depends on, so e.g. a
        # seed sweep on the loop engine keeps its warm grad fns
        bkey = (kind, cfg.local_epochs, cfg.lr, cfg.momentum) + (
            (cfg.use_kernel, cfg.resolved_embed_seed, cfg.agg_layout,
             cfg.k_chunk, cfg.wire, cfg.wire_tile, cfg.wire_sparse,
             cfg.compute_dtype, cfg.attn_backend)
            if kind == "unified" else ())
        if bkey not in self._backends:
            if kind == "unified":
                self._backends[bkey] = UnifiedBackend(
                    self.family, self.client_cfgs, self.samplers,
                    local_epochs=cfg.local_epochs, lr=cfg.lr,
                    momentum=cfg.momentum, use_kernel=cfg.use_kernel,
                    mesh=self.mesh, seed=cfg.resolved_embed_seed,
                    agg_layout=cfg.agg_layout, k_chunk=cfg.k_chunk,
                    wire=cfg.wire, wire_tile=cfg.wire_tile,
                    wire_sparse=cfg.wire_sparse,
                    compute_dtype=cfg.compute_dtype,
                    attn_backend=cfg.attn_backend)
            else:
                self._backends[bkey] = LoopBackend(
                    self.family, self.client_cfgs, self.samplers,
                    local_epochs=cfg.local_epochs, lr=cfg.lr,
                    momentum=cfg.momentum)
        return self._backends[bkey]

    def _build(self, callbacks=()) -> Federation:
        cfg = self.cfg
        strategy = self._strategy()
        backend = self._backend(self._resolve_engine(strategy))
        backend.samplers = self.samplers   # like cfg, mutable between runs
        self.backend = backend
        return Federation(
            strategy, backend, rounds=cfg.rounds, eval_batch=self.eval_batch,
            eval_every=cfg.eval_every,
            participation=Participation(cfg.participation,
                                        cfg.participation_seed),
            callbacks=callbacks)

    # -------------------------------------------------------------- runs
    def run(self, key=None, *, callbacks=()) -> Dict[str, Any]:
        """Run ``cfg.rounds`` rounds; ``callbacks`` get the Federation's
        per-round records (``{"round", "selected", "wall_s"[, "acc"]}``)."""
        key = key if key is not None else jax.random.PRNGKey(self.cfg.seed)
        return self._build(callbacks).run(key)
