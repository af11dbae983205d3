"""Cohort-parallel unified FL engine (DESIGN.md §2, §5) — packed.

NetChange embeds every heterogeneous client into the cohort's union
architecture, so a whole federated round can run as ONE stacked XLA
program instead of a Python loop over clients:

  * client k's model = the global architecture with a constant *filler*
    on the parameters the client doesn't have (zero blocks for pre-norm
    residual transformers, identity convs for VGG — whatever ``up()``
    would insert) and a 0/1 *trainable mask* on the ones it does; width
    heterogeneity adds the *segment operators* of ``core.segments``:
    ``up()`` is linear (``u = E p + filler``), E duplicates client
    channels into union segments,
  * round state lives on the packed parameter PLANE (``core.plane``):
    the union tree flattens once per round into a contiguous ``(K, P)``
    f32 plane (a static ``PlaneSpec`` records the layout), the four
    parallel coverage trees (mask / filler / aggregation-coverage /
    multiplicity) become four row-aligned planes built once per
    (cohort, seed), participant gathers are row slices (``plane[idx]``)
    instead of per-leaf tree gathers, and round start is the fused
    ``g·m + f·(1−m)`` on planes,
  * local training = ``jax.vmap`` over the unpacked (K, ...) view of the
    plane (pack/unpack are reshape/concat — XLA fuses them away) with
    gradients transformed by ``E Eᵀ`` (per-axis segment sums, 1/c² on
    Net2Net split axes) then mask-projected on the plane — exactly the
    pushforward of the client-shape gradient, so union-space SGD(+
    momentum, from ``repro.optim``) *equals* client-shape SGD. The step
    is jitted ONCE per engine and participating-subset size and DONATES
    the plane buffers (params + optimizer state), so a round trains
    in-place,
  * the client axis (plane rows) is ``shard_map``-ed over a device mesh
    via the ``sharding/rules.py`` machinery (``stacked_client_spec``) —
    local training is embarrassingly parallel over K, so the
    shard-mapped body needs no collectives,
  * aggregation = ONE fused whole-plane kernel pass
    (``kernels/fedavg.plane_agg``: weights, coverage masks,
    multiplicity division, renormalization and fallback substitution in
    a single tiled dispatch — not one per leaf), with the coverage
    semantics single-sourced in ``core.aggregation``.

Partial participation: ``run_round(state, batches, selected=...)`` runs
the round on the ``selected`` ROWS of the plane — weights/masks
renormalize over the subset, per-client rows scatter back,
cluster/prefix aggregation intersects with the participants — so the
engine supports every participation schedule the loop reference does,
bit-compatibly on its exact domain.

Faithfulness (verified in tests/test_unified.py + tests/test_federation.py
against the per-client ``LoopBackend`` reference path; ``UnifiedBackend``
in fl/backends.py is the Federation-facing wrapper around this engine —
DESIGN.md §7):

  * EXACT for depth-heterogeneous cohorts: the filler is a pointwise
    identity in the forward pass, masked gradients keep it constant, and
    aggregating the plane with the filler in place reproduces the
    paper's zero/identity-filler FedAvg literally. Packing changes the
    LAYOUT, not the math: every per-coordinate operation is identical to
    the tree-shaped reference (f32 accumulation; non-f32 leaves are
    re-quantized through their storage dtype each step —
    ``plane.requantize``, a static no-op on all-f32 cohorts).
  * EXACT (to float tolerance) for width-heterogeneous cohorts whose
    embedding is segment-representable (``family.segment_representable``):
    fedadp rounds draw the SAME per-(round, client) To-Wider mappings as
    the loop (``netchange.round_embed_seed``), round start is the
    literal ``up(down(·))`` under the strategy's ``narrow_mode`` — one
    compiled program per client architecture with the round's mappings
    as data, writing its row of the chunk's plane, and the ``E Eᵀ``
    matrices built on the device from segment ids — training keeps the
    stack in image(E) via the segment-projected gradients, and both
    paths read coverage + multiplicity from ``core.aggregation``.

Methods: ``fedadp`` (filler "zero" | "global"), ``clustered``,
``flexifed`` (VGG chain — the common prefix is a COLUMN mask on the
plane, ``PlaneSpec.col_mask``), ``standalone``.

All embedding artifacts (masks, segment matrices, coverage rows) live in
ONE bounded ``netchange.KeyedCache`` shared-sizing with the loop's
``FedADP`` cache; ``cache_stats()`` exposes its counters.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import plane, quant, segments as sg
from repro.core.aggregation import (AGG_MODES, COVERAGE_POLICIES,
                                    client_weights, coverage_and_filler,
                                    default_k_chunk, finish_partials,
                                    global_shapes, loosen, plane_partials,
                                    resolve_agg_layout, stack_trees,
                                    subset_weights)
from repro.core.baselines import _cluster_ids
from repro.core.netchange import (KeyedCache, NARROW_MODES,
                                  round_embed_seed)
from repro.fl import spans
from repro.kernels.fedavg import ops as kops
from repro.kernels.fedavg.fedavg import on_tpu
from repro.optim import sgd
from repro.sharding.ctx import CohortCtx

ENGINE_LAYOUTS = ("auto", "plane", "stream")
COMPUTE_DTYPES = ("f32", "bf16")
ATTN_BACKENDS = ("auto", "flash", "blockwise")


def client_embedding(family, client_cfgs: Sequence, global_cfg, *,
                     seed: int = 0):
    """Stacked (strict masks, filler) for embedding a cohort into
    ``global_cfg`` — per-client trees from
    ``core.aggregation.coverage_and_filler``, stacked on a leading K
    axis."""
    masks, fillers = [], []
    for cfg in client_cfgs:
        m, f = coverage_and_filler(family, cfg, global_cfg, seed=seed)
        masks.append(m)
        fillers.append(f)
    return stack_trees(masks), stack_trees(fillers)


# ---- the engine's hot plane algebra as module-level jitted programs:
# eager versions built a handful of full (K_rows, P) temporaries per call
# (BENCH_new.json showed the plane layout losing to the tree path on CPU
# exactly here); module-level jits also share compile caches across
# engines of the same plane shape
@jax.jit
def _fused_round_start(gp: jnp.ndarray, m: jnp.ndarray, f: jnp.ndarray
                       ) -> jnp.ndarray:
    """Depth-only round start on gathered rows: ``up(down(g))`` is
    literally ``g·m + f·(1−m)`` there."""
    return gp[None, :] * m + f * (1.0 - m)


@jax.jit
def _fold_rows(sp: jnp.ndarray, cov_p: jnp.ndarray, gp: jnp.ndarray
               ) -> jnp.ndarray:
    """filler_mode="global" on gathered rows: substitute the server's
    current values on the coordinates a client does not cover."""
    return sp * cov_p + gp[None, :] * (1.0 - cov_p)


@functools.partial(jax.jit, static_argnames=("fmt", "tile"))
def _wire_encode(x, res, mask, *, fmt: str, tile: int):
    """Error-feedback wire encode of a gathered row chunk (ONE jitted
    program per (fmt, tile, masked?) signature — steady-state rounds
    compile nothing): ``core.quant.encode`` on ``(k_chunk, P)`` rows."""
    return quant.encode(x, res, fmt, tile=tile, mask=mask)


@functools.partial(jax.jit,
                   static_argnames=("renorm", "use_kernel", "fold_global"))
def _plane_agg_fused(sp, w, cov_p, mult_p, gp, *, renorm: bool,
                     use_kernel: bool, fold_global: bool):
    """The whole (sub-)plane aggregation as ONE jitted program:
    ``fold_global`` fuses filler_mode="global"'s uncovered-coordinate
    substitution into the same pass (no eager (K, P) temporaries), then
    a single ``plane_agg`` dispatch."""
    if fold_global:
        sp = sp * cov_p + gp[None, :] * (1.0 - cov_p)
        cov_p = mult_p = gp = None
    return kops.plane_agg(sp, w, masks=cov_p, mult=mult_p, fallback=gp,
                          renorm=renorm, use_kernel=use_kernel)


@dataclass
class UnifiedEngine:
    """Runs FL methods in the packed unified space. See module docstring."""
    family: Any
    client_cfgs: Sequence[Any]
    n_samples: Sequence[int]
    lr: float = 0.01
    momentum: float = 0.0
    method: str = "fedadp"
    filler_mode: str = "zero"            # fedadp only: "zero" | "global"
    agg_mode: str = "filler"             # "filler" (Eq. 1) | "coverage"
    coverage: str = "loose"              # what counts as covered when
                                         # aggregating (core.aggregation)
    narrow_mode: str = "paper"           # fedadp distribute: Alg. 3 | fold
    loss_fn: Optional[Callable] = None   # loss(params, batch) under the
                                         # GLOBAL cfg; default: family's
    use_kernel: Optional[bool] = None    # None = auto (Pallas on TPU)
    mesh: Optional[Mesh] = None          # shard the client axis over this
    client_axes: Tuple[str, ...] = ("clients",)
    embed_seed: int = 0                  # base NetChange seed; fedadp
                                         # rounds derive per-(round, k)
                                         # seeds from it (round_embed_seed)
    agg_layout: str = "auto"             # "auto" | "plane" | "stream":
                                         # whole-plane vs O(P·k_chunk)
                                         # streaming fedadp rounds
    k_chunk: Optional[int] = None        # streaming chunk rows (None=auto)
    wire: str = "f32"                    # client->server payload encoding
                                         # (core.quant): "f32" | "bf16" |
                                         # "int8" — non-f32 rides the
                                         # streaming round path
    wire_tile: int = quant.DEFAULT_TILE  # int8 scale tile (lane multiple)
    wire_sparse: bool = False            # ship covered coords only —
                                         # needs agg_mode="coverage"
    compute_dtype: str = "f32"           # "f32" | "bf16": local-training
                                         # compute policy — the (K, P)
                                         # plane stays f32 master weights,
                                         # params are cast once at unpack
                                         # inside the jitted step and
                                         # grads fold back into f32
                                         # optimizer state
    attn_backend: str = "auto"           # "auto" | "flash" | "blockwise":
                                         # attention backend of the local
                                         # training step (ShardCtx knob;
                                         # transformer families only when
                                         # forced off "auto")
    timing: bool = False                 # wall-clock the training phase
                                         # into phase_stats() (adds a
                                         # sync point per train call —
                                         # benches only, off by default)

    def __post_init__(self):
        if self.agg_layout not in ENGINE_LAYOUTS:
            raise ValueError(
                f"agg_layout={self.agg_layout!r}, expected one of "
                f"{ENGINE_LAYOUTS} (the engine has no per-leaf layout — "
                f"'leaf' lives in core.aggregation only)")
        if self.k_chunk is not None and int(self.k_chunk) < 1:
            raise ValueError(f"k_chunk={self.k_chunk!r}, expected a "
                             f"positive int or None")
        if self.agg_mode not in AGG_MODES:
            raise ValueError(f"agg_mode={self.agg_mode!r}, expected one of "
                             f"{AGG_MODES}")
        if self.coverage not in COVERAGE_POLICIES:
            raise ValueError(f"coverage={self.coverage!r}, expected one of "
                             f"{COVERAGE_POLICIES}")
        if self.narrow_mode not in NARROW_MODES:
            raise ValueError(f"narrow_mode={self.narrow_mode!r}, expected "
                             f"one of {NARROW_MODES}")
        if self.wire not in quant.WIRE_FORMATS:
            raise ValueError(f"wire={self.wire!r}, expected one of "
                             f"{quant.WIRE_FORMATS}")
        quant.validate_tile(self.wire_tile)
        if self.wire != "f32":
            if self.method != "fedadp":
                raise ValueError(
                    f"wire={self.wire!r} compresses the fedadp round "
                    f"payloads; method={self.method!r} does not ship "
                    "plane rows through the wire layer")
            if self.agg_layout == "plane":
                raise ValueError(
                    "wire compression aggregates on the streaming path "
                    "(the fused dequantize-accumulate kernel); "
                    "agg_layout='plane' contradicts it — use 'auto' or "
                    "'stream'")
        if self.wire_sparse:
            if self.wire == "f32":
                raise ValueError("wire_sparse needs a compressed wire "
                                 "(wire='bf16' or 'int8')")
            if self.agg_mode != "coverage":
                raise ValueError(
                    "wire_sparse ships only covered coordinates, which "
                    'is exact only under agg_mode="coverage" (uncovered '
                    "coordinates never enter the masked average); "
                    f"agg_mode={self.agg_mode!r} averages them")
        if self.compute_dtype not in COMPUTE_DTYPES:
            raise ValueError(f"compute_dtype={self.compute_dtype!r}, "
                             f"expected one of {COMPUTE_DTYPES}")
        if self.attn_backend not in ATTN_BACKENDS:
            raise ValueError(f"attn_backend={self.attn_backend!r}, "
                             f"expected one of {ATTN_BACKENDS}")
        self._clock = spans.PhaseClock("train")
        self.global_cfg = self.family.union(list(self.client_cfgs))
        self.weights = client_weights(self.n_samples)
        self._depth_only = self.family.depth_only(list(self.client_cfgs))
        if not self._depth_only:
            rep = getattr(self.family, "segment_representable", None)
            if rep is None or not rep(list(self.client_cfgs)):
                raise ValueError(
                    "unified engine needs a depth-only or segment-"
                    "representable cohort (family.segment_representable); "
                    "use the loop backend for this cohort")
        self._gshapes = global_shapes(self.family, self.global_cfg)
        # the packed layout: one static spec for every plane this engine
        # touches (round state, masks, filler, coverage, multiplicity)
        self.plane_spec = plane.PlaneSpec.from_tree(self._gshapes)
        # the static segment structure (which leaves/axes are widened) is
        # seed-invariant — only the matrix VALUES change per round seed
        if self._depth_only:
            self._axes_map: Dict = {}
        else:
            specs = [self.family.segment_spec(cfg, self.global_cfg,
                                              seed=self.embed_seed)
                     for cfg in self.client_cfgs]
            self._axes_map = sg.union_axes(specs, self._gshapes)
            self._seg_roles = sg.axis_roles(specs, self._axes_map,
                                            self._gshapes)
        self._seg_axes = {"/".join(p): a for p, a in self._axes_map.items()}
        # ONE bounded cache for every embedding artifact — masks, segment
        # matrices, coverage/multiplicity rows, prefix column masks —
        # sharing the sizing rule with the loop's FedADP cache
        self._cache = KeyedCache(n_clients=len(self.client_cfgs))
        # fixed-seed cohort embedding, DEDUPLICATED per unique client
        # config: 100×-scale cohorts repeat a handful of architectures,
        # so the seed-invariant artifacts (strict mask, filler, coverage
        # reading, multiplicity at embed_seed — all functions of the
        # config alone) are built once per UNIQUE config and stored as
        # (U, P) row planes; client k's row is a gather through the uid
        # index. Only the mask store, which every round trains with, is
        # built here; the filler / coverage / multiplicity stores, the
        # full (K, P) planes and the stacked trees are LAZY
        # (cached_property) — at published widths each (U, P) store is
        # over a GB of device memory, and the streaming round path only
        # ever gathers chunk rows, keeping round memory O(P·k_chunk) at
        # any K. The strict mask (and with it the strict coverage
        # reading) is seed-invariant even on width cohorts — To-Wider
        # lands a client parameter on EVERY union channel of a widened
        # axis no matter the mapping.
        uid_of: Dict[Any, int] = {}
        for cfg in self.client_cfgs:
            uid_of.setdefault(cfg, len(uid_of))
        self._uniq_cfgs = list(uid_of)
        self._uid = np.asarray([uid_of[c] for c in self.client_cfgs],
                               np.int32)
        self._uid_jnp = jnp.asarray(self._uid)
        self._umask_p = self._uid_store(lambda mask, filler: mask)
        # the compiled width round start, one program per unique config
        # (``_start_fn``), with its trace and row counters
        self._start_fns: Dict[int, Callable] = {}
        self._start_traces: Dict[int, int] = {}
        self._start_rows = 0
        self._ctx = CohortCtx(mesh=self.mesh, client_axes=self.client_axes,
                              k_chunk=self.k_chunk)
        self._edge_fns: Dict = {}
        self._agg_stats: Dict = {}
        # per-client error-feedback residual plane (K, P) f32 — lazily
        # allocated on the first compressed round; checkpointed by the
        # Federation so resumed runs bit-match (DESIGN.md §10)
        self._wire_res: Optional[jnp.ndarray] = None
        self._wire_stats: Dict = {}
        self.clusters = _cluster_ids(self.client_cfgs)
        if self.method == "flexifed":
            full = tuple(range(len(self.client_cfgs)))
            self._prefix_paths = self._prefix_for(full)
        self._opt = sgd(self.lr, self.momentum)
        self._steps: Dict[int, Callable] = {}
        self._step_traces: Dict[int, int] = {}

    # ----------------------------------------------------------- embedding
    def cache_stats(self) -> dict:
        """Hit/miss/size/bound of the embedding-artifact cache
        (``netchange.KeyedCache`` — one cache, one bound)."""
        return self._cache.stats()

    def step_stats(self) -> dict:
        """Introspection over the per-subset-size jitted steps — the
        engine's known retrace hazard. ``traces[k]`` counts how many
        times the size-``k`` step's Python body was traced (a trace ==
        a jit cache miss; steady-state rounds must add none), and
        ``cache_sizes`` reports jax's own per-function compile-cache
        entry counts where available. ``round_start`` counts the
        compiled width round start: ``traces[uid]`` per unique client
        config, and ``rows`` built since construction. ``analysis.
        retrace`` and the retrace regression test read this."""
        sizes = {}
        for k, f in self._steps.items():
            cs = getattr(f, "_cache_size", None)
            if callable(cs):
                sizes[k] = cs()
        return {"subset_sizes": sorted(self._steps),
                "traces": dict(self._step_traces),
                "cache_sizes": sizes,
                "round_start": {"traces": dict(self._start_traces),
                                "rows": self._start_rows}}

    def _uid_mask(self, u: int):
        """(strict mask, filler, cov) of UNIQUE config ``u`` at the fixed
        ``embed_seed`` — the strict mask is seed-invariant always; filler
        and the loose cov reading are seed-invariant on depth-only
        cohorts (the only place the fixed filler/cov are used for
        fedadp). Built once per unique architecture, not per client."""
        def build():
            mask, filler = coverage_and_filler(
                self.family, self._uniq_cfgs[u], self.global_cfg,
                seed=self.embed_seed)
            cov = mask if self.coverage == "strict" else loosen(mask, filler)
            return (mask, filler, cov)
        return self._cache.get(("mask", "uid", u), build)

    def _client_mask(self, k: int):
        """Client k's (strict mask, filler, cov) — a uid-deduplicated
        view of ``_uid_mask``."""
        return self._uid_mask(int(self._uid[k]))

    def _uid_store(self, fn) -> jnp.ndarray:
        """A ``(U, P)`` store: row u packs ``fn(strict mask, filler)``
        of unique config u at ``embed_seed``, built one config at a time
        so no embedding tree outlives its packed row."""
        rows = []
        for cfg in self._uniq_cfgs:
            mask, filler = coverage_and_filler(
                self.family, cfg, self.global_cfg, seed=self.embed_seed)
            rows.append(plane.pack(fn(mask, filler), self.plane_spec))
        return jnp.stack(rows)

    @functools.cached_property
    def _ufill_p(self):
        return self._uid_store(lambda mask, filler: filler)

    @functools.cached_property
    def _ucov_p(self):
        if self.coverage == "strict":
            return self._umask_p
        return self._uid_store(loosen)

    @functools.cached_property
    def _umult_p(self):
        if self._depth_only:
            return None
        rep = [int(np.argmax(self._uid == u))
               for u in range(len(self._uniq_cfgs))]
        return jnp.stack([plane.pack(self._client_mult(k, self.embed_seed),
                                     self.plane_spec) for k in rep])

    # ---- lazy full-cohort views (tree-facing consumers only): the
    # streaming round path never touches these, so a K=256 engine holds
    # (U, P) per-uid rows, not four (K, P) planes
    @functools.cached_property
    def masks(self):
        return stack_trees([self._client_mask(k)[0]
                            for k in range(len(self.client_cfgs))])

    @functools.cached_property
    def filler(self):
        return stack_trees([self._client_mask(k)[1]
                            for k in range(len(self.client_cfgs))])

    @functools.cached_property
    def cov_masks(self):
        return stack_trees([self._client_mask(k)[2]
                            for k in range(len(self.client_cfgs))])

    @functools.cached_property
    def masks_p(self):
        return self._umask_p[self._uid_jnp]

    @functools.cached_property
    def filler_p(self):
        return self._ufill_p[self._uid_jnp]

    @functools.cached_property
    def cov_p(self):
        return self._ucov_p[self._uid_jnp]

    @functools.cached_property
    def mult_p(self):
        return (None if self._umult_p is None
                else self._umult_p[self._uid_jnp])

    # ---- chunk-row gathers from the per-uid store: ``(len(ks), P)``
    # rows for a participating chunk, never the full plane
    def _uid_rows(self, store: jnp.ndarray, ks: Sequence[int]
                  ) -> jnp.ndarray:
        return store[self._uid_jnp[jnp.asarray(list(ks))]]

    def _place_rows(self, rows: jnp.ndarray) -> jnp.ndarray:
        """Split a ``(k, P)`` training plane over the client mesh as the
        training step reads it (``CohortCtx.row_spec``), so no device
        keeps a whole cohort's copy beside its own rows; unchanged
        without a mesh or when k does not divide."""
        spec = self._ctx.row_spec(int(rows.shape[0]))
        if spec == P():
            return rows
        return jax.device_put(rows, NamedSharding(self.mesh, spec))

    def _mask_rows(self, ks) -> jnp.ndarray:
        return self._place_rows(self._uid_rows(self._umask_p, ks))

    def _filler_rows(self, ks) -> jnp.ndarray:
        return self._uid_rows(self._ufill_p, ks)

    def _cov_rows(self, ks) -> jnp.ndarray:
        return self._uid_rows(self._ucov_p, ks)

    def _mult_rows(self, ks) -> Optional[jnp.ndarray]:
        return (None if self._umult_p is None
                else self._uid_rows(self._umult_p, ks))

    def _client_spec(self, k: int, seed: int):
        """Client k's ``segment_spec`` at one seed — plain numpy segment
        ids, no jnp pushes; bounded LRU."""
        return self._cache.get(
            ("spec", k, seed),
            lambda: self.family.segment_spec(self.client_cfgs[k],
                                             self.global_cfg, seed=seed))

    def _client_mult(self, k: int, seed: int):
        """Client k's multiplicity tree at one seed — union-sized, so
        built only where a multiplicity row is packed, never cached."""
        return sg.multiplicity_tree(self._client_spec(k, seed),
                                    self._gshapes)

    def _client_cov_row(self, k: int, seed: int) -> jnp.ndarray:
        """Client k's aggregation-coverage mask at a round seed as a
        ``(P,)`` row. Strict (and every depth-only) coverage is the
        seed-invariant store row; loose width coverage needs the round's
        filler (widened identity-conv taps move with the mapping) — one
        pair of ``up`` pushes per (client, seed), cached so a repeated
        (round, client) costs a dict hit."""
        if self._depth_only or self.coverage == "strict":
            return self._ucov_p[int(self._uid[k])]

        def build():
            mask, filler = coverage_and_filler(
                self.family, self.client_cfgs[k], self.global_cfg, seed=seed)
            return plane.pack(loosen(mask, filler), self.plane_spec,
                              what="cov_row")
        return self._cache.get(("covrow", k, seed), build)

    def _client_mult_row(self, k: int, seed: int) -> jnp.ndarray:
        """Client k's multiplicity counts at a round seed as a packed
        ``(P,)`` row (width cohorts only)."""
        return self._cache.get(
            ("multrow", k, seed),
            lambda: plane.pack(self._client_mult(k, seed), self.plane_spec,
                               what="mult_row"))

    def _round_seed(self, round_idx: int, k: int) -> int:
        return round_embed_seed(self.embed_seed, round_idx, k)

    def _agg_rows(self, ks: Sequence[int], seeds
                  ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
        """Aggregation-coverage rows of participants ``ks`` and, under
        ``agg_mode="coverage"`` on width cohorts, their multiplicity rows
        — at the round's ``seeds`` (None on depth-only cohorts, whose
        rows are the seed-invariant store's)."""
        if self._depth_only:
            return self._cov_rows(ks), None
        cov = jnp.stack([self._client_cov_row(k, s)
                         for k, s in zip(ks, seeds)])
        mult = (jnp.stack([self._client_mult_row(k, s)
                           for k, s in zip(ks, seeds)])
                if self.agg_mode == "coverage" else None)
        return cov, mult

    # ------------------------------------------------------------- step fn
    def _step_for(self, k_count: int):
        """The packed SGD step for a cohort (or participating subset) of
        ``k_count`` clients — jitted exactly once per subset size, plane
        buffers donated."""
        if k_count not in self._steps:
            self._steps[k_count] = self._build_step(k_count)
        return self._steps[k_count]

    def _train_cfg(self):
        """Model config of the local training step: the union config,
        with its compute dtype flipped under the bf16 policy (the model
        casts activations to ``cfg.dtype``, so the grad fn must be built
        on the bf16 config — the plane itself never leaves f32)."""
        if self.compute_dtype == "bf16":
            import dataclasses as _dc
            return _dc.replace(self.global_cfg, dtype="bfloat16")
        return self.global_cfg

    def _train_ctx(self):
        """ShardCtx override for a forced attention backend (None when
        "auto" — the family's default ctx already auto-selects)."""
        if self.attn_backend == "auto":
            return None
        from repro.sharding.ctx import ShardCtx
        return ShardCtx(attn_backend=self.attn_backend)

    def _build_step(self, k_count: int):
        if self.loss_fn is not None:
            lf = self.loss_fn

            def grads_one(p, b):
                return jax.grad(lf)(p, b)
        else:
            ctx = self._train_ctx()
            try:
                gf = (self.family.loss_and_grad(self._train_cfg())
                      if ctx is None else
                      self.family.loss_and_grad(self._train_cfg(), ctx=ctx))
            except TypeError as e:
                raise ValueError(
                    f"attn_backend={self.attn_backend!r} needs a family "
                    "whose loss_and_grad accepts a ShardCtx (transformer "
                    "families); this one does not") from e

            def grads_one(p, b):
                return gf(p, b)[1]

        opt = self._opt
        seg_axes = self._seg_axes
        spec = self.plane_spec
        cdt = jnp.bfloat16 if self.compute_dtype == "bf16" else None

        def step_core(sp, opt_state, masks_p, seg_mats, batch, step_idx):
            # the plane unpacks to the stacked tree for the model's grad
            # fn (reshape/concat only — fused away under jit), and the
            # update itself happens back on the plane:
            # width: E Eᵀ per leaf keeps the update in image(E) and equal
            # to the client-shape SGD step; depth: the 0/1 mask row keeps
            # the filler constant. The two commute (masks are constant
            # along segment axes).
            params = plane.unpack_stacked(sp, spec)
            if cdt is not None:
                # bf16 compute policy: cast ONCE at unpack — the f32 plane
                # stays the master copy, the whole fwd/bwd runs in bf16,
                # and the grads rejoin the f32 optimizer state below
                params = jax.tree_util.tree_map(
                    lambda x: x.astype(cdt), params)
            grads = jax.vmap(grads_one)(params, batch)
            if cdt is not None:
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32), grads)
            grads = sg.project_stacked(grads, seg_axes, seg_mats)
            gp = plane.pack_stacked(grads, spec) * masks_p
            new_sp, new_state = opt.update(gp, opt_state, sp, step_idx)
            # reproduce the tree path's per-step storage rounding for
            # non-f32 leaves (static no-op on all-f32 cohorts)
            return plane.requantize(new_sp, spec), new_state

        fn = step_core
        if self.mesh is not None:
            pspec = self._ctx.row_spec(k_count)
            if pspec != P():
                # local training is independent per client: every operand
                # carries the K axis (plane rows, mask rows, stacked
                # matrices, batch), the body needs no collectives.
                fn = jax.shard_map(step_core, mesh=self.mesh,
                                   in_specs=(pspec, pspec, pspec, pspec,
                                             pspec, P()),
                                   out_specs=(pspec, pspec), check_vma=False)
        inner = fn

        def train_step(sp, opt_state, masks_p, seg_mats, batch, step_idx):
            # this Python body runs only when jit (re)traces — i.e. on a
            # compile-cache miss — so the counter measures retraces
            self._step_traces[k_count] = \
                self._step_traces.get(k_count, 0) + 1
            return inner(sp, opt_state, masks_p, seg_mats, batch, step_idx)

        # the round state is consumed step-over-step: donating the plane
        # and the optimizer-state plane lets XLA update them in place;
        # the function's name names the program (``jit_train_step``) in
        # profiler traces
        return jax.jit(train_step, donate_argnums=(0, 1))

    # ------------------------------------------------------------- subsets
    def _resolve(self, selected) -> Optional[list]:
        """None = full participation; otherwise the participating subset."""
        if selected is None:
            return None
        sel = list(selected)
        return None if sel == list(range(len(self.client_cfgs))) else sel

    @staticmethod
    def _rows(plane_arr, selected):
        """Participant gather on a plane = ONE row slice."""
        if plane_arr is None or selected is None:
            return plane_arr
        return plane_arr[jnp.asarray(list(selected))]

    @staticmethod
    def _gather(tree, selected):
        if selected is None:
            return tree
        idx = jnp.asarray(selected)
        return jax.tree.map(lambda x: x[idx], tree)

    @staticmethod
    def _scatter(tree, selected, sub):
        if selected is None:
            return sub
        idx = jnp.asarray(selected)
        return jax.tree.map(lambda t, s: t.at[idx].set(s), tree, sub)

    # ----------------------------------------------------------- embedding
    def init_global(self, key):
        return self.family.init(key, self.global_cfg)

    def _round_start_packed(self, gp: jnp.ndarray, selected=None
                            ) -> jnp.ndarray:
        """Depth-only round start, fused on planes: ``up(down(g))`` is
        literally ``g·m + f·(1−m)`` there — one jitted broadcast over
        the uid-gathered mask/filler rows instead of a per-leaf
        tree-map."""
        ks = (range(len(self.client_cfgs)) if selected is None
              else list(selected))
        return _fused_round_start(gp, self._mask_rows(ks),
                                  self._filler_rows(ks))

    def round_start(self, global_params, selected=None, round_idx: int = 0):
        """Stacked per-client views of a global model: the unified-space
        equivalent of FedADP's distribute (To-Shallower/To-Narrower),
        restricted to the participating subset when given. Depth-only
        cohorts use the fused packed mask/filler arithmetic
        (``_round_start_packed``); width cohorts the compiled literal
        ``up(down(g))`` at the round's seeds under ``narrow_mode``
        (``_width_start``) — the same NetChange work the loop's
        distribute + collect would do, with training still stacked."""
        gp = plane.pack(global_params, self.plane_spec)
        if self._depth_only:
            rows = self._round_start_packed(gp, selected)
        else:
            ks = (list(range(len(self.client_cfgs))) if selected is None
                  else list(selected))
            rows = self._width_start(
                gp, ks, [self._round_seed(round_idx, k) for k in ks])[0]
        return plane.unpack_stacked(rows, self.plane_spec)

    def _start_fn(self, u: int) -> Callable:
        """Unique config ``u``'s width round start as ONE jitted program:
        ``pack(up(down(unpack(gp))))`` with the round's To-Wider
        mappings passed in as data (``family.width_mappings``), written
        into row ``j`` of the donated ``(k, P)`` chunk buffer. A new
        seed is new data, not a new program: compiles scale with the
        cohort's architectures (and chunk sizes), never with rounds."""
        if u not in self._start_fns:
            family, gcfg, cfg = self.family, self.global_cfg, \
                self._uniq_cfgs[u]
            spec, mode = self.plane_spec, self.narrow_mode

            def start_row(buf, gp, maps, j):
                # runs only when jit traces: counts this program's compiles
                self._start_traces[u] = self._start_traces.get(u, 0) + 1
                down = family.down(plane.unpack(gp, spec), gcfg, cfg,
                                   mode=mode, mappings=maps)
                row = plane.pack(family.up(down, cfg, gcfg, mappings=maps),
                                 spec, what="round_start")
                return jax.lax.dynamic_update_index_in_dim(buf, row, j, 0)

            self._start_fns[u] = jax.jit(start_row, donate_argnums=(0,))
        return self._start_fns[u]

    def _width_start(self, gp: jnp.ndarray, ks: Sequence[int], seeds
                     ) -> Tuple[jnp.ndarray, Dict, int]:
        """Width round start of participants ``ks`` at their round
        ``seeds``: the ``(k, P)`` rows (one ``_start_fn`` call each),
        their stacked ``E Eᵀ`` gradient matrices (built on the device
        from segment ids, ``segments.grad_matrices``), and the bytes the
        host sent — the mappings, row indices and ids, a few KB a row."""
        buf = jnp.zeros((len(ks), self.plane_spec.size), jnp.float32)
        sent = 0
        for j, (k, s) in enumerate(zip(ks, seeds)):
            maps = self.family.width_mappings(self.client_cfgs[k],
                                              self.global_cfg, seed=s)
            buf = self._start_fn(int(self._uid[k]))(buf, gp, maps,
                                                     np.int32(j))
            sent += 4 + sum(m.nbytes for m in maps.values())
        self._start_rows += len(ks)
        mats, id_bytes = self._grad_mats(ks, seeds)
        return self._place_rows(buf), mats, sent + id_bytes

    def _grad_mats(self, ks: Sequence[int], seeds) -> Tuple[Dict, int]:
        """Participants ``ks``' stacked ``E Eᵀ`` gradient matrices at
        ``seeds``, built on the device from their stacked segment ids;
        and the ids' bytes."""
        ids = [sg.segment_ids(self._client_spec(k, s), self._axes_map,
                              self._gshapes) for k, s in zip(ks, seeds)]
        stacked = {p: [np.stack([c[p][i] for c in ids])
                       for i in range(len(v))] for p, v in ids[0].items()}
        return (sg.grad_matrices(stacked, roles=self._seg_roles),
                sum(a.nbytes for v in stacked.values() for a in v))

    @functools.cached_property
    def _seg_mats0(self) -> Dict:
        """The fixed-seed cohort's stacked ``E Eᵀ`` matrices (the
        per-client-state methods and ``train_round``)."""
        if self._depth_only:
            return {}
        k = len(self.client_cfgs)
        return self._grad_mats(range(k), [self.embed_seed] * k)[0]

    def embed(self, client_params: Sequence):
        """Stack per-client (client-space) trees into the unified space
        at the FIXED ``embed_seed`` — the per-client-state layout, where
        same-architecture clients must share one mapping so cluster and
        prefix averages commute with the embedding."""
        return stack_trees([
            self.family.up(p, cfg, self.global_cfg, seed=self.embed_seed)
            for p, cfg in zip(client_params, self.client_cfgs)])

    def client_view(self, stacked, k: int):
        return jax.tree.map(lambda x: x[k], stacked)

    # ------------------------------------------------------------ training
    def _train_packed(self, sp: jnp.ndarray, stacked_batches: Sequence,
                      masks_p: jnp.ndarray, seg_mats) -> jnp.ndarray:
        """One local-training round on the packed plane: fresh optimizer
        state (matching the per-client loop, which re-inits SGD momentum
        every round), one donated jitted step per stacked batch."""
        rows = int(sp.shape[0])
        with self._clock.span(spans.TRAIN, "train", self.timing, rows=rows,
                              steps=len(stacked_batches)) as sync:
            step = self._step_for(rows)
            opt_state = self._opt.init(sp)
            for i, batch in enumerate(stacked_batches):
                with spans.span(spans.STEP,
                                bytes=spans.host_bytes(batch)):
                    sp, opt_state = step(sp, opt_state, masks_p, seg_mats,
                                         batch, jnp.asarray(i, jnp.int32))
            sync.append(sp)
        return sp

    def phase_stats(self, reset: bool = False):
        """Cumulative host-clock seconds per round phase (``timing=True``
        only; ``train`` = the ``fedadp.train`` spans, each synced at its
        end: the donated jitted local-training steps, every layout and
        chunk included)."""
        return self._clock.stats(reset)

    def _train_packed_chunked(self, sp: jnp.ndarray,
                              stacked_batches: Sequence,
                              masks_p: jnp.ndarray, seg_mats,
                              k_chunk: int) -> jnp.ndarray:
        """``_train_packed`` in ``k_chunk``-row chunks: the per-client
        -state methods must keep the full ``(K, P)`` state anyway, but
        chunking bounds the TRAINING working set (grads + donated
        optimizer plane) to O(P·k_chunk), and equal chunk sizes reuse
        one per-size jitted step."""
        parts = []
        for lo, hi in plane.chunk_bounds(int(sp.shape[0]), k_chunk):
            parts.append(self._train_packed(
                sp[lo:hi],
                [jax.tree.map(lambda a: a[lo:hi], b)
                 for b in stacked_batches],
                masks_p[lo:hi],
                jax.tree.map(lambda a: a[lo:hi], seg_mats)))
        return jnp.concatenate(parts, axis=0)

    def train_round(self, stacked, stacked_batches: Sequence, *, masks=None,
                    seg_mats=None):
        """Tree-facing wrapper over ``_train_packed``: packs the stacked
        tree (and mask tree, when given) once, trains on the plane,
        unpacks once. ``masks``/``seg_mats`` default to the fixed-seed
        full-cohort embedding; pass gathered/per-round values for
        partial or fedadp width rounds."""
        masks_p = (self.masks_p if masks is None
                   else plane.pack_stacked(masks, self.plane_spec,
                                           what="train_round/masks"))
        seg_mats = self._seg_mats0 if seg_mats is None else seg_mats
        sp = plane.pack_stacked(stacked, self.plane_spec,
                                what="train_round")
        return plane.unpack_stacked(
            self._train_packed(sp, stacked_batches, masks_p, seg_mats),
            self.plane_spec)

    # --------------------------------------------------------- aggregation
    def _use_kernel(self) -> bool:
        return on_tpu() if self.use_kernel is None else bool(self.use_kernel)

    def agg_stats(self) -> dict:
        """Accounting of the LAST aggregation pass — layout, row count,
        and ``peak_bytes`` (the resident aggregation working set: the
        whole ``(K, P)`` sub-plane for layout "plane"; three ``(P,)``
        buffers + one ``(k_chunk, P)`` chunk for "stream" —
        ``PlaneAccumulator.stats``). The bench's peak-memory column and
        the O(P·k_chunk) envelope test read this."""
        return dict(self._agg_stats)

    def wire_stats(self) -> dict:
        """Byte accounting of the LAST compressed round (empty when
        ``wire="f32"``): payload ``bytes_per_round`` (values + int8
        scale grids, covered coordinates only under ``wire_sparse``),
        the dense-f32 baseline, and the reduction factor."""
        return dict(self._wire_stats)

    def wire_residuals(self) -> Optional[jnp.ndarray]:
        """The per-client error-feedback residual plane ``(K, P)`` f32 —
        ``None`` until a compressed round has run (or when
        ``wire="f32"``). What the Federation checkpoints."""
        return self._wire_res

    def load_wire_residuals(self, arr):
        """Restore a checkpointed residual plane (resume path)."""
        arr = jnp.asarray(arr, jnp.float32)
        want = (len(self.client_cfgs), self.plane_spec.size)
        if tuple(arr.shape) != want:
            raise ValueError(f"wire residual plane has shape "
                             f"{tuple(arr.shape)}, engine expects {want}")
        self._wire_res = arr

    def _wire_cov_count(self, k: int, seed) -> int:
        """Covered-coordinate count of client k's aggregation-coverage
        row (the sparse wire's payload length) — cached per (uid, seed)
        so steady-state rounds do no device syncs."""
        key = (("covcount", "uid", int(self._uid[k]))
               if (self._depth_only or self.coverage == "strict")
               else ("covcount", k, seed))
        return self._cache.get(
            key, lambda: int(np.asarray(
                jnp.sum(self._client_cov_row(k, 0 if seed is None
                                             else seed)))))

    def _aggregate_packed(self, sp: jnp.ndarray, w, gp=None, cov_p=None,
                          mult_p=None) -> jnp.ndarray:
        """FedADP Eq. 1-2 over the (sub-)plane in ONE fused jitted pass
        (``_plane_agg_fused`` → ``kernels/fedavg.plane_agg``) — weights
        already renormalized over the participating subset by the
        caller."""
        w = jnp.asarray(w, jnp.float32)
        self._agg_stats = {
            "layout": "plane", "k_chunk": None,
            "rows": int(sp.shape[0]), "n": int(sp.shape[1]),
            "peak_bytes": 4 * int(sp.shape[0]) * int(sp.shape[1])}
        uk = self._use_kernel()
        if self.agg_mode == "coverage":
            assert gp is not None, \
                'agg_mode="coverage" needs the current global params'
            return _plane_agg_fused(sp, w, cov_p, mult_p, gp, renorm=True,
                                    use_kernel=uk, fold_global=False)
        if self.filler_mode == "global":
            assert gp is not None
            return _plane_agg_fused(sp, w, cov_p, None, gp, renorm=True,
                                    use_kernel=uk, fold_global=True)
        return _plane_agg_fused(sp, w, None, None, None, renorm=True,
                                use_kernel=uk, fold_global=False)

    def _edge_fn(self, k_count: int, pspec, has_mask: bool, has_mult: bool,
                 fold: bool):
        """Build (once per signature) the shard-mapped edge reduce: each
        device runs the pure-jnp ``aggregation.plane_partials`` on its
        LOCAL rows, a ``psum`` over the client axes is the global reduce
        — exact by associativity, no gather of the full plane on any
        device."""
        axes = (self.client_axes if len(self.client_axes) > 1
                else self.client_axes[0])

        def psum3(trip):
            return tuple(jax.lax.psum(t, axes) for t in trip)

        if fold:
            def body(sp, w, cov_p, gp):
                folded = sp * cov_p + gp[None, :] * (1.0 - cov_p)
                return psum3(plane_partials(folded, w))
            in_specs = (pspec, pspec, pspec, P())
        elif has_mult:
            def body(sp, w, cov_p, mult_p):
                return psum3(plane_partials(sp, w, cov_p, mult_p))
            in_specs = (pspec, pspec, pspec, pspec)
        elif has_mask:
            def body(sp, w, cov_p):
                return psum3(plane_partials(sp, w, cov_p))
            in_specs = (pspec, pspec, pspec)
        else:
            def body(sp, w):
                return psum3(plane_partials(sp, w))
            in_specs = (pspec, pspec)
        return jax.jit(jax.shard_map(body, mesh=self.mesh, in_specs=in_specs,
                                     out_specs=(P(), P(), P()),
                                     check_vma=False))

    def _edge_reduce_packed(self, sp: jnp.ndarray, w, gp=None, cov_p=None,
                            mult_p=None) -> Optional[jnp.ndarray]:
        """Two-level hierarchical aggregation over the cohort mesh
        (DESIGN.md §9): sub-cohort "edge" reducers (one per mesh slot of
        the client axes) pre-reduce their rows to partial
        (num, den, cov) triples, the psum combines them, and ONE
        replicated finish pass closes. Weights are the GLOBAL subset
        weights — per-edge renormalization would be wrong and never
        happens. Returns ``None`` when the rows don't shard over the
        mesh (caller falls back to the flat fused pass)."""
        if self.mesh is None:
            return None
        k_count = int(sp.shape[0])
        pspec = self._ctx.row_spec(k_count)
        if pspec == P():
            return None
        coverage = self.agg_mode == "coverage"
        fold = (not coverage) and self.filler_mode == "global"
        has_mask = coverage and cov_p is not None
        has_mult = coverage and mult_p is not None
        key = (k_count, has_mask, has_mult, fold)
        if key not in self._edge_fns:
            self._edge_fns[key] = self._edge_fn(k_count, pspec, has_mask,
                                                has_mult, fold)
        fn = self._edge_fns[key]
        w = jnp.asarray(w, jnp.float32)
        if fold:
            trip = fn(sp, w, cov_p, gp)
        elif has_mult:
            trip = fn(sp, w, cov_p, mult_p)
        elif has_mask:
            trip = fn(sp, w, cov_p)
        else:
            trip = fn(sp, w)
        self._agg_stats = {
            "layout": "edge", "k_chunk": None, "rows": k_count,
            "n": int(sp.shape[1]), "edges": self._ctx.edge_extent,
            "peak_bytes": 4 * int(sp.shape[1]) * (
                3 + -(-k_count // max(self._ctx.edge_extent, 1)))}
        return finish_partials(*trip, renorm=coverage,
                               fallback=gp if coverage else None)

    def aggregate_global(self, stacked, global_params=None, selected=None,
                         *, cov=None, mult=None):
        """FedADP Eq. 1-2 over the (sub-)stacked tree, weights
        renormalized over the participating subset.

        ``agg_mode="filler"``: filler_mode="zero" keeps the filler
        constants in the average (the paper's rule — exactly what
        averaging ``up()`` outputs does); "global" (FedADP-U) substitutes
        the server's current values on UNCOVERED coordinates, where
        covered is read from ``core.aggregation.coverage_mask`` under the
        engine's ``coverage`` policy — the same mask the loop reference
        uses, so the two paths agree by construction.

        ``agg_mode="coverage"``: the HeteroFL-style average — each
        coordinate over only the clients that cover it, per-coordinate
        weight renormalization (multiplicity-aware on width cohorts:
        W_k/m_k per duplicated coordinate), server values where no
        participant covers.

        Tree-facing wrapper: packs once, runs the ONE fused plane pass
        (``_aggregate_packed``), unpacks once. ``cov``/``mult`` override
        the fixed-seed embedding's masks for per-round-seeded fedadp
        width rounds.
        """
        spec = self.plane_spec
        w = subset_weights(self.n_samples, selected)
        sp = plane.pack_stacked(stacked, spec, what="aggregate_global")
        need_global = (self.agg_mode == "coverage"
                       or self.filler_mode == "global")
        gp = (plane.pack(global_params, spec, what="aggregate_global/"
                         "global") if global_params is not None
              and need_global else None)
        cov_p = mult_p = None
        if need_global:
            if self.agg_mode == "coverage":
                assert global_params is not None, \
                    'agg_mode="coverage" needs the current global params'
            cov_p = (plane.pack_stacked(cov, spec, what="aggregate_global/"
                                        "cov") if cov is not None
                     else self._rows(self.cov_p, selected))
            if self.agg_mode == "coverage":
                mult_p = (plane.pack_stacked(mult, spec,
                                             what="aggregate_global/mult")
                          if mult is not None
                          else self._rows(self.mult_p, selected))
        return plane.unpack(
            self._aggregate_packed(sp, w, gp, cov_p, mult_p), spec)

    def _agg_clustered_p(self, sp: jnp.ndarray, selected=None
                         ) -> jnp.ndarray:
        """Per-cluster FedAvg on the plane: each (cluster ∩ participants)
        aggregates with one row-sliced ``plane_agg`` pass and broadcasts
        back onto its rows; non-participants keep their rows."""
        sel = (set(range(len(self.client_cfgs))) if selected is None
               else set(selected))
        new = sp
        for ids in self.clusters.values():
            ids = [i for i in ids if i in sel]
            if not ids:
                continue
            idx = jnp.asarray(ids)
            agg = kops.plane_agg(sp[idx],
                                 jnp.asarray(subset_weights(self.n_samples,
                                                            ids),
                                             jnp.float32),
                                 use_kernel=self.use_kernel)
            new = new.at[idx].set(
                jnp.broadcast_to(agg[None, :], (len(ids), sp.shape[1])))
        return new

    def _flexifed_prefix_paths(self, sel):
        """Chain positions shared by the WHOLE participating subset (same
        layer id) — FlexiFed's common prefix, computed from configs
        alone. The tree paths come from the CLIENTS' chains (identical
        across the subset wherever the ids agree, and preserved by the
        front-aligned embedding); indexing into the union's chain instead
        would mis-map whenever the subset's prefix extends beyond the
        full cohort's. Layer ids carry widths, so the prefix stops at
        the first width divergence; on the prefix every participant's
        embedding is the same operator (same tag/widths/fixed seed), so
        averaging embedded prefixes equals embedding the averaged
        prefix."""
        chains = [self.family.chain_paths(self.client_cfgs[i]) for i in sel]
        paths = set()
        for pos in range(min(len(c) for c in chains)):
            if len({c[pos][0] for c in chains}) == 1:
                paths.add(chains[0][pos][1])
            else:
                break
        return paths

    def _prefix_for(self, sel) -> set:
        key = tuple(sel)
        return self._cache.get(("prefix", key),
                               lambda: self._flexifed_prefix_paths(key))

    def _prefix_cols(self, sel) -> jnp.ndarray:
        """The FlexiFed common prefix as a 0/1 COLUMN mask on the plane
        (``PlaneSpec.col_mask``) — prefix substitution becomes one fused
        arithmetic expression instead of a per-leaf path walk."""
        key = tuple(sel)

        def build():
            prefix = self._prefix_for(key)
            return jnp.asarray(self.plane_spec.col_mask(
                lambda path: any(path[:len(pp)] == pp for pp in prefix)))
        return self._cache.get(("prefixcols", key), build)

    def _agg_flexifed_p(self, sp: jnp.ndarray, selected=None
                        ) -> jnp.ndarray:
        """Common prefix averaged over the PARTICIPANTS, remainder within
        (same-architecture cluster ∩ participants) — Clustered-Common.
        Non-participants keep their rows."""
        sel = (list(range(len(self.client_cfgs))) if selected is None
               else list(selected))
        idx = jnp.asarray(sel)
        glob = kops.plane_agg(sp[idx],
                              jnp.asarray(subset_weights(self.n_samples,
                                                         sel), jnp.float32),
                              use_kernel=self.use_kernel)
        clus = self._agg_clustered_p(sp, sel)
        cm = self._prefix_cols(sel)
        sub = clus[idx]
        return clus.at[idx].set(sub * (1.0 - cm) + glob[None, :] * cm)

    # ---------------------------------------------------------- full round
    def run_round(self, state, stacked_batches: Sequence, selected=None,
                  round_idx: int = 0):
        """One federated round over the participating subset (default:
        full cohort). ``state`` is the global tree for fedadp and the
        stacked client tree for the per-client-parameter methods; returns
        the same kind. ``stacked_batches`` leaves carry a leading axis of
        ``len(selected)`` (participants only, in ``selected`` order).
        ``round_idx`` seeds fedadp's per-round To-Wider mappings (the
        loop's ``FedADP._seed`` numbers — identical on both paths).

        The round state is packed ONCE on entry and unpacked ONCE on
        exit; everything between — round start, training steps (donated
        buffers), participant gathers (row slices), aggregation (one
        fused kernel pass) — happens on the plane."""
        sel = self._resolve(selected)
        spec = self.plane_spec
        if self.method == "fedadp":
            ks = (list(range(len(self.client_cfgs))) if sel is None
                  else list(sel))
            layout = resolve_agg_layout(self.agg_layout, k=len(ks),
                                        p=spec.size, k_chunk=self.k_chunk)
            # a compressed wire ALWAYS streams: the fused dequantize-
            # accumulate kernel is the only consumer of int8 chunks, and
            # bf16 chunks ride the same casting accumulate
            if layout == "stream" or self.wire != "f32":
                return self._run_fedadp_stream(state, stacked_batches, sel,
                                               round_idx)
            w = subset_weights(self.n_samples, sel)
            gp = plane.pack(state, spec, what="run_round/state")
            need_cov = (self.agg_mode == "coverage"
                        or self.filler_mode == "global")
            path = "fused" if self._depth_only else "width"
            with spans.span(spans.ROUND_START, rows=len(ks),
                            path=path) as span:
                m_rows = self._mask_rows(ks)      # seed-invariant rows
                if self._depth_only:
                    seeds, seg_mats = None, {}
                    start = self._round_start_packed(gp, sel)
                else:
                    seeds = [self._round_seed(round_idx, k) for k in ks]
                    start, seg_mats, sent = self._width_start(gp, ks, seeds)
                    span.set_metadata(bytes=sent)
            trained = self._train_packed(start, stacked_batches, m_rows,
                                         seg_mats)
            cov_p = mult_p = None
            if need_cov:
                with spans.span(spans.ROUND_START, rows=len(ks), path=path):
                    cov_p, mult_p = self._agg_rows(ks, seeds)
            with spans.span(spans.AGGREGATE, rows=len(ks)):
                out = self._edge_reduce_packed(
                    trained, w, gp if need_cov else None, cov_p, mult_p)
                if out is None:
                    out = self._aggregate_packed(
                        trained, w, gp if need_cov else None, cov_p, mult_p)
            return plane.unpack(out, spec)
        # per-client-state methods: the stacked tree packs to (K, P),
        # participants are row slices, and the state scatters back as rows
        sp = plane.pack_stacked(state, spec, what="run_round/state")
        ks = (list(range(len(self.client_cfgs))) if sel is None
              else list(sel))
        masks_p = self._mask_rows(ks)
        seg_mats = self._gather(self._seg_mats0, sel)
        if self.k_chunk is not None:
            trained = self._train_packed_chunked(
                self._rows(sp, sel), stacked_batches, masks_p, seg_mats,
                default_k_chunk(len(ks), self.k_chunk))
        else:
            trained = self._train_packed(self._rows(sp, sel),
                                         stacked_batches, masks_p, seg_mats)
        if sel is None:
            new = trained
        else:
            new = sp.at[jnp.asarray(sel)].set(trained)
        if self.method == "clustered":
            new = self._agg_clustered_p(new, sel)
        elif self.method == "flexifed":
            new = self._agg_flexifed_p(new, sel)
        elif self.method != "standalone":
            raise ValueError(self.method)
        return plane.unpack_stacked(new, spec)

    def _run_fedadp_stream(self, state, stacked_batches: Sequence, sel,
                           round_idx: int):
        """The streaming fedadp round (DESIGN.md §9): the participating
        cohort is consumed in ``k_chunk``-row chunks — round start, local
        training and the aggregation UPDATE all happen per chunk, so no
        more than one ``(k_chunk, P)`` slab of round state is ever
        resident (plus the accumulator's three ``(P,)`` buffers);
        ``finish`` closes with the one divide/fallback pass. Identical
        math to the whole-plane round for every agg/filler mode (the
        masked weighted sum splits associatively; weights stay the GLOBAL
        subset weights), verified to 1e-6 in tests/test_streaming.py.
        Chunks of equal size reuse one per-size jitted training step and
        one accumulate program — steady-state rounds compile nothing
        (tests/test_retrace.py)."""
        spec = self.plane_spec
        ks = (list(range(len(self.client_cfgs))) if sel is None
              else list(sel))
        w = subset_weights(self.n_samples, sel)
        gp = plane.pack(state, spec, what="run_round/state")
        kc = default_k_chunk(len(ks), self.k_chunk)
        coverage = self.agg_mode == "coverage"
        fold = (not coverage) and self.filler_mode == "global"
        wire = self.wire
        if wire != "f32" and (self._wire_res is None or round_idx == 0):
            # round 0 = a FRESH run: residuals start at zero. The engine
            # (and its residual plane) outlives a Federation.run, so a
            # second run on the same backend must not inherit the first
            # one's error feedback; a resume (round_idx > 0) keeps what
            # load_wire_residuals restored.
            self._wire_res = jnp.zeros((len(self.client_cfgs), spec.size),
                                       jnp.float32)
        acc = kops.PlaneAccumulator(
            spec.size, use_kernel=self._use_kernel(), k_hint=kc,
            q_tile=self.wire_tile if wire == "int8" else None,
            mesh=self.mesh, axes=self.client_axes)
        payload_bytes = 0
        path = "fused" if self._depth_only else "width"
        trained = None
        for lo, hi in plane.chunk_bounds(len(ks), kc):
            cks = ks[lo:hi]
            if trained is not None and not self._depth_only:
                # the runtime reserves a program's outputs when it is
                # enqueued: this chunk's mask rows, start rows and
                # optimizer state, enqueued while the previous chunk
                # trains, sit beside its working set (the paper cohort's
                # peak rose 1.24 GB on a TPU v5e). The compiled width
                # start costs the host about a millisecond a row, so
                # enqueue it once the previous chunk's training is done,
                # and let go of its rows (its accumulate is enqueued)
                jax.block_until_ready(trained)
                trained = vals = m_rows = None
            with spans.span(spans.ROUND_START, rows=len(cks),
                            path=path) as span:
                m_rows = self._mask_rows(cks)
                if self._depth_only:
                    seeds = None
                    seg_mats: Dict = {}
                    start = _fused_round_start(gp, m_rows,
                                               self._filler_rows(cks))
                else:
                    seeds = [self._round_seed(round_idx, k) for k in cks]
                    start, seg_mats, sent = self._width_start(gp, cks,
                                                              seeds)
                    span.set_metadata(bytes=sent)
            trained = self._train_packed(
                start,
                [jax.tree.map(lambda a: a[lo:hi], b)
                 for b in stacked_batches],
                m_rows, seg_mats)
            wk = jnp.asarray(w[lo:hi], jnp.float32)
            cov_rows = mult_rows = None
            if coverage or fold:
                with spans.span(spans.ROUND_START, rows=len(cks),
                                path=path):
                    cov_rows, mult_rows = self._agg_rows(cks, seeds)
            vals = trained
            if wire != "f32":
                # error-feedback encode the chunk for the wire: the
                # residual rows gather/scatter by client index, the
                # payload aggregates through the fused dequantize-
                # accumulate kernel (int8) or the casting accumulate
                # (bf16) — the f32 cohort never materializes
                idx = jnp.asarray(cks)
                vals, scales, new_res = _wire_encode(
                    trained, self._wire_res[idx],
                    cov_rows if self.wire_sparse else None,
                    fmt=wire, tile=self.wire_tile)
                self._wire_res = self._wire_res.at[idx].set(new_res)
                counts = ([self._wire_cov_count(
                               k, None if seeds is None else s)
                           for k, s in zip(cks, seeds or cks)]
                          if self.wire_sparse else None)
                for j, k in enumerate(cks):
                    payload_bytes += quant.payload_nbytes(
                        wire, spec.size, tile=self.wire_tile,
                        covered=None if counts is None else counts[j])
            with spans.span(spans.AGGREGATE, rows=len(cks)):
                if wire == "int8":
                    if coverage:
                        acc.update_q(vals, scales, wk, masks=cov_rows,
                                     mult=mult_rows)
                    elif fold:
                        acc.update_q(vals, scales, wk, masks=cov_rows,
                                     base=gp)
                    else:
                        acc.update_q(vals, scales, wk)
                elif coverage:
                    acc.update(vals, wk, masks=cov_rows, mult=mult_rows)
                elif fold:
                    acc.update(_fold_rows(vals, cov_rows, gp), wk)
                else:
                    acc.update(vals, wk)
        with spans.span(spans.AGGREGATE, rows=len(ks)):
            out = acc.finish(renorm=coverage,
                             fallback=gp if coverage else None)
        self._agg_stats = {"layout": "stream", "k_chunk": kc,
                           **acc.stats()}
        if wire != "f32":
            f32_bytes = len(ks) * spec.size * 4
            self._wire_stats = {
                "wire": wire, "tile": self.wire_tile,
                "sparse": self.wire_sparse, "rows": len(ks),
                "bytes_per_round": int(payload_bytes),
                "f32_bytes": int(f32_bytes),
                "reduction": f32_bytes / max(payload_bytes, 1)}
        return plane.unpack(out, spec)
