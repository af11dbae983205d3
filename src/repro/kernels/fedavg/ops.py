"""Jitted public wrappers: aggregate arbitrary-shaped stacked tensors."""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.fedavg import ref
from repro.kernels.fedavg.fedavg import (LANE, Q_TEMPS, on_tpu,
                                         plane_accum_2d,
                                         plane_accum_q_2d, plane_agg_2d,
                                         plane_finish_2d, select_block,
                                         weighted_sum_2d,
                                         weighted_sum_masked_2d,
                                         weighted_sum_masked_mult_2d)


def _flatten_pad(stacked):
    """(K, *shape) -> lane-padded (K, N) plus the original (n, shape)."""
    K = stacked.shape[0]
    shape = stacked.shape[1:]
    n = math.prod(shape) if shape else 1
    flat = stacked.reshape(K, n)
    pad = (-n) % LANE
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    return flat, n, shape


def _block_for(n_flat: int, block: int) -> int:
    blk = min(block, n_flat)
    while n_flat % blk:
        blk //= 2
    return max(blk, LANE) if n_flat >= LANE else n_flat


def _row_bytes(*rows):
    """Itemsizes of the ``(K, n)`` operands a kernel streams (absent
    optional operands skipped) — ``select_block``'s ``row_bytes``."""
    return tuple(jnp.dtype(r.dtype).itemsize for r in rows if r is not None)


def _tile(n: int, block: int, unit: int = LANE):
    """``(block, pad)`` of a kernel call over ``n`` columns: the tile
    rounded up to ``unit`` (a lane multiple) but no wider than the plane,
    so the grid's last tile is the only ragged one and the operands are
    never copied; planes narrower than one ``unit`` are zero-padded to
    it (``pad`` columns, uncovered by construction, sliced away)."""
    if n < unit:
        return unit, unit - n
    return min(-(-block // unit) * unit, n // unit * unit), 0


def _pad_cols(a, pad: int):
    if not pad:
        return a
    width = [(0, 0)] * (a.ndim - 1) + [(0, pad)]
    return jnp.pad(a, width)


# the jnp oracle as ONE jitted program (CPU/GPU hot path): the eager
# call used to build ~6 full (K, P) temporaries per aggregation — jit
# fuses them and was the plane layout's missing CPU win (BENCH_new.json
# showed plane losing to the tree path exactly on this path)
_plane_agg_ref_jit = jax.jit(
    lambda plane, w, masks, mult, fallback, renorm: ref.plane_agg_ref(
        plane, w, masks=masks, mult=mult, fallback=fallback, renorm=renorm),
    static_argnums=(5,))


def plane_agg(plane, w, *, masks=None, mult=None, fallback=None,
              renorm: bool = True, block: Optional[int] = None,
              interpret: Optional[bool] = None,
              use_kernel: Optional[bool] = None):
    """Aggregate a packed ``(K, P)`` parameter plane in ONE pass:
    ``plane_agg(x, w) -> (P,)`` fp32.

    The whole-cohort realization of ``fedavg_stacked``'s math on the
    packed layout (``core.plane``): plain Eq. 1 without ``masks``;
    coverage-weighted with them (renormalized over the covering subset
    when ``renorm``, multiplicity-aware with ``mult``, uncovered
    coordinates substituted from ``fallback``) — masks/mult/fallback are
    row/column-aligned planes, and the entire union model aggregates in
    a single tiled kernel dispatch instead of one per leaf.

    ``use_kernel=None`` auto-selects the Pallas kernel on TPU and the
    jnp oracle (``ref.plane_agg_ref``, as ONE jitted program) elsewhere;
    the two agree to 1e-6 (tests/test_plane.py). The grid walks the
    parameter axis in lane-multiple tiles with a ragged last tile, so
    the plane is never copied (``_tile``). ``block=None`` auto-selects
    the P-tile from the cohort shape and the VMEM budget
    (``fedavg.select_block``); an explicit int passes through
    lane-rounded but otherwise verbatim.
    """
    if mult is not None:
        assert masks is not None, "mult needs masks (coverage aggregation)"
    if fallback is not None:
        assert masks is not None, "fallback needs masks (uncovered coords)"
    if use_kernel is None:
        use_kernel = on_tpu()
    if not use_kernel:
        return _plane_agg_ref_jit(plane, w, masks, mult, fallback, renorm)
    K, n = plane.shape
    if block is None:
        block = select_block(n, K, row_bytes=_row_bytes(plane, masks, mult),
                             col_streams=1 + (fallback is not None))
    blk, pad = _tile(n, block)
    x = _pad_cols(plane, pad)
    if masks is None:
        out = weighted_sum_2d(x, w, block=blk, interpret=interpret)
        return out[:n]
    out = plane_agg_2d(
        x, w, _pad_cols(masks, pad),
        _pad_cols(mult, pad) if mult is not None else None,
        _pad_cols(fallback, pad) if fallback is not None else None,
        block=blk, interpret=interpret, renorm=renorm)
    return out[:n]


def weighted_sum(stacked, w, *, block: int = 4096,
                 interpret: Optional[bool] = None):
    """stacked: (K, *shape); w: (K,) -> (*shape,) fp32.

    Pads the flattened parameter axis to a lane multiple, runs the Pallas
    kernel, and restores the original shape. ``interpret=None`` compiles
    on TPU and falls back to interpreter mode elsewhere.
    """
    flat, n, shape = _flatten_pad(stacked)
    out = weighted_sum_2d(flat, w, block=_block_for(flat.shape[1], block),
                          interpret=interpret)
    return out[:n].reshape(shape)


def weighted_sum_masked(stacked, w, masks, *, mult=None, block: int = 4096,
                        interpret: Optional[bool] = None,
                        renorm: bool = True):
    """stacked, masks [, mult]: (K, *shape); w: (K,) -> (*shape,) fp32.

    Coverage-weighted aggregation: out = sum_k w_k m_k x_k, divided per
    coordinate by ``sum_k w_k m_k`` when ``renorm`` (coordinates covered
    by no client come back 0 — callers substitute their own fallback).
    With ``mult`` (per-coordinate duplication counts of the width
    embedding) the client weight becomes ``w_k m_k / mult_k`` — the
    multiplicity-aware variant, fused in the same streaming pass. The
    zero padding keeps padded coordinates uncovered, so they slice away
    cleanly (mult's zero padding is neutralized inside the kernel).
    """
    flat, n, shape = _flatten_pad(stacked)
    mflat, _, _ = _flatten_pad(masks)
    blk = _block_for(flat.shape[1], block)
    if mult is None:
        out = weighted_sum_masked_2d(flat, w, mflat, block=blk,
                                     interpret=interpret, renorm=renorm)
    else:
        muflat, _, _ = _flatten_pad(mult)
        out = weighted_sum_masked_mult_2d(flat, w, mflat, muflat, block=blk,
                                          interpret=interpret, renorm=renorm)
    return out[:n].reshape(shape)


# ------------------------------------------------- streaming accumulation
def _on_mesh(local, shard, num, den, cov, *operands, rows=()):
    """Run one accumulate step ``local(num, den, cov, *operands)`` where
    its operands live. ``shard=None``: one device. ``shard=(mesh, axes,
    split)``: under a device mesh, where a Pallas call must sit inside a
    ``shard_map`` (Mosaic kernels are not partitioned automatically).
    The ``(1, N)`` buffers are replicated; with ``split`` the operands
    flagged in ``rows`` arrive split over ``axes`` by client row, each
    device accumulates its rows from zero and a ``psum`` adds the
    partial triples (exact up to float reassociation — the masked
    weighted sum is associative); without it every device accumulates
    the whole chunk."""
    if shard is None:
        return local(num, den, cov, *operands)
    mesh, axes, split = shard
    row = P(axes if len(axes) > 1 else axes[0]) if split else P()

    def body(num, den, cov, *operands):
        if not split:
            return local(num, den, cov, *operands)
        z = jnp.zeros_like(num)
        part = local(z, z, z, *operands)
        return tuple(a + jax.lax.psum(b, axes)
                     for a, b in zip((num, den, cov), part))

    specs = (P(), P(), P()) + tuple(row if r else P() for r in rows)
    return jax.shard_map(body, mesh=mesh, in_specs=specs, out_specs=P(),
                         check_vma=False)(num, den, cov, *operands)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2),
                   static_argnames=("block", "interpret", "use_kernel",
                                    "shard"))
def _accum_step(num, den, cov, x, w, m, mu, *, block: int,
                interpret: Optional[bool], use_kernel: bool, shard=None):
    """One donated accumulate step on ``(1, N)`` buffers — the Pallas
    streaming kernel (aliased in-place) on TPU, the jnp oracle (fused by
    this jit, buffers still donated) elsewhere; ``shard`` as
    ``_on_mesh``."""
    def local(num, den, cov, x, w, m, mu):
        if use_kernel:
            return plane_accum_2d(num, den, cov, x, w, m, mu, block=block,
                                  interpret=interpret)
        return ref.plane_accum_ref(num, den, cov, x, w, m, mu)
    return _on_mesh(local, shard, num, den, cov, x, w, m, mu,
                    rows=(True, True, True, True))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2),
                   static_argnames=("tile", "block", "interpret",
                                    "use_kernel", "shard"))
def _accum_q_step(num, den, cov, xq, s, w, m, mu, base, *, tile: int,
                  block: int, interpret: Optional[bool], use_kernel: bool,
                  shard=None):
    """One donated fused dequantize-accumulate step on ``(1, N)``
    buffers — the Pallas kernel (aliased in-place) on TPU, the jnp
    oracle (fused by this jit, buffers still donated) elsewhere;
    ``shard`` as ``_on_mesh``."""
    def local(num, den, cov, xq, s, w, m, mu, base):
        if use_kernel:
            return plane_accum_q_2d(num, den, cov, xq, s, w, m, mu, base,
                                    tile=tile, block=block,
                                    interpret=interpret)
        return ref.plane_accum_q_ref(num, den, cov, xq, s, w, m, mu, base,
                                     tile=tile)
    return _on_mesh(local, shard, num, den, cov, xq, s, w, m, mu, base,
                    rows=(True, True, True, True, True, False))


@functools.partial(jax.jit, static_argnames=("n", "renorm", "block",
                                             "interpret", "use_kernel",
                                             "shard"))
def _accum_finish(num, den, cov, fb, *, n: int, renorm: bool, block: int,
                  interpret: Optional[bool], use_kernel: bool, shard=None):
    """The final divide pass on ``(1, N)`` buffers, sliced back to
    ``(n,)``; ``shard`` as ``_on_mesh`` (every device finishes its
    replica)."""
    if fb is not None:
        fb = _pad_cols(fb.astype(jnp.float32), num.shape[1] - fb.shape[0]
                       ).reshape(1, -1)

    def local(num, den, cov, fb):
        if use_kernel:
            return plane_finish_2d(num, den, cov, fb, block=block,
                                   interpret=interpret, renorm=renorm)
        return ref.plane_finish_ref(num, den, cov, fb, renorm=renorm)
    if shard is not None:
        shard = shard[:2] + (False,)
    return _on_mesh(local, shard, num, den, cov, fb, rows=(False,))[0, :n]


def plane_accum(num, den, cov, chunk, w, *, masks=None, mult=None,
                block: Optional[int] = None,
                interpret: Optional[bool] = None,
                use_kernel: Optional[bool] = None):
    """Functional streaming accumulate on UNPADDED ``(n,)`` buffers:
    ``(num, den, cov) + (K_chunk, n) chunk -> updated (num, den, cov)``.

    The stateless face of :class:`PlaneAccumulator` (which keeps its
    buffers padded and donated across chunks — prefer it in loops; this
    wrapper pads and slices per call). ``use_kernel=None`` auto-selects
    the Pallas kernel on TPU, the jnp oracle elsewhere; the two agree to
    1e-6. The analysis gate traces THIS surface
    (``analysis/kernels_check.py``)."""
    if use_kernel is None:
        use_kernel = on_tpu()
    if mult is not None:
        assert masks is not None, "mult needs masks (coverage aggregation)"
    K, n = chunk.shape
    assert num.shape == den.shape == cov.shape == (n,), \
        (num.shape, den.shape, cov.shape, chunk.shape)
    if not use_kernel:
        return ref.plane_accum_ref(num, den, cov, chunk, w, masks, mult)
    if block is None:
        block = select_block(n, K, row_bytes=_row_bytes(chunk, masks, mult),
                             col_streams=6)
    blk, pad = _tile(n, block)
    trip = plane_accum_2d(
        _pad_cols(num, pad).reshape(1, -1),
        _pad_cols(den, pad).reshape(1, -1),
        _pad_cols(cov, pad).reshape(1, -1),
        _pad_cols(chunk, pad), w,
        _pad_cols(masks, pad) if masks is not None else None,
        _pad_cols(mult, pad) if mult is not None else None,
        block=blk, interpret=interpret)
    return tuple(t[0, :n] for t in trip)


def plane_accum_q(num, den, cov, chunk, scales, w, *, masks=None,
                  mult=None, base=None, tile: int = 256,
                  block: Optional[int] = None,
                  interpret: Optional[bool] = None,
                  use_kernel: Optional[bool] = None):
    """Functional fused dequantize-accumulate on UNPADDED ``(n,)``
    buffers: ``(num, den, cov) + int8 (K_chunk, n) chunk with per-tile
    scales (K_chunk, ceil(n/tile)) -> updated (num, den, cov)``.

    The compressed-wire twin of :func:`plane_accum` (``core.quant``
    encodes, this accumulates — the f32 chunk never materializes):
    ``masks``/``mult`` are the coverage variants, ``base`` ``(n,)`` is
    the filler_mode="global" fold (x·m + base·(1−m), then an unmasked
    accumulate).  ``use_kernel=None`` auto-selects the Pallas kernel on
    TPU, the jnp oracle elsewhere; the two agree to 1e-6 after
    dequantization.  The analysis gate traces THIS surface
    (``analysis/kernels_check.py``)."""
    if use_kernel is None:
        use_kernel = on_tpu()
    if mult is not None:
        assert masks is not None, "mult needs masks (coverage aggregation)"
    if base is not None:
        assert masks is not None and mult is None, \
            "fold needs masks and is exclusive with mult"
    K, n = chunk.shape
    assert num.shape == den.shape == cov.shape == (n,), \
        (num.shape, den.shape, cov.shape, chunk.shape)
    assert tile % LANE == 0, tile
    nt = -(-n // tile)
    assert scales.shape == (K, nt), (scales.shape, (K, nt))
    if not use_kernel:
        return ref.plane_accum_q_ref(num, den, cov, chunk, scales, w,
                                     masks, mult,
                                     None if base is None
                                     else base.reshape(1, -1), tile=tile)
    if block is None:
        block = select_block(n, K, row_bytes=_row_bytes(chunk, masks, mult),
                             col_streams=6 + (base is not None),
                             temps=Q_TEMPS, unit=tile)
    # tile-multiple blocks, so every grid step owns whole scale tiles
    blk, pad = _tile(n, block, tile)
    trip = plane_accum_q_2d(
        _pad_cols(num, pad).reshape(1, -1),
        _pad_cols(den, pad).reshape(1, -1),
        _pad_cols(cov, pad).reshape(1, -1),
        _pad_cols(chunk, pad),
        jnp.asarray(scales, jnp.float32), w,
        _pad_cols(masks, pad) if masks is not None else None,
        _pad_cols(mult, pad) if mult is not None else None,
        (_pad_cols(base, pad).reshape(1, -1)
         if base is not None else None),
        tile=tile, block=blk, interpret=interpret)
    return tuple(t[0, :n] for t in trip)


def plane_finish(num, den, cov, *, fallback=None, renorm: bool = True,
                 block: Optional[int] = None,
                 interpret: Optional[bool] = None,
                 use_kernel: Optional[bool] = None):
    """Close a streamed accumulation on UNPADDED ``(n,)`` buffers ->
    ``(n,)`` f32 — renorm divide where den > 0, ``fallback`` where no
    client ever covered (cov == 0). ``plane_accum`` chunks + this equal
    ``plane_agg`` on the whole plane to 1e-6."""
    if use_kernel is None:
        use_kernel = on_tpu()
    n = num.shape[0]
    assert num.shape == den.shape == cov.shape == (n,)
    if not use_kernel:
        return ref.plane_finish_ref(num, den, cov, fallback, renorm=renorm)
    if block is None:
        block = select_block(n, 1, row_bytes=(), col_streams=5)
    blk, pad = _tile(n, block)
    out = plane_finish_2d(
        _pad_cols(num, pad).reshape(1, -1),
        _pad_cols(den, pad).reshape(1, -1),
        _pad_cols(cov, pad).reshape(1, -1),
        (_pad_cols(fallback, pad).reshape(1, -1)
         if fallback is not None else None),
        block=blk, interpret=interpret, renorm=renorm)
    return out[0, :n]


class PlaneAccumulator:
    """Streaming O(P)-memory plane aggregation state (DESIGN.md §9).

    Holds three running ``(P,)`` buffers — numerator, renorm denominator
    and coverage count — and consumes a cohort in ``(K_chunk, P)`` row
    chunks: ``update`` is ONE donated jitted step per chunk (the Pallas
    streaming kernel with in-place aliasing on TPU, the fused jnp oracle
    elsewhere), so aggregation memory is the three buffers plus one
    chunk, independent of the cohort size K. ``finish`` closes with the
    single divide/fallback pass and reproduces ``plane_agg`` on the
    whole plane to 1e-6.

    Under a client ``mesh`` (the engine's cohort mesh) a chunk whose
    rows shard over ``axes`` is accumulated where it lives — one partial
    triple per device, summed by a ``psum`` — and the buffers stay
    replicated: the Pallas kernels run inside ``shard_map`` because the
    TPU compiler cannot partition them.

    Hierarchical (two-level) aggregation composes for free: edge
    reducers each stream their sub-cohort into their own accumulator,
    ``merge`` sums the partial triples (exact — the masked weighted sum
    is associative), and the global reducer finishes once.

    ``stats()`` reports the donated-buffer accounting the memory
    envelope test asserts on: ``buffer_bytes`` (the three padded
    buffers) and ``peak_bytes`` (buffers + the largest chunk's streamed
    operands) — O(P·K_chunk), never O(P·K).
    """

    def __init__(self, n: int, *, block: Optional[int] = None,
                 interpret: Optional[bool] = None,
                 use_kernel: Optional[bool] = None, k_hint: int = 16,
                 q_tile: Optional[int] = None, mesh=None,
                 axes: Tuple[str, ...] = ("clients",)):
        self.n = int(n)
        self.use_kernel = on_tpu() if use_kernel is None else bool(use_kernel)
        self.interpret = interpret
        # a client mesh: chunks whose rows shard over ``axes`` accumulate
        # one partial triple per device (``_on_mesh``)
        self.mesh, self.axes = mesh, tuple(axes)
        # the fused dequantize path (``update_q``) needs blocks of whole
        # scale tiles — set ``q_tile`` (a lane multiple, ``core.quant``'s
        # tile) to make the block a tile multiple
        self.q_tile = None
        if q_tile is not None:
            assert q_tile >= LANE and q_tile % LANE == 0, q_tile
            self.q_tile = int(q_tile)
        unit = self.q_tile or LANE
        if block is None:
            # the VMEM-budgeted tile only matters on the kernel path (sized
            # for the widest update: f32 chunk + masks + mult, or the int8
            # path's extra dequantized temporary); the jnp oracle just
            # wants minimal column padding
            block = (select_block(self.n, k_hint, row_bytes=(4, 4, 4),
                                  col_streams=7, temps=Q_TEMPS, unit=unit)
                     if self.use_kernel else LANE)
        self.block, self._pad = _tile(self.n, block, unit)
        shape = (1, self.n + self._pad)
        self._num = jnp.zeros(shape, jnp.float32)
        self._den = jnp.zeros(shape, jnp.float32)
        self._cov = jnp.zeros(shape, jnp.float32)
        self.rows = 0
        self.chunks = 0
        self.peak_rows = 0
        self._chunk_bytes = 0

    def _shard(self, rows: Optional[int]):
        """``_on_mesh``'s placement for a chunk of ``rows`` client rows
        (``None``: the finish pass) — split when the rows shard over the
        mesh (``sharding.rules.stacked_client_spec``, the engine's rule
        for its training step), replicated otherwise."""
        if self.mesh is None:
            return None
        from repro.sharding.rules import stacked_client_spec
        split = (rows is not None and stacked_client_spec(
            self.mesh, self.axes, rows) != P())
        return (self.mesh, self.axes, split)

    def _note(self, kc: int, nbytes: int):
        self.rows += int(kc)
        self.chunks += 1
        self.peak_rows = max(self.peak_rows, int(kc))
        self._chunk_bytes = max(self._chunk_bytes, int(nbytes))

    def update(self, chunk, w, *, masks=None, mult=None):
        """Accumulate one ``(K_chunk, n)`` row chunk with weights ``w``
        (``(K_chunk,)`` — already renormalized over the FULL cohort by
        the caller; chunking must not change the weights).  The chunk's
        float dtype is preserved into the kernel (bf16 wire chunks
        stream at 2 bytes/coordinate — the kernels cast to f32 in VMEM);
        everything else is taken as f32."""
        if mult is not None:
            assert masks is not None, "mult needs masks"
        kc, n = chunk.shape
        assert n == self.n, (n, self.n)
        x = jnp.asarray(chunk)
        if x.dtype not in (jnp.bfloat16, jnp.float16, jnp.float32):
            x = x.astype(jnp.float32)
        x = _pad_cols(x, self._pad)
        m = (_pad_cols(jnp.asarray(masks, jnp.float32), self._pad)
             if masks is not None else None)
        mu = (_pad_cols(jnp.asarray(mult, jnp.float32), self._pad)
              if mult is not None else None)
        self._num, self._den, self._cov = _accum_step(
            self._num, self._den, self._cov, x,
            jnp.asarray(w, jnp.float32), m, mu,
            block=self.block, interpret=self.interpret,
            use_kernel=self.use_kernel, shard=self._shard(kc))
        n_pad = self.n + self._pad
        self._note(kc, kc * n_pad * (x.dtype.itemsize
                                     + 4 * (m is not None)
                                     + 4 * (mu is not None)))
        return self

    def update_q(self, chunk, scales, w, *, masks=None, mult=None,
                 base=None):
        """Accumulate one int8 ``(K_chunk, n)`` chunk with per-tile
        ``scales`` (``(K_chunk, ceil(n/q_tile))``) through the FUSED
        dequantize-accumulate kernel — the f32 chunk never exists;
        aggregation traffic is 1 byte/coordinate plus the scale grid.
        ``base`` ``(n,)`` is the filler_mode="global" fold.  Needs
        ``q_tile`` set at construction (kernel blocks hold whole scale
        tiles)."""
        assert self.q_tile is not None, \
            "update_q needs q_tile set at construction"
        if mult is not None:
            assert masks is not None, "mult needs masks"
        if base is not None:
            assert masks is not None and mult is None, \
                "fold needs masks and is exclusive with mult"
        kc, n = chunk.shape
        assert n == self.n, (n, self.n)
        tile = self.q_tile
        n_pad = self.n + self._pad
        nt = -(-n // tile)
        assert scales.shape == (kc, nt), (scales.shape, (kc, nt))
        xq = _pad_cols(jnp.asarray(chunk, jnp.int8), self._pad)
        s = jnp.asarray(scales, jnp.float32)
        m = (_pad_cols(jnp.asarray(masks, jnp.float32), self._pad)
             if masks is not None else None)
        mu = (_pad_cols(jnp.asarray(mult, jnp.float32), self._pad)
              if mult is not None else None)
        b = (_pad_cols(jnp.asarray(base, jnp.float32), self._pad
                       ).reshape(1, -1) if base is not None else None)
        self._num, self._den, self._cov = _accum_q_step(
            self._num, self._den, self._cov, xq, s,
            jnp.asarray(w, jnp.float32), m, mu, b,
            tile=tile, block=self.block, interpret=self.interpret,
            use_kernel=self.use_kernel, shard=self._shard(kc))
        self._note(kc, kc * (n_pad + 4 * nt
                             + 4 * n_pad * (m is not None)
                             + 4 * n_pad * (mu is not None))
                   + 4 * n_pad * (b is not None))
        return self

    def merge(self, other: "PlaneAccumulator"):
        """Global reduce of the two-level hierarchy: sum another edge
        reducer's partial triple into this one (exact by associativity).
        Layouts must match (same n and padded block)."""
        assert other.n == self.n and other._num.shape == self._num.shape, \
            "merge needs accumulators over the same plane layout"
        self._num = self._num + other._num
        self._den = self._den + other._den
        self._cov = self._cov + other._cov
        self.rows += other.rows
        self.chunks += other.chunks
        self.peak_rows = max(self.peak_rows, other.peak_rows)
        self._chunk_bytes = max(self._chunk_bytes, other._chunk_bytes)
        return self

    def partials(self):
        """The raw (num, den, cov) triple, unpadded ``(n,)`` each — what
        an edge reducer ships to the global reduce."""
        return (self._num[0, :self.n], self._den[0, :self.n],
                self._cov[0, :self.n])

    def finish(self, *, renorm: bool = True, fallback=None):
        """The one divide pass -> ``(n,)`` f32. ``renorm`` divides by the
        accumulated covering mass where positive; ``fallback``
        substitutes on coordinates no streamed client covered."""
        fb = (jnp.asarray(fallback, jnp.float32)
              if fallback is not None else None)
        return _accum_finish(self._num, self._den, self._cov, fb,
                             n=self.n, renorm=renorm, block=self.block,
                             interpret=self.interpret,
                             use_kernel=self.use_kernel,
                             shard=self._shard(None))

    def stats(self) -> dict:
        """Donated-buffer accounting: the accumulation's memory envelope
        is ``buffer_bytes`` (3 padded f32 buffers) + the largest chunk's
        streamed operands (actual itemsizes — an int8 wire chunk counts
        1 byte/coordinate plus its scale grid) — O(P·K_chunk),
        independent of total rows."""
        n_pad = self.n + self._pad
        buffers = 3 * n_pad * 4
        return {"n": self.n, "padded": n_pad, "block": self.block,
                "rows": self.rows, "chunks": self.chunks,
                "peak_chunk_rows": self.peak_rows,
                "buffer_bytes": buffers, "chunk_bytes": self._chunk_bytes,
                "peak_bytes": buffers + self._chunk_bytes}
