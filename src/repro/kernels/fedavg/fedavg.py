"""Pallas TPU kernel: weighted multi-model parameter aggregation.

FedAvg's inner loop (paper Eq. 1) is a memory-bound streaming reduction
over K stacked client parameter tensors: out[n] = sum_k w[k] * x[k, n].
The kernel tiles the flattened parameter axis into VMEM-resident blocks
(lane-aligned, 128 multiple) and keeps the K axis resident, so every HBM
byte is touched exactly once (arithmetic intensity ~= 1 FLOP/byte — see
the roofline discussion in EXPERIMENTS.md).

TARGET: TPU (pl.pallas_call + BlockSpec). ``interpret=None`` auto-selects:
compiled (interpret=False) on a TPU backend, interpreter mode elsewhere —
so the same call site is production-fast on TPU and still validated via
interpret=True on CPU against ``ref.weighted_sum_ref``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128

# The ONE VMEM budget model of the aggregation kernels. A pallas_call
# may use the chip's default scoped VMEM limit (16 MiB on v5e); the
# pipeline double-buffers every blocked operand, a (K, T) block occupies
# K rounded up to the dtype's sublane tile (8 rows of f32, 16 of bf16,
# 32 of int8), and the kernel bodies keep a few f32 (K, T) temporaries
# live (w·m, w·m·x; the int8 kernel also its dequantized chunk).
# ``VMEM_HEADROOM`` leaves the rest for Mosaic's own scratch. The model
# is checked against the TPU compiler in tests/test_tpu_compile.py;
# ``analysis/kernels_check`` reads the same limit.
VMEM_BUDGET_BYTES = 16 * 2 ** 20
VMEM_HEADROOM = 0.85
DOUBLE_BUFFER = 2
F32_TEMPS = 2
Q_TEMPS = 3


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _padded_rows(k_rows: int, itemsize: int) -> int:
    tile = 8 * 4 // itemsize
    return -(-max(k_rows, 1) // tile) * tile


def vmem_bytes_per_lane(k_rows: int, *, row_bytes=(), col_streams: int = 1,
                        temps: int = F32_TEMPS) -> int:
    """VMEM bytes one lane column of a P-tile costs: the double-buffered
    ``(K, T)`` operands (one itemsize per entry of ``row_bytes``: plane,
    masks, mult, int8 payload) and ``(1, T)`` operands (``col_streams``:
    fallback, outputs, accumulators), plus ``temps`` f32 ``(K, T)``
    temporaries of the kernel body and two f32 ``(1, T)`` ones."""
    rows = sum(b * _padded_rows(k_rows, b) for b in row_bytes)
    return (DOUBLE_BUFFER * (rows + 4 * col_streams)
            + 4 * (temps * _padded_rows(k_rows, 4) + 2))


def select_block(n: int, k_rows: int, *, row_bytes=(4,),
                 col_streams: int = 1, temps: int = F32_TEMPS,
                 unit: int = LANE, cap: int = 1 << 16) -> int:
    """The P-tile of an aggregation kernel: the largest multiple of
    ``unit`` (a lane multiple) whose VMEM footprint
    (``vmem_bytes_per_lane``) stays within ``VMEM_HEADROOM`` of the
    scoped limit, no wider than the plane (the grid's last tile may be
    ragged — the kernels never see a padded copy). ``cap`` bounds the
    tile so interpret-mode tracing stays cheap; an EXPLICIT ``block``
    argument anywhere in ``ops`` passes through."""
    per_lane = vmem_bytes_per_lane(k_rows, row_bytes=row_bytes,
                                   col_streams=col_streams, temps=temps)
    blk = min(int(VMEM_BUDGET_BYTES * VMEM_HEADROOM) // per_lane, cap,
              max(n, 1))
    return max(unit, (blk // unit) * unit)


def _check_block(n: int, block: int) -> int:
    """Clamp a P-tile to the plane and check it tiles on the TPU: a lane
    multiple, or the whole (narrow) plane. ``n`` need not divide — the
    grid is ``cdiv(n, block)`` and Pallas masks the ragged last tile's
    out-of-range columns (they read unspecified values, which only ever
    reach those masked output columns: every kernel here is
    column-local)."""
    block = min(block, n)
    assert block % LANE == 0 or block == n, (n, block)
    return block


def _kernel(x_ref, w_ref, o_ref):
    # x_ref: (K, T) block; w_ref: (K, 1); o_ref: (1, T)
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)          # (K, 1)
    o_ref[...] = jnp.sum(x * w, axis=0, keepdims=True).astype(o_ref.dtype)


def _masked_kernel(x_ref, w_ref, m_ref, o_ref, *, renorm: bool):
    # x_ref/m_ref: (K, T) blocks; w_ref: (K, 1); o_ref: (1, T).
    # out[n] = sum_k w[k] m[k,n] x[k,n]  (/ sum_k w[k] m[k,n] when renorm;
    # coordinates no client covers produce 0 — the caller substitutes its
    # fallback there).
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)          # (K, 1)
    m = m_ref[...].astype(jnp.float32)
    wm = w * m                                  # (K, T)
    num = jnp.sum(wm * x, axis=0, keepdims=True)
    if renorm:
        den = jnp.sum(wm, axis=0, keepdims=True)
        num = jnp.where(den > 0, num / jnp.where(den > 0, den, 1.0), 0.0)
    o_ref[...] = num.astype(o_ref.dtype)


def _masked_mult_kernel(x_ref, w_ref, m_ref, mu_ref, o_ref, *, renorm: bool):
    # The multiplicity-aware coverage pass: per-coordinate client weight
    # w[k] m[k,n] / mu[k,n] (mu = how many union coordinates duplicate the
    # same client coordinate — a duplicated channel's total weight stays
    # w[k]). Same single streaming pass, one extra (K, T) operand; mu <= 0
    # (zero padding) is treated as 1, harmless because m is 0 there too.
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)          # (K, 1)
    m = m_ref[...].astype(jnp.float32)
    mu = mu_ref[...].astype(jnp.float32)
    wm = w * m / jnp.where(mu > 0, mu, 1.0)     # (K, T)
    num = jnp.sum(wm * x, axis=0, keepdims=True)
    if renorm:
        den = jnp.sum(wm, axis=0, keepdims=True)
        num = jnp.where(den > 0, num / jnp.where(den > 0, den, 1.0), 0.0)
    o_ref[...] = num.astype(o_ref.dtype)


def _plane_kernel(*refs, renorm: bool, has_mult: bool, has_fb: bool):
    # The whole-plane fused aggregation pass: x/m[/mu]: (K, T) blocks,
    # w: (K, 1), [fb: (1, T)], o: (1, T). Per coordinate
    #   out = Σ_k (w_k m_k [/ mu_k]) x_k   [ / Σ_k w_k m_k/mu_k  if renorm]
    # and coordinates NO client covers (Σ_k m_k == 0) take fb (or 0) —
    # coverage average, multiplicity division, renormalization and
    # fallback substitution in ONE streaming kernel, so a packed cohort
    # aggregates in a single pallas dispatch instead of one per leaf.
    it = iter(refs)
    x = next(it)[...].astype(jnp.float32)
    w = next(it)[...].astype(jnp.float32)           # (K, 1)
    m = next(it)[...].astype(jnp.float32)
    mu = next(it)[...].astype(jnp.float32) if has_mult else None
    fb = next(it)[...].astype(jnp.float32) if has_fb else None
    o_ref = next(it)
    wm = w * m
    if has_mult:
        # mu <= 0 (zero padding) treated as 1 — harmless, m is 0 there
        wm = wm / jnp.where(mu > 0, mu, 1.0)
    num = jnp.sum(wm * x, axis=0, keepdims=True)
    if renorm:
        den = jnp.sum(wm, axis=0, keepdims=True)
        num = jnp.where(den > 0, num / jnp.where(den > 0, den, 1.0), 0.0)
    if has_fb:
        covered = jnp.sum(m, axis=0, keepdims=True) > 0
        num = jnp.where(covered, num, fb)
    o_ref[...] = num.astype(o_ref.dtype)


def _accum_kernel(*refs, has_mask: bool, has_mult: bool):
    # The streaming accumulate pass: num/den/cov are (1, T) RUNNING
    # accumulator blocks ALIASED input->output (in-place — the caller
    # donates them), x [, m, mu]: (K_chunk, T) chunk blocks, w: (K, 1).
    # Per coordinate the chunk contributes
    #   num += Σ_k (w_k m_k [/ mu_k]) x_k
    #   den += Σ_k  w_k m_k [/ mu_k]
    #   cov += Σ_k  m_k
    # so after streaming every chunk, ONE finish pass (``_finish_kernel``)
    # reproduces the whole-plane kernel exactly: renorm divides num/den
    # where den > 0, and cov > 0 is the same "some client covers this
    # coordinate" criterion ``_plane_kernel`` reads from Σ m — kept as a
    # separate buffer so the w=0 corner case agrees bit-for-bit.
    it = iter(refs)
    num_in, den_in, cov_in = next(it), next(it), next(it)
    x = next(it)[...].astype(jnp.float32)
    w = next(it)[...].astype(jnp.float32)           # (K, 1)
    m = next(it)[...].astype(jnp.float32) if has_mask else jnp.ones_like(x)
    mu = next(it)[...].astype(jnp.float32) if has_mult else None
    num_o, den_o, cov_o = next(it), next(it), next(it)
    wm = w * m
    if has_mult:
        # mu <= 0 (zero padding) treated as 1 — harmless, m is 0 there
        wm = wm / jnp.where(mu > 0, mu, 1.0)
    num_o[...] = (num_in[...].astype(jnp.float32)
                  + jnp.sum(wm * x, axis=0, keepdims=True)
                  ).astype(num_o.dtype)
    den_o[...] = (den_in[...].astype(jnp.float32)
                  + jnp.sum(wm, axis=0, keepdims=True)).astype(den_o.dtype)
    cov_o[...] = (cov_in[...].astype(jnp.float32)
                  + jnp.sum(m, axis=0, keepdims=True)).astype(cov_o.dtype)


def _accum_q_kernel(*refs, has_mask: bool, has_mult: bool, fold: bool,
                    tile: int):
    # The fused dequantize-accumulate pass (DESIGN.md §10): identical
    # accumulation semantics to ``_accum_kernel``, but x arrives as an
    # int8 block with symmetric per-tile scales and dequantizes IN VMEM
    # — the f32 chunk never exists in HBM.  The scales arrive as one
    # (K, block/tile) slab per grid step (``plane_accum_q_2d`` lays the
    # scale grid out as (N/block, K, block/tile) so a BlockSpec tiles
    # it).  ``fold`` is filler_mode="global" fused in: x·m + base·(1−m)
    # before an UNMASKED accumulate, one extra (1, T) stream.
    it = iter(refs)
    num_in, den_in, cov_in = next(it), next(it), next(it)
    xq_ref = next(it)
    s = next(it)[0]                                 # (K, block/tile)
    w = next(it)[...].astype(jnp.float32)           # (K, 1)
    m_ref = next(it) if (has_mask or fold) else None
    mu_ref = next(it) if has_mult else None
    base_ref = next(it) if fold else None
    num_o, den_o, cov_o = next(it), next(it), next(it)
    K, block = xq_ref.shape
    nb = block // tile
    x = xq_ref[...].astype(jnp.float32).reshape(K, nb, tile)
    x = (x * s[:, :, None]).reshape(K, block)
    if fold:
        mf = m_ref[...].astype(jnp.float32)
        x = x * mf + base_ref[...].astype(jnp.float32) * (1.0 - mf)
        m = jnp.ones_like(x)
    elif has_mask:
        m = m_ref[...].astype(jnp.float32)
    else:
        m = jnp.ones_like(x)
    wm = w * m
    if has_mult:
        mu = mu_ref[...].astype(jnp.float32)
        # mu <= 0 (zero padding) treated as 1 — harmless, m is 0 there
        wm = wm / jnp.where(mu > 0, mu, 1.0)
    num_o[...] = (num_in[...].astype(jnp.float32)
                  + jnp.sum(wm * x, axis=0, keepdims=True)
                  ).astype(num_o.dtype)
    den_o[...] = (den_in[...].astype(jnp.float32)
                  + jnp.sum(wm, axis=0, keepdims=True)).astype(den_o.dtype)
    cov_o[...] = (cov_in[...].astype(jnp.float32)
                  + jnp.sum(m, axis=0, keepdims=True)).astype(cov_o.dtype)


def _finish_kernel(*refs, renorm: bool, has_fb: bool):
    # The one divide pass closing a streamed accumulation: num/den/cov
    # [, fb]: (1, T) blocks -> out (1, T). Same per-coordinate semantics
    # as the tail of ``_plane_kernel``.
    it = iter(refs)
    num = next(it)[...].astype(jnp.float32)
    den = next(it)[...].astype(jnp.float32)
    cov = next(it)[...].astype(jnp.float32)
    fb = next(it)[...].astype(jnp.float32) if has_fb else None
    o_ref = next(it)
    out = num
    if renorm:
        out = jnp.where(den > 0, num / jnp.where(den > 0, den, 1.0), 0.0)
    if has_fb:
        out = jnp.where(cov > 0, out, fb)
    o_ref[...] = out.astype(o_ref.dtype)


def plane_accum_2d(num, den, cov, x, w, m=None, mu=None, *,
                   block: int = 4096, interpret: Optional[bool] = None):
    """One streaming accumulate step: num/den/cov ``(1, N)`` f32 running
    buffers (updated IN PLACE via ``input_output_aliases`` — callers
    donate them under jit), x [, m, mu] ``(K_chunk, N)``, w ``(K_chunk,)``,
    ``block`` a lane multiple (the last tile may be ragged). Returns the
    updated triple.

    The O(P)-memory realization of ``plane_agg_2d``: a cohort streams
    through in ``K_chunk``-row chunks, only the three (N,) accumulators
    and one chunk are ever resident, and ``plane_finish_2d`` closes with
    the single divide/fallback pass. NOT jitted here — ``ops``'s
    accumulator wraps it in a donated jit so the aliasing actually
    updates in place.
    """
    if interpret is None:
        interpret = not on_tpu()
    K, N = x.shape
    assert num.shape == den.shape == cov.shape == (1, N), \
        (num.shape, den.shape, cov.shape, x.shape)
    if mu is not None:
        assert m is not None, "mult needs masks"
    block = _check_block(N, block)
    acc = pl.BlockSpec((1, block), lambda i: (0, i))
    row = pl.BlockSpec((K, block), lambda i: (0, i))
    ins = [num, den, cov, x, w.reshape(K, 1)]
    specs = [acc, acc, acc, row, pl.BlockSpec((K, 1), lambda i: (0, 0))]
    if m is not None:
        assert m.shape == (K, N), (m.shape, x.shape)
        ins.append(m)
        specs.append(row)
    if mu is not None:
        assert mu.shape == (K, N), (mu.shape, x.shape)
        ins.append(mu)
        specs.append(row)
    sds = jax.ShapeDtypeStruct((1, N), jnp.float32)
    return pl.pallas_call(
        functools.partial(_accum_kernel, has_mask=m is not None,
                          has_mult=mu is not None),
        grid=(pl.cdiv(N, block),),
        in_specs=specs,
        out_specs=(acc, acc, acc),
        out_shape=(sds, sds, sds),
        input_output_aliases={0: 0, 1: 1, 2: 2},
        interpret=interpret,
    )(*ins)


def plane_accum_q_2d(num, den, cov, xq, s, w, m=None, mu=None, base=None,
                     *, tile: int = 256, block: int = 4096,
                     interpret: Optional[bool] = None):
    """One fused dequantize-accumulate step: num/den/cov ``(1, N)`` f32
    running buffers (aliased in place — callers donate them under jit),
    xq ``(K_chunk, N)`` int8, s ``(K_chunk, N/tile)`` f32 per-tile
    scales, w ``(K_chunk,)``; optional m/mu ``(K_chunk, N)`` coverage/
    multiplicity rows and ``base`` ``(1, N)`` (filler_mode="global"
    fold: x·m + base·(1−m), then an unmasked accumulate).  ``block``
    is a multiple of ``tile`` (itself a lane multiple) no wider than N;
    the last tile may be ragged.  Same accumulation math as
    ``plane_accum_2d`` on ``dequantize(xq, s)`` — the int8 chunk dequantizes in VMEM, so the
    f32 cohort is never materialized (``core.quant`` + DESIGN.md §10).
    """
    if interpret is None:
        interpret = not on_tpu()
    K, N = xq.shape
    assert num.shape == den.shape == cov.shape == (1, N), \
        (num.shape, den.shape, cov.shape, xq.shape)
    assert xq.dtype == jnp.int8, xq.dtype
    if mu is not None:
        assert m is not None, "mult needs masks"
    if base is not None:
        assert m is not None and mu is None, \
            "fold needs masks and is exclusive with mult"
    block = _check_block(N, block)
    assert tile % LANE == 0 and block % tile == 0, (N, block, tile)
    nt, G, nb = pl.cdiv(N, tile), pl.cdiv(N, block), block // tile
    assert s.shape == (K, nt), (s.shape, (K, nt))
    acc = pl.BlockSpec((1, block), lambda i: (0, i))
    row = pl.BlockSpec((K, block), lambda i: (0, i))
    # one (K, nb) scale slab per grid step: its last two dims are the
    # whole slab, so the block tiles on the TPU whatever nb is (a
    # (K, nb) block of the flat grid would need nb % 128 == 0)
    slabs = jnp.pad(s.astype(jnp.float32), ((0, 0), (0, G * nb - nt)))
    slabs = slabs.reshape(K, G, nb).transpose(1, 0, 2)
    ins = [num, den, cov, xq, slabs, w.reshape(K, 1)]
    specs = [acc, acc, acc, row,
             pl.BlockSpec((1, K, nb), lambda i: (i, 0, 0)),
             pl.BlockSpec((K, 1), lambda i: (0, 0))]
    fold = base is not None
    if m is not None:
        assert m.shape == (K, N), (m.shape, xq.shape)
        ins.append(m)
        specs.append(row)
    if mu is not None:
        assert mu.shape == (K, N), (mu.shape, xq.shape)
        ins.append(mu)
        specs.append(row)
    if fold:
        assert base.shape == (1, N), (base.shape, xq.shape)
        ins.append(base)
        specs.append(acc)
    sds = jax.ShapeDtypeStruct((1, N), jnp.float32)
    return pl.pallas_call(
        functools.partial(_accum_q_kernel,
                          has_mask=(m is not None) and not fold,
                          has_mult=mu is not None, fold=fold, tile=tile),
        grid=(pl.cdiv(N, block),),
        in_specs=specs,
        out_specs=(acc, acc, acc),
        out_shape=(sds, sds, sds),
        input_output_aliases={0: 0, 1: 1, 2: 2},
        interpret=interpret,
    )(*ins)


def plane_finish_2d(num, den, cov, fb=None, *, block: int = 4096,
                    interpret: Optional[bool] = None, renorm: bool = True):
    """The final divide pass of a streamed accumulation: num/den/cov
    [, fb]: ``(1, N)`` -> ``(1, N)`` f32. ``renorm`` divides num by den
    where den > 0; coordinates with cov == 0 (no client ever covered
    them) take ``fb``. Composes with ``plane_accum_2d`` to reproduce
    ``plane_agg_2d`` exactly."""
    if interpret is None:
        interpret = not on_tpu()
    _, N = num.shape
    assert num.shape == den.shape == cov.shape == (1, N)
    block = _check_block(N, block)
    acc = pl.BlockSpec((1, block), lambda i: (0, i))
    ins = [num, den, cov]
    specs = [acc, acc, acc]
    if fb is not None:
        assert fb.shape == (1, N), (fb.shape, num.shape)
        ins.append(fb)
        specs.append(acc)
    return pl.pallas_call(
        functools.partial(_finish_kernel, renorm=renorm,
                          has_fb=fb is not None),
        grid=(pl.cdiv(N, block),),
        in_specs=specs,
        out_specs=acc,
        out_shape=jax.ShapeDtypeStruct((1, N), jnp.float32),
        interpret=interpret,
    )(*ins)


@functools.partial(jax.jit, static_argnames=("block", "interpret", "renorm"))
def plane_agg_2d(x, w, m, mu=None, fb=None, *, block: int = 4096,
                 interpret: Optional[bool] = None, renorm: bool = True):
    """x, m [, mu]: (K, N); w: (K,); [fb: (N,)] -> (N,) fp32.

    The tiled whole-plane coverage aggregation (``_plane_kernel``): one
    grid over N/block P-tiles, the K axis VMEM-resident, every operand
    streamed from HBM exactly once. ``mu`` (duplication counts) and
    ``fb`` (fallback values for uncovered coordinates) are optional —
    each adds one streamed operand to the SAME single pass.
    """
    if interpret is None:
        interpret = not on_tpu()
    K, N = x.shape
    assert m.shape == (K, N), (m.shape, x.shape)
    block = _check_block(N, block)
    row = pl.BlockSpec((K, block), lambda i: (0, i))
    ins = [x, w.reshape(K, 1), m]
    specs = [row, pl.BlockSpec((K, 1), lambda i: (0, 0)), row]
    if mu is not None:
        assert mu.shape == (K, N), (mu.shape, x.shape)
        ins.append(mu)
        specs.append(row)
    if fb is not None:
        assert fb.shape == (N,), (fb.shape, x.shape)
        ins.append(fb.reshape(1, N))
        specs.append(pl.BlockSpec((1, block), lambda i: (0, i)))
    out = pl.pallas_call(
        functools.partial(_plane_kernel, renorm=renorm,
                          has_mult=mu is not None, has_fb=fb is not None),
        grid=(pl.cdiv(N, block),),
        in_specs=specs,
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, N), jnp.float32),
        interpret=interpret,
    )(*ins)
    return out[0]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def weighted_sum_2d(x, w, *, block: int = 4096,
                    interpret: Optional[bool] = None):
    """x: (K, N); w: (K,) -> (N,) fp32."""
    if interpret is None:
        interpret = not on_tpu()
    K, N = x.shape
    block = _check_block(N, block)
    grid = (pl.cdiv(N, block),)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((K, block), lambda i: (0, i)),
            pl.BlockSpec((K, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, N), jnp.float32),
        interpret=interpret,
    )(x, w.reshape(K, 1))
    return out[0]


@functools.partial(jax.jit, static_argnames=("block", "interpret", "renorm"))
def weighted_sum_masked_2d(x, w, m, *, block: int = 4096,
                           interpret: Optional[bool] = None,
                           renorm: bool = True):
    """x, m: (K, N); w: (K,) -> (N,) fp32.

    Per-coordinate coverage-weighted aggregation: the mask m selects which
    clients own each coordinate, and ``renorm`` divides by the covering
    weight mass ``sum_k w[k] m[k, n]`` (HeteroFL-style renormalization).
    Same blocking as ``weighted_sum_2d`` with the K axis VMEM-resident;
    the mask stream doubles the HBM traffic but the reduction stays
    memory-bound and single-pass.
    """
    if interpret is None:
        interpret = not on_tpu()
    K, N = x.shape
    assert m.shape == (K, N), (m.shape, x.shape)
    block = _check_block(N, block)
    grid = (pl.cdiv(N, block),)
    out = pl.pallas_call(
        functools.partial(_masked_kernel, renorm=renorm),
        grid=grid,
        in_specs=[
            pl.BlockSpec((K, block), lambda i: (0, i)),
            pl.BlockSpec((K, 1), lambda i: (0, 0)),
            pl.BlockSpec((K, block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, N), jnp.float32),
        interpret=interpret,
    )(x, w.reshape(K, 1), m)
    return out[0]


@functools.partial(jax.jit, static_argnames=("block", "interpret", "renorm"))
def weighted_sum_masked_mult_2d(x, w, m, mu, *, block: int = 4096,
                                interpret: Optional[bool] = None,
                                renorm: bool = True):
    """x, m, mu: (K, N); w: (K,) -> (N,) fp32.

    Multiplicity-aware coverage aggregation: client k's per-coordinate
    weight is ``w[k] m[k,n] / mu[k,n]`` (``mu`` = duplication counts of
    the width embedding), renormalized by the covering mass when
    ``renorm``. Same blocking and single streaming pass as
    ``weighted_sum_masked_2d`` with one more (K, T) operand — still
    memory-bound, every HBM byte touched once.
    """
    if interpret is None:
        interpret = not on_tpu()
    K, N = x.shape
    assert m.shape == (K, N) and mu.shape == (K, N), (m.shape, mu.shape)
    block = _check_block(N, block)
    grid = (pl.cdiv(N, block),)
    out = pl.pallas_call(
        functools.partial(_masked_mult_kernel, renorm=renorm),
        grid=grid,
        in_specs=[
            pl.BlockSpec((K, block), lambda i: (0, i)),
            pl.BlockSpec((K, 1), lambda i: (0, 0)),
            pl.BlockSpec((K, block), lambda i: (0, i)),
            pl.BlockSpec((K, block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, N), jnp.float32),
        interpret=interpret,
    )(x, w.reshape(K, 1), m, mu)
    return out[0]
