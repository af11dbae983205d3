"""Pallas kernel validator — static checks on traced ``pallas_call`` specs.

Traces the ``kernels/fedavg`` public wrappers (plain, masked,
masked+mult, whole-plane) over representative shapes with
``jax.make_jaxpr`` — abstract evaluation, nothing launches — then walks
the jaxpr for ``pallas_call`` equations and validates each one's grid
mapping:

  * every block fits its array axis-by-axis (the last tile of an axis
    may be ragged — Pallas masks its out-of-range columns),
  * the grid covers every operand's tiles a whole number of times (no
    dropped or duplicated tiles),
  * tiled blocks are lane-aligned (last axis a multiple of 128) —
    whole-array blocks like the ``(K, 1)`` weight column are exempt,
  * the estimated VMEM footprint (Σ block bytes over all operands ×2 for
    the pipeline's double buffering) fits the kernels' VMEM budget
    (``kernels/fedavg/fedavg.py``, the one budget model),
  * the ops-layer padding contract holds: the wrapper's OUTPUT aval is
    the caller's shape — columns a narrow plane was padded with never
    leak out of the final slice.

The TPU compiler itself checks tiling and VMEM at the main path's real
widths (tests/test_tpu_compile.py); this pass traces many more shapes
without one.

Representative shapes deliberately include lane-odd parameter counts
(exercising ``ops``'s pad-then-slice path), a sub-lane tensor, and a
multi-megabyte plane at the default block size.
"""
from __future__ import annotations

import math
from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.analysis import Finding
from repro.kernels.fedavg import ops
from repro.kernels.fedavg.fedavg import (DOUBLE_BUFFER, LANE,
                                         VMEM_BUDGET_BYTES)


def _sds(*shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _subjaxprs(value):
    if hasattr(value, "jaxpr"):            # ClosedJaxpr
        yield value.jaxpr
    elif hasattr(value, "eqns"):           # Jaxpr
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _subjaxprs(v)


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from _walk_eqns(sub)


def _block_shape(bm) -> Tuple[int, ...]:
    # blocked dims carry their size (``pl.Blocked``); squeezed dims are
    # sentinels that occupy one row/col, so count them as 1
    def size(b):
        b = getattr(b, "block_size", b)
        return b if isinstance(b, int) else 1
    return tuple(size(b) for b in bm.block_shape)


def _check_pallas_eqn(name: str, eqn) -> List[Finding]:
    out: List[Finding] = []
    gm = eqn.params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid)
    n_tiles = math.prod(grid) if grid else 1
    vmem = 0
    for i, bm in enumerate(gm.block_mappings):
        arr = tuple(int(s) for s in bm.array_aval.shape)
        blk = _block_shape(bm)
        where = f"{name}/operand{i}"
        if len(arr) != len(blk):
            out.append(Finding("kernels", "block-rank", where, 0,
                               f"block rank {len(blk)} != array rank "
                               f"{len(arr)}"))
            continue
        tiles = 1
        for ax, (a, b) in enumerate(zip(arr, blk)):
            if b <= 0 or b > a:
                out.append(Finding(
                    "kernels", "block-extent", where, 0,
                    f"axis {ax}: block {b} does not fit array extent {a}"))
            else:
                tiles *= -(-a // b)
        if blk and arr and blk[-1] != arr[-1] and blk[-1] % LANE:
            out.append(Finding(
                "kernels", "lane-alignment", where, 0,
                f"tiled last axis block {blk[-1]} is not a multiple of "
                f"the {LANE}-wide lane"))
        if tiles and n_tiles % tiles:
            # an operand may be tiled on a SUBSET of grid axes (the flash
            # kernels broadcast k/v blocks over the q-block axis and vice
            # versa), so each tile must be visited a whole number of
            # times: tile count divides the grid size
            out.append(Finding(
                "kernels", "grid-coverage", where, 0,
                f"operand tiles {tiles} do not divide the grid size "
                f"{n_tiles} — tiles dropped or duplicated"))
        vmem += math.prod(blk) * bm.array_aval.dtype.itemsize
    est = vmem * DOUBLE_BUFFER
    if est > VMEM_BUDGET_BYTES:
        out.append(Finding(
            "kernels", "vmem-budget", name, 0,
            f"estimated VMEM footprint {est / 2**20:.2f} MiB "
            f"(double-buffered blocks) exceeds the "
            f"{VMEM_BUDGET_BYTES / 2**20:.0f} MiB budget — shrink `block`"))
    return out


def _case_findings(name: str, fn: Callable, avals: Sequence,
                   expect_shape: Tuple[int, ...]) -> List[Finding]:
    try:
        closed = jax.make_jaxpr(fn)(*avals)
    except Exception as e:
        return [Finding("kernels", "trace-crash", name, 0,
                        f"tracing raised {type(e).__name__}: {e}")]
    out: List[Finding] = []
    pallas = [e for e in _walk_eqns(closed.jaxpr)
              if e.primitive.name == "pallas_call"]
    if not pallas:
        out.append(Finding("kernels", "no-kernel", name, 0,
                           "no pallas_call in the traced jaxpr — the "
                           "wrapper silently fell back off the kernel"))
    for eqn in pallas:
        out.extend(_check_pallas_eqn(name, eqn))
    got = tuple(int(s) for s in closed.out_avals[0].shape)
    if got != tuple(expect_shape):
        out.append(Finding(
            "kernels", "pad-slice", name, 0,
            f"wrapper output {got} != caller shape {tuple(expect_shape)} "
            "— padded columns leak out of the kernel"))
    return out


def cases():
    """(name, fn, avals, expected output shape) — the kernel surface ×
    representative shapes. ``interpret=True`` + ``use_kernel=True`` so
    the pallas path traces identically on CPU CI and TPU."""
    K = 8
    n_odd = 4096 * 3 + 517        # lane-odd plane -> pad-then-slice path
    n_even = 4096 * 4             # block-aligned plane -> zero padding
    n_big = 1 << 22               # ~128 MiB of stacked params, K=8
    x = lambda n: _sds(K, n)      # noqa: E731
    w = _sds(K)
    for n in (n_odd, n_even, n_big):
        yield (f"plane_agg/N={n}",
               lambda p, wt, n=n: ops.plane_agg(
                   p, wt, use_kernel=True, interpret=True),
               (x(n), w), (n,))
        yield (f"plane_agg_masked/N={n}",
               lambda p, wt, m, n=n: ops.plane_agg(
                   p, wt, masks=m, use_kernel=True, interpret=True),
               (x(n), w, x(n)), (n,))
        yield (f"plane_agg_mult_fb/N={n}",
               lambda p, wt, m, mu, fb, n=n: ops.plane_agg(
                   p, wt, masks=m, mult=mu, fallback=fb,
                   use_kernel=True, interpret=True),
               (x(n), w, x(n), x(n), _sds(n)), (n,))
    # streaming surface (DESIGN.md §9): the chunked accumulate + finish
    # pair behind fedavg_stacked(layout="stream"). Kc is a CHUNK of
    # client rows (smaller than any realistic cohort — the chunk
    # boundary is the contract), the buffers are (n,); shapes hit the
    # lane-odd pad-then-slice path, an even plane, and a multi-MiB
    # accumulator at the auto-selected block.
    Kc = 4
    a = _sds  # (n,) accumulator aval
    for n in (n_odd, n_even, n_big):
        yield (f"plane_accum/N={n}",
               lambda nm, dn, cv, c, wt: ops.plane_accum(
                   nm, dn, cv, c, wt, use_kernel=True, interpret=True),
               (a(n), a(n), a(n), _sds(Kc, n), _sds(Kc)), (n,))
        yield (f"plane_accum_masked_mult/N={n}",
               lambda nm, dn, cv, c, wt, m, mu: ops.plane_accum(
                   nm, dn, cv, c, wt, masks=m, mult=mu,
                   use_kernel=True, interpret=True),
               (a(n), a(n), a(n), _sds(Kc, n), _sds(Kc), _sds(Kc, n),
                _sds(Kc, n)), (n,))
        yield (f"plane_finish/N={n}",
               lambda nm, dn, cv, fb: ops.plane_finish(
                   nm, dn, cv, fallback=fb, use_kernel=True,
                   interpret=True),
               (a(n), a(n), a(n), a(n)), (n,))
    # quantized-wire surface (DESIGN.md §10): the fused
    # dequantize-accumulate pass behind wire="int8". int8 chunk rows +
    # a per-tile f32 scale grid (whole-array resident operand); same
    # lane-odd / even / multi-MiB planes as the f32 streaming cases.
    tile = 256
    nt = lambda n: -(-n // tile)  # noqa: E731
    for n in (n_odd, n_even, n_big):
        yield (f"plane_accum_q/N={n}",
               lambda nm, dn, cv, c, s, wt: ops.plane_accum_q(
                   nm, dn, cv, c, s, wt, tile=tile,
                   use_kernel=True, interpret=True),
               (a(n), a(n), a(n), _sds(Kc, n, dtype=jnp.int8),
                _sds(Kc, nt(n)), _sds(Kc)), (n,))
        yield (f"plane_accum_q_masked_mult/N={n}",
               lambda nm, dn, cv, c, s, wt, m, mu: ops.plane_accum_q(
                   nm, dn, cv, c, s, wt, masks=m, mult=mu, tile=tile,
                   use_kernel=True, interpret=True),
               (a(n), a(n), a(n), _sds(Kc, n, dtype=jnp.int8),
                _sds(Kc, nt(n)), _sds(Kc), _sds(Kc, n), _sds(Kc, n)),
               (n,))
        yield (f"plane_accum_q_fold/N={n}",
               lambda nm, dn, cv, c, s, wt, m, b: ops.plane_accum_q(
                   nm, dn, cv, c, s, wt, masks=m, base=b, tile=tile,
                   use_kernel=True, interpret=True),
               (a(n), a(n), a(n), _sds(Kc, n, dtype=jnp.int8),
                _sds(Kc, nt(n)), _sds(Kc), _sds(Kc, n), a(n)), (n,))
    # flash-attention surface (DESIGN.md §11): the training forward plus
    # the custom_vjp backward (dQ and dK/dV recomputation kernels, traced
    # through jax.grad so the bwd pallas_calls appear in the jaxpr).
    # Shapes: lane-aligned causal GQA, a sliding-window band, a lane-odd
    # head dim (hd=72 -> whole-axis last blocks), and a sub-lane short
    # sequence (bq=8 rows).
    from repro.kernels.flash_attention import ops as fops

    def _flash_avals(B, Sq, Sk, KV, G, hd):
        return (_sds(B, Sq, KV, G, hd), _sds(B, Sk, KV, hd),
                _sds(B, Sk, KV, hd), _sds(Sq, dtype=jnp.int32),
                _sds(Sk, dtype=jnp.int32))

    flash_shapes = (
        ("causal_gqa", (2, 256, 256, 2, 4, 128), True, 0),
        ("window", (1, 256, 256, 1, 8, 64), True, 64),
        ("cross_laneodd", (2, 128, 192, 2, 1, 72), False, 0),
        ("sublane", (1, 8, 8, 2, 2, 64), True, 0),
    )
    for tag, (B, Sq, Sk, KV, G, hd), causal, window in flash_shapes:
        def fwd_fn(q, k, v, qp, kp, *, c=causal, w=window):
            return fops.flash_attention(q, k, v, qp, kp, causal=c,
                                        window=w, use_kernel=True,
                                        interpret=True)

        def bwd_fn(q, k, v, qp, kp, *, c=causal, w=window):
            def loss(q, k, v):
                return fops.flash_attention(
                    q, k, v, qp, kp, causal=c, window=w, use_kernel=True,
                    interpret=True).sum()
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        yield (f"flash_fwd/{tag}", fwd_fn, _flash_avals(B, Sq, Sk, KV, G, hd),
               (B, Sq, KV * G, hd))
        yield (f"flash_bwd/{tag}", bwd_fn, _flash_avals(B, Sq, Sk, KV, G, hd),
               (B, Sq, KV, G, hd))
    # leaf-shaped wrappers: lane-odd tensor + sub-lane tensor
    for shape in ((33, 7), (5,), (256, 130)):
        n = math.prod(shape)
        yield (f"weighted_sum/{shape}",
               lambda s, wt: ops.weighted_sum(s, wt, interpret=True),
               (_sds(K, *shape), w), shape)
        yield (f"weighted_sum_masked/{shape}",
               lambda s, wt, m: ops.weighted_sum_masked(
                   s, wt, m, interpret=True),
               (_sds(K, *shape), w, _sds(K, *shape)), shape)
        yield (f"weighted_sum_masked_mult/{shape}",
               lambda s, wt, m, mu: ops.weighted_sum_masked(
                   s, wt, m, mult=mu, interpret=True, renorm=False),
               (_sds(K, *shape), w, _sds(K, *shape), _sds(K, *shape)),
               shape)


def check_all() -> Tuple[List[Finding], int]:
    findings: List[Finding] = []
    n = 0
    for name, fn, avals, expect in cases():
        findings.extend(_case_findings(name, fn, avals, expect))
        n += 1
    return findings, n
