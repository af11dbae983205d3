"""Pure-jnp oracles for the fedavg aggregation kernels.

The weighted sums over clients are dots; they run at full f32 precision
(``Precision.HIGHEST``) so the oracle stays exact on a TPU, whose default
matmul precision would round the operands to bf16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_F32 = jax.lax.Precision.HIGHEST


def weighted_sum_ref(x, w):
    """x: (K, N); w: (K,) -> (N,) fp32."""
    return jnp.einsum("k,kn->n", w.astype(jnp.float32),
                      x.astype(jnp.float32), precision=_F32)


def plane_agg_ref(x, w, *, masks=None, mult=None, fallback=None,
                  renorm: bool = True):
    """x [, masks, mult]: (K, N); w: (K,); [fallback: (N,)] -> (N,) fp32.

    Oracle for the fused whole-plane kernel (``fedavg.plane_agg_2d``):
    coverage-weighted (optionally multiplicity-aware) average with the
    fallback substituted on coordinates no client covers."""
    if masks is None:
        assert mult is None and fallback is None
        return weighted_sum_ref(x, w)
    out = weighted_sum_masked_ref(x, w, masks, mult=mult, renorm=renorm)
    if fallback is not None:
        covered = jnp.sum(masks.astype(jnp.float32), axis=0) > 0
        out = jnp.where(covered, out, fallback.astype(jnp.float32))
    return out


def plane_accum_ref(num, den, cov, x, w, m=None, mu=None):
    """Streaming accumulate oracle: num/den/cov ``(N,)`` (or ``(1, N)``)
    running buffers, x [, m, mu]: ``(K_chunk, N)``, w: ``(K_chunk,)`` ->
    the updated (num, den, cov). One chunk of
    ``fedavg.plane_accum_2d``'s math: num += Σ w·m[/mu]·x,
    den += Σ w·m[/mu], cov += Σ m (m = 1 when absent)."""
    keep = num.ndim == 2
    xf = x.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    if m is None and mu is None:
        # unmasked Eq. 1 chunk: one dot instead of (K_chunk, N)
        # temporaries — den/cov updates collapse to scalars
        s = jnp.dot(wf, xf, precision=_F32)
        kc = jnp.float32(x.shape[0])
        return (num + (s[None] if keep else s),
                den + jnp.sum(wf), cov + kc)
    mf = m.astype(jnp.float32) if m is not None else jnp.ones_like(xf)
    wm = wf[:, None] * mf
    if mu is not None:
        muf = mu.astype(jnp.float32)
        wm = wm / jnp.where(muf > 0, muf, 1.0)
    return (num + jnp.sum(wm * xf, axis=0, keepdims=keep),
            den + jnp.sum(wm, axis=0, keepdims=keep),
            cov + jnp.sum(mf, axis=0, keepdims=keep))


def dequantize_ref(xq, s, *, tile: int = 256):
    """int8 ``(K, N)`` payload + per-tile scales ``(K, ceil(N/tile))``
    -> f32 ``(K, N)``.  Mirrors ``core.quant.dequantize`` (q·scale per
    dense tile; the trailing partial tile reads the same scale)."""
    K, n = xq.shape
    pad = (-n) % tile
    x = xq.astype(jnp.float32)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    x = x.reshape(K, -1, tile) * s.astype(jnp.float32)[:, :, None]
    return x.reshape(K, -1)[:, :n]


def plane_accum_q_ref(num, den, cov, xq, s, w, m=None, mu=None, base=None,
                      *, tile: int = 256):
    """Fused dequantize-accumulate oracle (``fedavg.plane_accum_q_2d``):
    dequantize the int8 chunk, optionally fold the uncovered
    coordinates onto ``base`` (filler_mode="global": x·m + base·(1−m),
    then an UNMASKED accumulate), and run the plain streaming
    accumulate math."""
    x = dequantize_ref(xq, s, tile=tile)
    if base is not None:
        assert m is not None and mu is None, \
            "fold needs masks and is exclusive with mult"
        mf = m.astype(jnp.float32)
        bf = base.astype(jnp.float32).reshape(1, -1)
        x = x * mf + bf * (1.0 - mf)
        m = None
    return plane_accum_ref(num, den, cov, x, w, m, mu)


def plane_finish_ref(num, den, cov, fallback=None, *, renorm: bool = True):
    """The one divide pass closing a streamed accumulation (oracle for
    ``fedavg.plane_finish_2d``): renorm divides num by den where den > 0;
    coordinates no client ever covered (cov == 0) take ``fallback`` —
    exactly the whole-plane kernel's tail, so accumulate-then-finish
    equals ``plane_agg_ref``."""
    out = num.astype(jnp.float32)
    if renorm:
        den = den.astype(jnp.float32)
        out = jnp.where(den > 0, out / jnp.where(den > 0, den, 1.0), 0.0)
    if fallback is not None:
        out = jnp.where(cov > 0, out, fallback.astype(jnp.float32))
    return out


def weighted_sum_masked_ref(x, w, m, *, mult=None, renorm: bool = True):
    """x, m [, mult]: (K, N); w: (K,) -> (N,) fp32 — coverage-weighted
    average; with ``mult`` the per-coordinate client weight is
    ``w_k m_k / mult_k`` (multiplicity-aware)."""
    wm = w.astype(jnp.float32)[:, None] * m.astype(jnp.float32)
    if mult is not None:
        mu = mult.astype(jnp.float32)
        wm = wm / jnp.where(mu > 0, mu, 1.0)
    num = jnp.sum(wm * x.astype(jnp.float32), axis=0)
    if not renorm:
        return num
    den = jnp.sum(wm, axis=0)
    return jnp.where(den > 0, num / jnp.where(den > 0, den, 1.0), 0.0)
