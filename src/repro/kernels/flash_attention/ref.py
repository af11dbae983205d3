"""jnp reference for the flash-attention kernels — fallback AND oracle.

Mirrors ``models.attention.blockwise_attention`` semantics exactly
(scale on q, position masks with -1 = masked key, fp32 accumulation,
``acc / max(l, 1e-30)`` normalisation) but is vectorised over the whole
query axis: no ``lax.map`` over q blocks, so it is the faster XLA path
off-TPU, and it additionally returns the log-sum-exp residual that the
hand-written backward consumes.

Layout is the kernel layout: q ``(B, KV, G, Sq, hd)``; k, v
``(B, KV, Sk, hd)``; q_pos ``(Sq,)`` / kv_pos ``(Sk,)`` int32 absolute
positions. Sequences longer than one kv block stream through a
``lax.scan`` so peak memory stays O(Sq * block_kv) per head.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _block_mask(q_pos, kv_pos, causal: bool, window: int):
    """(Sq, Sk) bool mask from absolute positions (-1 = masked key)."""
    valid = jnp.broadcast_to((kv_pos >= 0)[None, :],
                             (q_pos.shape[0], kv_pos.shape[0]))
    if causal:
        valid = valid & (q_pos[:, None] >= kv_pos[None, :])
    if window > 0:
        valid = valid & (q_pos[:, None] - kv_pos[None, :] < window)
    return valid


def _attend_block(qf, kb, vb, qpos, kpos, causal, window, m, l, acc):
    """One online-softmax step. qf (B,KV,G,Sq,hd) pre-scaled f32;
    kb/vb (B,KV,bk,hd); carry m/l (B,KV,G,Sq), acc (B,KV,G,Sq,hd)."""
    s = jnp.einsum("bkgqd,bksd->bkgqs", qf, kb.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    mask = _block_mask(qpos, kpos, causal, window)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l = l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bkgqs,bksd->bkgqd", p, vb.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    acc = acc * corr[..., None] + pv
    return m_new, l, acc


def flash_fwd_ref(q, k, v, q_pos, kv_pos, *, causal=True, window=0,
                  block_kv=128):
    """Returns (out, lse): out (B,KV,G,Sq,hd) f32, lse (B,KV,G,Sq) f32
    with lse = rowmax + log(rowsum) of the masked scores."""
    B, KV, G, Sq, hd = q.shape
    Sk = k.shape[2]
    scale = hd ** -0.5
    qf = q.astype(jnp.float32) * scale
    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, G, Sq, hd), jnp.float32)
    if Sk <= block_kv:
        m, l, acc = _attend_block(qf, k, v, q_pos, kv_pos, causal, window,
                                  m0, l0, a0)
    else:
        assert Sk % block_kv == 0, (Sk, block_kv)
        nk, bk = Sk // block_kv, block_kv
        kbs = k.reshape(B, KV, nk, bk, hd).transpose(2, 0, 1, 3, 4)
        vbs = v.reshape(B, KV, nk, bk, hd).transpose(2, 0, 1, 3, 4)
        kps = kv_pos.reshape(nk, bk)

        def body(carry, xs):
            kb, vb, kpi = xs
            return _attend_block(qf, kb, vb, q_pos, kpi, causal, window,
                                 *carry), None

        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kbs, vbs, kps))
    lmax = jnp.maximum(l, 1e-30)
    return acc / lmax[..., None], m + jnp.log(lmax)


def _bwd_block(qf, kb, vb, qpos, kpos, causal, window, lse, delta, do):
    """Per-kv-block backward. Returns (dq_partial (B,KV,G,Sq,hd),
    dk_block, dv_block (B,KV,bk,hd)) — all f32."""
    s = jnp.einsum("bkgqd,bksd->bkgqs", qf, kb.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    mask = _block_mask(qpos, kpos, causal, window)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jnp.exp(s - lse[..., None])          # normalized probs, 0 off-mask
    dv = jnp.einsum("bkgqs,bkgqd->bksd", p, do,
                    preferred_element_type=jnp.float32)
    dp = jnp.einsum("bkgqd,bksd->bkgqs", do, vb.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    ds = p * (dp - delta[..., None])
    dq = jnp.einsum("bkgqs,bksd->bkgqd", ds, kb.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    dk = jnp.einsum("bkgqs,bkgqd->bksd", ds, qf,
                    preferred_element_type=jnp.float32)
    return dq, dk, dv


def flash_bwd_ref(q, k, v, q_pos, kv_pos, out, lse, dout, *, causal=True,
                  window=0, block_kv=128):
    """Recompute-from-residuals backward. Returns (dq, dk, dv) f32 in the
    primal layouts. ``delta = rowsum(dout * out)`` is the FlashAttention-2
    normalizer correction; dk absorbs the q scale because s = (q*scale)k^T."""
    B, KV, G, Sq, hd = q.shape
    Sk = k.shape[2]
    scale = hd ** -0.5
    qf = q.astype(jnp.float32) * scale
    do = dout.astype(jnp.float32)
    delta = (do * out).sum(axis=-1)          # (B,KV,G,Sq)
    if Sk <= block_kv:
        dq, dk, dv = _bwd_block(qf, k, v, q_pos, kv_pos, causal, window,
                                lse, delta, do)
        return dq * scale, dk, dv
    assert Sk % block_kv == 0, (Sk, block_kv)
    nk, bk = Sk // block_kv, block_kv
    kbs = k.reshape(B, KV, nk, bk, hd).transpose(2, 0, 1, 3, 4)
    vbs = v.reshape(B, KV, nk, bk, hd).transpose(2, 0, 1, 3, 4)
    kps = kv_pos.reshape(nk, bk)

    def body(dq_acc, xs):
        kb, vb, kpi = xs
        dq, dk, dv = _bwd_block(qf, kb, vb, q_pos, kpi, causal, window,
                                lse, delta, do)
        return dq_acc + dq, (dk, dv)

    dq, (dks, dvs) = jax.lax.scan(
        body, jnp.zeros((B, KV, G, Sq, hd), jnp.float32), (kbs, vbs, kps))
    dk = dks.transpose(1, 2, 0, 3, 4).reshape(B, KV, Sk, hd)
    dv = dvs.transpose(1, 2, 0, 3, 4).reshape(B, KV, Sk, hd)
    return dq * scale, dk, dv
