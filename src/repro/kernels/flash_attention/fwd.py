"""Pallas TPU kernel: flash-attention FORWARD for local training.

Grid (b, kv_head, q_block, kv_block): the innermost grid dim streams kv
blocks through VMEM with an online-softmax accumulator in scratch
(m/l/acc persist across the sequential innermost dimension — TPU grid
semantics), so VMEM stays O(block_q * block_kv) per head group and the
full (Sq, Sk) score matrix never materializes.

Masking is position-based (same contract as ``models.attention``): the
caller passes absolute positions per q/kv row, -1 marks a padded key, so
causal + sliding-window + padding all reduce to one mask. Alongside the
output the kernel writes the log-sum-exp residual ``lse = m + log(l)``
that the backward kernels use to recompute attention probabilities.

TARGET: TPU. Validated via interpret=True against ``ref.flash_fwd_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Every grid step holds a whole query-head group as (G·bq, hd) tiles: at
# glm4-9b's G=16 with 128-row blocks the forward needs ~18 MiB of VMEM
# (the TPU compiler's count), past the 16 MiB default scoped limit, so
# the flash kernels raise it — v5e has 128 MiB of VMEM per core.
VMEM_LIMIT_BYTES = 64 * 2 ** 20


def _kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
            m_ref, l_ref, acc_ref, *, causal: bool, window: int, n_kv: int):
    r = pl.program_id(3)

    @pl.when(r == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                   # (G, bq, hd)
    G, bq, hd = q.shape
    k = k_ref[0, 0].astype(jnp.float32)                   # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    bk = k.shape[0]
    qpos = qpos_ref[0]                                    # (bq,)
    kpos = kpos_ref[0]                                    # (bk,)

    mask = jnp.broadcast_to((kpos >= 0)[None, :], (bq, bk))
    if causal:
        mask = mask & (qpos[:, None] >= kpos[None, :])
    if window > 0:
        mask = mask & (qpos[:, None] - kpos[None, :] < window)

    scale = hd ** -0.5
    s = jax.lax.dot_general(q.reshape(G * bq, hd) * scale, k,
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s.reshape(G, bq, bk)
    s = jnp.where(mask[None], s, NEG_INF)

    m_prev = m_ref[...]                                   # (G, bq, 1)
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
    pv = jax.lax.dot_general(p.reshape(G * bq, bk), v,
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + pv.reshape(G, bq, hd)
    m_ref[...] = m_new

    @pl.when(r == n_kv - 1)
    def _finish():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[...] + jnp.log(l_safe))[..., 0]


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_kv", "interpret"))
def flash_fwd(q, k, v, q_pos, kv_pos, *, causal: bool = True,
              window: int = 0, block_q: int = 128, block_kv: int = 128,
              interpret: bool = True):
    """q: (B, KV, G, Sq, hd); k, v: (B, KV, Sk, hd); q_pos (Sq,) /
    kv_pos (Sk,) int32 absolute positions (-1 = masked key). Sq/Sk must
    divide by the blocks. Returns (out (B,KV,G,Sq,hd) f32,
    lse (B,KV,G,Sq) f32)."""
    B, KV, G, Sq, hd = q.shape
    Sk = k.shape[2]
    bq, bk = min(block_q, Sq), min(block_kv, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, Sk, bq, bk)
    nq, nk = Sq // bq, Sk // bk
    return pl.pallas_call(
        functools.partial(_kernel, causal=causal, window=window, n_kv=nk),
        grid=(B, KV, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq), lambda b, h, qi, r: (0, qi)),
            pl.BlockSpec((1, bk), lambda b, h, qi, r: (0, r)),
            pl.BlockSpec((1, 1, G, bq, hd),
                         lambda b, h, qi, r: (b, h, 0, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, qi, r: (b, h, r, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, qi, r: (b, h, r, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G, bq, hd),
                         lambda b, h, qi, r: (b, h, 0, qi, 0)),
            pl.BlockSpec((1, 1, G, bq), lambda b, h, qi, r: (b, h, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, G, Sq, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, KV, G, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, bq, 1), jnp.float32),     # running row max
            pltpu.VMEM((G, bq, 1), jnp.float32),     # running normalizer
            pltpu.VMEM((G, bq, hd), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(q_pos.reshape(1, Sq), kv_pos.reshape(1, Sk), q, k, v)
