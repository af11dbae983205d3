"""Pallas TPU kernels: flash-attention BACKWARD (dQ and dK/dV).

Two kernels, both recomputing the attention probabilities from the
forward's log-sum-exp residual (``p = exp(s - lse)``) instead of storing
the (Sq, Sk) score matrix:

  * dQ   — grid (b, kv_head, q_block, kv_block): each q block streams the
           kv blocks, accumulating ``dq += ds @ k`` in VMEM scratch.
  * dK/dV — grid (b, kv_head, kv_block, q_block): each kv block streams
           the q blocks, accumulating ``dk += ds^T @ (q*scale)`` and
           ``dv += p^T @ do`` (summed over the G query heads of the
           group) in VMEM scratch.

``delta = rowsum(dout * out)`` (the FlashAttention-2 softmax correction)
is precomputed by the caller — it is a cheap elementwise reduction.

TARGET: TPU. Validated via interpret=True against ``ref.flash_bwd_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention.fwd import VMEM_LIMIT_BYTES

NEG_INF = -1e30


def _probs(q, k, qpos, kpos, lse, *, causal: bool, window: int):
    """Recompute normalized attention probs p (G,bq,bk) and the masked
    scaled scores' ingredients. q (G,bq,hd) f32 pre-scaled; k (bk,hd)."""
    G, bq, hd = q.shape
    bk = k.shape[0]
    mask = jnp.broadcast_to((kpos >= 0)[None, :], (bq, bk))
    if causal:
        mask = mask & (qpos[:, None] >= kpos[None, :])
    if window > 0:
        mask = mask & (qpos[:, None] - kpos[None, :] < window)
    s = jax.lax.dot_general(q.reshape(G * bq, hd), k,
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = jnp.where(mask[None], s.reshape(G, bq, bk), NEG_INF)
    return jnp.exp(s - lse[..., None])


def _dq_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, lse_ref, delta_ref,
               do_ref, dq_ref, acc_ref, *, causal: bool, window: int,
               n_kv: int):
    r = pl.program_id(3)

    @pl.when(r == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                   # (G, bq, hd)
    G, bq, hd = q.shape
    scale = hd ** -0.5
    k = k_ref[0, 0].astype(jnp.float32)                   # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    bk = k.shape[0]
    do = do_ref[0, 0].astype(jnp.float32)                 # (G, bq, hd)

    p = _probs(q * scale, k, qpos_ref[0], kpos_ref[0], lse_ref[0, 0],
               causal=causal, window=window)
    dp = jax.lax.dot_general(do.reshape(G * bq, hd), v,
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp.reshape(G, bq, bk) - delta_ref[0, 0][..., None])
    dq = jax.lax.dot_general(ds.reshape(G * bq, bk), k,
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] + dq.reshape(G, bq, hd) * scale

    @pl.when(r == n_kv - 1)
    def _finish():
        dq_ref[0, 0] = acc_ref[...]


def _dkv_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, lse_ref, delta_ref,
                do_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, causal: bool,
                window: int, n_q: int):
    r = pl.program_id(3)

    @pl.when(r == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0, 0].astype(jnp.float32)                   # (G, bq, hd)
    G, bq, hd = q.shape
    scale = hd ** -0.5
    qf = q * scale
    k = k_ref[0, 0].astype(jnp.float32)                   # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    bk = k.shape[0]
    do = do_ref[0, 0].astype(jnp.float32)                 # (G, bq, hd)

    p = _probs(qf, k, qpos_ref[0], kpos_ref[0], lse_ref[0, 0],
               causal=causal, window=window)
    # dv += p^T @ do, dk += ds^T @ qf — contract over (G, bq) jointly
    dv = jax.lax.dot_general(p.reshape(G * bq, bk), do.reshape(G * bq, hd),
                             (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do.reshape(G * bq, hd), v,
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp.reshape(G, bq, bk) - delta_ref[0, 0][..., None])
    dk = jax.lax.dot_general(ds.reshape(G * bq, bk), qf.reshape(G * bq, hd),
                             (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dk_acc[...] = dk_acc[...] + dk
    dv_acc[...] = dv_acc[...] + dv

    @pl.when(r == n_q - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...]
        dv_ref[0, 0] = dv_acc[...]


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_kv", "interpret"))
def flash_bwd(q, k, v, q_pos, kv_pos, lse, delta, dout, *,
              causal: bool = True, window: int = 0, block_q: int = 128,
              block_kv: int = 128, interpret: bool = True):
    """Inputs in the forward's layouts; lse/delta (B,KV,G,Sq) f32;
    dout (B,KV,G,Sq,hd). Returns (dq (B,KV,G,Sq,hd), dk, dv (B,KV,Sk,hd)),
    all f32."""
    B, KV, G, Sq, hd = q.shape
    Sk = k.shape[2]
    bq, bk = min(block_q, Sq), min(block_kv, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, Sk, bq, bk)
    nq, nk = Sq // bq, Sk // bk
    qp2, kp2 = q_pos.reshape(1, Sq), kv_pos.reshape(1, Sk)

    q_spec = pl.BlockSpec((1, 1, G, bq, hd),
                          lambda b, h, i, r: (b, h, 0, i, 0))
    q_spec_t = pl.BlockSpec((1, 1, G, bq, hd),
                            lambda b, h, i, r: (b, h, 0, r, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, r: (b, h, r, 0))
    kv_spec_t = pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, r: (b, h, i, 0))
    row_spec = pl.BlockSpec((1, 1, G, bq), lambda b, h, i, r: (b, h, 0, i))
    row_spec_t = pl.BlockSpec((1, 1, G, bq), lambda b, h, i, r: (b, h, 0, r))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, window=window, n_kv=nk),
        grid=(B, KV, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq), lambda b, h, i, r: (0, i)),
            pl.BlockSpec((1, bk), lambda b, h, i, r: (0, r)),
            q_spec, kv_spec, kv_spec, row_spec, row_spec, q_spec,
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, Sq, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((G, bq, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(qp2, kp2, q, k, v, lse, delta, dout)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, window=window, n_q=nq),
        grid=(B, KV, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq), lambda b, h, i, r: (0, r)),
            pl.BlockSpec((1, bk), lambda b, h, i, r: (0, i)),
            q_spec_t, kv_spec_t, kv_spec_t, row_spec_t, row_spec_t, q_spec_t,
        ],
        out_specs=[kv_spec_t, kv_spec_t],
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, Sk, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, KV, Sk, hd), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, hd), jnp.float32),       # dk accumulator
            pltpu.VMEM((bk, hd), jnp.float32),       # dv accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(qp2, kp2, q, k, v, lse, delta, dout)
    return dq, dk, dv
