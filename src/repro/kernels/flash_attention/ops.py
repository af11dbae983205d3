"""Dispatch + custom_vjp for flash attention — blockwise_attention drop-in.

Auto-select follows the fedavg contract: ``use_kernel=None`` resolves to
the compiled Pallas kernels on TPU and the vectorised jnp reference
elsewhere; ``interpret=None`` means compiled on TPU, interpreter off-TPU
(only reachable when the kernel is forced on for validation).

The custom_vjp core operates on the kernel layout — q (B,KV,G,S,hd),
k/v (B,KV,S,hd) — with block-padded sequences, so every kernel block's
last two dims are (rows, hd) tiles the TPU accepts; padding,
transposition and slicing live OUTSIDE the custom_vjp so JAX
differentiates them natively. Positions are integer
primals, so the backward returns float0 cotangents for them.

Block sizes are capped at ``BLOCK_CAP`` (=128): the backward keeps
q/do/dq blocks plus a (G, bq, bk) probability tile resident per grid
cell, and 128x128 holds that under the x2-buffered VMEM budget even at
G=16 (glm4-9b's 32q/2kv grouping).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.fedavg.fedavg import on_tpu
from repro.kernels.flash_attention import bwd as _bwd
from repro.kernels.flash_attention import fwd as _fwd
from repro.kernels.flash_attention import ref as _ref

BLOCK_CAP = 128


def _float0(x):
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


def _run_fwd(q, k, v, q_pos, kv_pos, causal, window, bq, bk, use_kernel,
             interpret):
    if use_kernel:
        return _fwd.flash_fwd(q, k, v, q_pos, kv_pos, causal=causal,
                              window=window, block_q=bq, block_kv=bk,
                              interpret=interpret)
    return _ref.flash_fwd_ref(q, k, v, q_pos, kv_pos, causal=causal,
                              window=window, block_kv=bk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash(q, k, v, q_pos, kv_pos, causal, window, bq, bk, use_kernel,
           interpret):
    out, _ = _run_fwd(q, k, v, q_pos, kv_pos, causal, window, bq, bk,
                      use_kernel, interpret)
    return out


def _flash_fwd_rule(q, k, v, q_pos, kv_pos, causal, window, bq, bk,
                    use_kernel, interpret):
    out, lse = _run_fwd(q, k, v, q_pos, kv_pos, causal, window, bq, bk,
                        use_kernel, interpret)
    return out, (q, k, v, q_pos, kv_pos, out, lse)


def _flash_bwd_rule(causal, window, bq, bk, use_kernel, interpret, res,
                    dout):
    q, k, v, q_pos, kv_pos, out, lse = res
    if use_kernel:
        do = dout.astype(jnp.float32)
        delta = (do * out).sum(axis=-1)
        dq, dk, dv = _bwd.flash_bwd(q, k, v, q_pos, kv_pos, lse, delta, do,
                                    causal=causal, window=window, block_q=bq,
                                    block_kv=bk, interpret=interpret)
    else:
        dq, dk, dv = _ref.flash_bwd_ref(q, k, v, q_pos, kv_pos, out, lse,
                                        dout, causal=causal, window=window,
                                        block_kv=bk)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            _float0(q_pos), _float0(kv_pos))


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _pad_to(x, size, axis, value=0):
    pad = size - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def flash_attention(q, k, v, q_pos, kv_pos, *, causal=True, window=0,
                    block_q=512, block_kv=512, use_kernel=None,
                    interpret=None):
    """Flash attention with a hand-written backward. Same contract as
    ``models.attention.blockwise_attention``: q (B,Sq,KV,G,hd);
    k, v (B,Sk,KV,hd); q_pos (Sq,) / kv_pos (Sk,) absolute positions
    (-1 = masked key). Returns (B,Sq,KV*G,hd) in q.dtype."""
    if use_kernel is None:
        use_kernel = on_tpu()
    if interpret is None:
        interpret = not on_tpu()
    B, Sq, KV, G, hd = q.shape
    Sk = k.shape[1]
    bq = max(1, min(block_q, BLOCK_CAP, Sq))
    bk = max(1, min(block_kv, BLOCK_CAP, Sk))
    nq, nk = -(-Sq // bq), -(-Sk // bk)

    qt = _pad_to(q, nq * bq, 1).transpose(0, 2, 3, 1, 4)   # (B,KV,G,Sq',hd)
    kt = _pad_to(k, nk * bk, 1).transpose(0, 2, 1, 3)      # (B,KV,Sk',hd)
    vt = _pad_to(v, nk * bk, 1).transpose(0, 2, 1, 3)
    qpos_p = _pad_to(q_pos.astype(jnp.int32), nq * bq, 0, value=-1)
    kpos_p = _pad_to(kv_pos.astype(jnp.int32), nk * bk, 0, value=-1)

    out = _flash(qt, kt, vt, qpos_p, kpos_p, causal, window, bq, bk,
                 bool(use_kernel), bool(interpret))
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, nq * bq, KV * G, hd)
    return out[:, :Sq].astype(q.dtype)
