"""Primitive layers shared by every architecture family."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def dense_init(key, shape, dtype, fan_in: Optional[int] = None):
    fan_in = fan_in or shape[-2] if len(shape) >= 2 else shape[-1]
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


def rms_norm(x, scale, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                         # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                         # (..., S, 1, hd/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


def mlp_init(key, cfg, d_model: int, d_ff: int, dtype):
    ks = jax.random.split(key, 3)
    bias = cfg.mlp_bias
    if cfg.mlp_kind == "gelu":
        p = {"wi": dense_init(ks[0], (d_model, d_ff), dtype),
             "wd": dense_init(ks[1], (d_ff, d_model), dtype)}
        if bias:
            p["bi"] = jnp.zeros((d_ff,), dtype)
            p["bd"] = jnp.zeros((d_model,), dtype)
        return p
    p = {"wg": dense_init(ks[0], (d_model, d_ff), dtype),
         "wu": dense_init(ks[1], (d_model, d_ff), dtype),
         "wd": dense_init(ks[2], (d_ff, d_model), dtype)}
    return p


def mlp_apply(p, x, mlp_kind: str, ctx=None):
    if mlp_kind == "gelu":
        h = x @ p["wi"]
        if "bi" in p:
            h = h + p["bi"]
        h = gelu(h)
        out = tp_row_matmul(h, p["wd"], ctx)
        if "bd" in p:
            out = out + p["bd"]
        return out
    act = gelu if mlp_kind == "geglu" else jax.nn.silu
    return tp_row_matmul(act(x @ p["wg"]) * (x @ p["wu"]), p["wd"], ctx)


def tp_row_matmul(h, w, ctx=None):
    """Row-parallel projection  y = h @ w  with the contraction dim sharded
    over the model axis (attention wo, MLP wd). With ``ctx.tp_bf16_reduce``
    the partial sums are cast to the activation dtype BEFORE the psum —
    XLA's default emits an f32 all-reduce + convert (2x collective bytes;
    verified in EXPERIMENTS.md §Perf glm4 iteration 4)."""
    if ctx is None or not (getattr(ctx, "distributed", False)
                           and ctx.tp_bf16_reduce):
        return h @ w
    K = h.shape[-1]
    m = ctx.model_size
    if K % m or w.shape[0] != K:
        return h @ w
    from jax.sharding import PartitionSpec as P
    ma = ctx.model_axis
    dp = ctx.data_axes if ctx.data_axes else None
    lead = (dp,) + (None,) * (h.ndim - 2)
    hspec = P(*lead, ma)
    ospec = P(*lead, None)

    def local(hl, wl):
        return jax.lax.psum((hl @ wl).astype(h.dtype), ma)

    return jax.shard_map(local, mesh=ctx.mesh, in_specs=(hspec, P(ma, None)),
                         out_specs=ospec, check_vma=False)(h, w)


def causal_conv1d(x, kernel, state=None):
    """Depthwise causal conv along time. x: (B, S, C), kernel: (W, C).

    Returns (out, new_state) where state is the last W-1 inputs (B, W-1, C).
    """
    W = kernel.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], W - 1, x.shape[-1]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)              # (B, S+W-1, C)
    out = jnp.zeros_like(x)
    for i in range(W):
        out = out + xp[:, i : i + x.shape[1], :] * kernel[i]
    new_state = xp[:, -(W - 1):, :] if W > 1 else state
    return out, new_state
