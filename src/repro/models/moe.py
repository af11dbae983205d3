"""Mixture-of-Experts FFN with sort-based (MegaBlocks-style, TPU-adapted)
dispatch.

Distribution strategy (see DESIGN.md §5): experts are sharded over the
``model`` mesh axis; activations enter replicated over ``model`` and
sharded over the data axes. Each device computes the contribution of its
local experts to its local tokens and the results are combined with a
``psum`` over ``model`` ("EP with replicated activations"). An optional
all-to-all dispatch variant (``ctx.moe_all_to_all``) is a §Perf knob.

The identical math runs single-device (CPU smoke tests) when no mesh is
present.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import dense_init, mlp_apply, mlp_init
from repro.sharding.ctx import CPU_CTX, ShardCtx


def moe_init(key, cfg, dtype):
    m = cfg.moe
    D, E, Fe = cfg.d_model, m.n_experts, m.d_ff_expert
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (D, E), dtype),
        # router bias: zero at init; NetChange expert duplication shifts the
        # duplicates by -log(group size) here (a logit shift cannot be
        # expressed in the weight matrix).
        "router_b": jnp.zeros((E,), dtype),
        "wg": dense_init(ks[1], (E, D, Fe), dtype, fan_in=D),
        "wu": dense_init(ks[2], (E, D, Fe), dtype, fan_in=D),
        "wd": dense_init(ks[3], (E, Fe, D), dtype, fan_in=Fe),
    }
    if m.n_shared:
        # shared experts: one fused MLP of width n_shared * d_ff_shared
        import dataclasses
        shared_cfg = dataclasses.replace(cfg, mlp_kind="swiglu")
        p["shared"] = mlp_init(ks[4], shared_cfg, D,
                               m.n_shared * m.d_ff_shared, dtype)
    return p


def _route(router, x2d, top_k, router_b=None):
    logits = (x2d @ router).astype(jnp.float32)               # (N,E)
    if router_b is not None:
        logits = logits + router_b.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    wts, ids = jax.lax.top_k(probs, top_k)                    # (N,k)
    wts = wts / jnp.maximum(wts.sum(-1, keepdims=True), 1e-9)
    return wts, ids, probs


def _capacity(n_tokens, top_k, n_experts_total, cf):
    return max(1, int(n_tokens * top_k / n_experts_total * cf) + 1)


def _dispatch_ffn_combine(x2d, ids, wts, wg, wu, wd, *, e_offset, n_experts_total,
                          capacity):
    """Sort-based dispatch -> per-expert matmuls -> weighted combine.

    x2d (N,D); ids/wts (N,k); wg/wu/wd local expert stacks (E_loc, ...).
    Tokens routed to experts outside [e_offset, e_offset+E_loc) contribute 0.
    """
    N, D = x2d.shape
    k = ids.shape[1]
    E_loc = wg.shape[0]
    C = capacity

    flat_ids = ids.reshape(-1) - e_offset                     # (N*k,)
    in_range = (flat_ids >= 0) & (flat_ids < E_loc)
    sort_key = jnp.where(in_range, flat_ids, E_loc)
    order = jnp.argsort(sort_key)                             # stable
    sid = sort_key[order]
    tok = order // k

    counts = jnp.bincount(sid, length=E_loc + 1)[:E_loc]
    starts = jnp.cumsum(counts) - counts                      # exclusive cumsum
    rank = jnp.arange(N * k) - starts[jnp.clip(sid, 0, E_loc - 1)]
    keep = (sid < E_loc) & (rank >= 0) & (rank < C)

    dest_e = jnp.where(keep, sid, 0)
    dest_c = jnp.where(keep, rank, C)                         # overflow row C
    buf = jnp.zeros((E_loc, C + 1, D), x2d.dtype)
    buf = buf.at[dest_e, dest_c].set(x2d[tok] * keep[:, None].astype(x2d.dtype))
    buf = buf[:, :C]                                          # (E_loc,C,D)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * jnp.einsum(
        "ecd,edf->ecf", buf, wu)
    y_buf = jnp.einsum("ecf,efd->ecd", h, wd)                 # (E_loc,C,D)

    gath = y_buf[dest_e, jnp.minimum(dest_c, C - 1)]          # (N*k,D)
    gate = wts.reshape(-1)[order]
    contrib = gath * (gate * keep).astype(gath.dtype)[:, None]
    out = jnp.zeros((N, D), x2d.dtype).at[tok].add(contrib)
    return out


def _moe_routed(x, p, cfg, *, e_offset=0, axis_name=None):
    """Routed-experts part. x: (B,S,D) local shard; expert stacks local."""
    m = cfg.moe
    B, S, D = x.shape
    x2d = x.reshape(-1, D)
    wts, ids, _ = _route(p["router"], x2d, m.top_k, p.get("router_b"))
    C = _capacity(x2d.shape[0], m.top_k, m.n_experts, m.capacity_factor)
    out = _dispatch_ffn_combine(x2d, ids, wts, p["wg"], p["wu"], p["wd"],
                                e_offset=e_offset, n_experts_total=m.n_experts,
                                capacity=C)
    if axis_name is not None:
        out = jax.lax.psum(out, axis_name)
    return out.reshape(B, S, D)


def moe_apply(p, cfg, x, ctx: ShardCtx = CPU_CTX):
    """x: (B,S,D) global. Dispatch + expert FFN + combine (+ shared experts)."""
    m = cfg.moe
    if not ctx.distributed or m.n_experts % ctx.model_size:
        # single device, or fewer experts than model shards: keep experts
        # whole and let XLA tensor-parallelize d_ff_expert (rules.py shards
        # wg/wu/wd over `model` on the F axis in that regime).
        out = _moe_routed(x, p, cfg)
    else:
        mesh = ctx.mesh
        ma = ctx.model_axis
        da = ctx.data_axes if ctx.data_axes else None
        E = m.n_experts
        msize = mesh.shape[ma]

        def local_fn(x_l, router, router_b, wg, wu, wd):
            e_off = jax.lax.axis_index(ma) * (E // msize)
            p_l = {"router": router, "router_b": router_b,
                   "wg": wg, "wu": wu, "wd": wd}
            return _moe_routed(x_l, p_l, cfg, e_offset=e_off, axis_name=ma)

        x_spec = P(da, None, None)
        fn = jax.shard_map(local_fn, mesh=mesh,
                           in_specs=(x_spec, P(), P(), P(ma), P(ma), P(ma)),
                           out_specs=x_spec, check_vma=False)
        out = fn(x, p["router"], p["router_b"], p["wg"], p["wu"], p["wd"])
    if m.n_shared:
        out = out + mlp_apply(p["shared"], x, "swiglu")
    return out
