"""Plain reference of one FedADP round on a VGG cohort.

Written from the method's description (FedADP §III, Alg. 2 and 3), in
straightforward ``jax.numpy`` at float32 with every convolution and
matrix product at ``Precision.HIGHEST``. It imports nothing of the
system under test. A round, for each client k of the cohort:

  1. distribute: To-Narrower (Alg. 3: drop the channels past the
     client's width, add the dropped outgoing rows' sum / N_tar to each
     survivor) then To-Shallower (drop the trailing layers of a stage);
  2. local training in the client's own architecture: SGD with
     momentum (``mu = m*mu + g; p -= lr*mu``, momentum fresh each round)
     on mean cross-entropy, one step per batch;
  3. collect: To-Deeper (identity 3x3 convs appended at the end of a
     stage) then To-Wider (Alg. 2: new channels duplicate channels drawn
     from a stream keyed by ``(layer tag, widths, round seed)``; the
     next layer's incoming rows are split by the duplicate count);
  4. aggregate (Eq. 1-2, filler "zero"): the new global model is
     ``sum_k n_k / n * collect_k``.

The round seed of client k in round r is
``(base * 1_000_003 + r * 997 + k) mod 2**31`` and the To-Wider stream
is numpy's default generator seeded with the first 8 bytes (big-endian)
of ``sha256("<tag>:<old>:<new>:<seed>")``: the protocol fixes both so
that server and clients derive the same mapping without talking.

A config here is a plain dict: ``stages`` (a list of per-stage lists of
conv widths), ``classifier``, ``n_classes``, ``in_channels``,
``image_size``.
"""
from __future__ import annotations

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------- structure
def union(cfgs):
    """Elementwise union: max depth per stage, max width per layer."""
    stages = []
    for si in range(len(cfgs[0]["stages"])):
        depth = max(len(c["stages"][si]) for c in cfgs)
        stages.append([max(c["stages"][si][li] for c in cfgs
                           if li < len(c["stages"][si]))
                       for li in range(depth)])
    out = dict(cfgs[0])
    out["stages"] = stages
    out["classifier"] = [max(c["classifier"][i] for c in cfgs)
                         for i in range(len(cfgs[0]["classifier"]))]
    return out


def chain(cfg):
    nodes = [("conv", si, li) for si, ws in enumerate(cfg["stages"])
             for li in range(len(ws))]
    nodes += [("fc", fi) for fi in range(len(cfg["classifier"]))]
    return nodes + [("out",)]


def width(cfg, node):
    if node[0] == "conv":
        return cfg["stages"][node[1]][node[2]]
    if node[0] == "fc":
        return cfg["classifier"][node[1]]
    return cfg["n_classes"]


def _key(node):
    if node[0] == "conv":
        return ("stages", f"s{node[1]}", f"c{node[2]}")
    if node[0] == "fc":
        return ("fc", f"f{node[1]}")
    return ("out",)


def _get(params, node):
    d = params
    for k in _key(node):
        d = d[k]
    return d


def _set(params, node, layer):
    path = _key(node)
    d = params
    for k in path[:-1]:
        d = d[k]
    d[path[-1]] = layer


def _copy(params):
    return jax.tree.map(lambda x: x, params)


def flat_spatial(cfg) -> int:
    """Spatial positions left after the last pool (rows per channel of
    the first fc, channel fastest)."""
    return (cfg["image_size"] // 2 ** len(cfg["stages"])) ** 2


# -------------------------------------------------------------- weights
def init_params(key, cfg):
    """He-normal weights, zero biases, one leaf key per layer."""
    params = {"stages": {}, "fc": {}}
    cin = cfg["in_channels"]
    i = 0
    for si, ws in enumerate(cfg["stages"]):
        stage = {}
        for li, cout in enumerate(ws):
            w = jax.random.normal(jax.random.fold_in(key, i),
                                  (3, 3, cin, cout), jnp.float32)
            stage[f"c{li}"] = {"w": w * math.sqrt(2.0 / (9 * cin)),
                               "b": jnp.zeros((cout,), jnp.float32)}
            cin, i = cout, i + 1
        params["stages"][f"s{si}"] = stage
    din = cin * flat_spatial(cfg)
    for fi, dout in enumerate(cfg["classifier"]):
        w = jax.random.normal(jax.random.fold_in(key, i), (din, dout),
                              jnp.float32)
        params["fc"][f"f{fi}"] = {"w": w * math.sqrt(2.0 / din),
                                  "b": jnp.zeros((dout,), jnp.float32)}
        din, i = dout, i + 1
    w = jax.random.normal(jax.random.fold_in(key, i),
                          (din, cfg["n_classes"]), jnp.float32)
    params["out"] = {"w": w * math.sqrt(2.0 / din),
                     "b": jnp.zeros((cfg["n_classes"],), jnp.float32)}
    return params


# -------------------------------------------------------------- forward
def conv3x3(x, w, dtype=jnp.float32):
    """3x3 SAME convolution, stride 1, NHWC x HWIO, as one matrix product
    of the nine shifted views (im2col) with the flattened kernel. Its
    gradients are matrix products too, which the TPU compiles at
    ``HIGHEST``; XLA's own convolution gradient at ``HIGHEST`` does not
    compile in bounded memory there."""
    b, h, wd, c = x.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    cols = jnp.concatenate([xp[:, i:i + h, j:j + wd, :]
                            for i in range(3) for j in range(3)], axis=-1)
    return jnp.dot(cols, w.astype(dtype).reshape(9 * c, -1), precision=HI)


def apply(params, x, dtype=jnp.float32):
    """x (B, H, W, C) -> logits (B, n_classes) in float32."""
    x = x.astype(dtype)
    for si in range(len(params["stages"])):
        stage = params["stages"][f"s{si}"]
        for li in range(len(stage)):
            p = stage[f"c{li}"]
            x = jax.nn.relu(conv3x3(x, p["w"], dtype) + p["b"].astype(dtype))
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                  (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    for fi in range(len(params["fc"])):
        p = params["fc"][f"f{fi}"]
        x = jax.nn.relu(jnp.dot(x, p["w"].astype(dtype), precision=HI)
                        + p["b"].astype(dtype))
    p = params["out"]
    out = jnp.dot(x, p["w"].astype(dtype), precision=HI) + p["b"].astype(dtype)
    return out.astype(jnp.float32)


def loss(params, x, y, valid, dtype=jnp.float32):
    """Mean cross-entropy over the valid rows of a padded batch."""
    logits = apply(params, x, dtype)
    ll = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(ll, y[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * valid) / jnp.sum(valid)


# ------------------------------------------------------------- training
def _train(params, xs, ys, vs, lr, momentum, dtype, store=jnp.float32):
    """SGD with momentum over stacked batches (S, B, ...), one step per
    batch. ``dtype`` is the compute type of the forward and backward
    passes; parameters and momentum are kept in ``store`` and returned
    as float32."""
    grad = jax.grad(lambda p, x, y, v: loss(p, x, y, v, dtype))
    params = jax.tree.map(lambda a: a.astype(store), params)
    mu0 = jax.tree.map(jnp.zeros_like, params)

    def step(carry, batch):
        p, mu = carry
        g = grad(p, *batch)
        mu = jax.tree.map(lambda m, gi: (momentum * m + gi).astype(m.dtype),
                          mu, g)
        p = jax.tree.map(lambda a, m: (a - lr * m).astype(a.dtype), p, mu)
        return (p, mu), None

    (params, _), _ = jax.lax.scan(step, (params, mu0), (xs, ys, vs))
    return jax.tree.map(lambda a: a.astype(jnp.float32), params)


train = jax.jit(_train, static_argnames=("dtype", "store"))


# ----------------------------------------------------------- NetChange
def dup_mapping(old: int, new: int, tag: str, seed: int) -> np.ndarray:
    h = int.from_bytes(hashlib.sha256(f"{tag}:{old}:{new}:{seed}".encode())
                       .digest()[:8], "big")
    extra = np.random.default_rng(h).integers(0, old, size=new - old)
    return np.concatenate([np.arange(old), extra]).astype(np.int32)


def round_seed(base: int, round_idx: int, k: int) -> int:
    return (base * 1_000_003 + round_idx * 997 + k) % (2 ** 31)


def _in_axis(node, nxt):
    """The axis of ``nxt``'s weight that reads ``node``'s channels, and
    whether it is the conv -> fc flatten (rows (spatial, channel))."""
    if nxt[0] == "conv":
        return 2, False
    return 0, node[0] == "conv"


def _rows(w, axis, flatten, sp, fn):
    """Apply ``fn(w, axis)`` to the incoming-channel axis of a weight,
    through the (spatial, channel) view at the flatten boundary."""
    if not flatten:
        return fn(w, axis)
    w3 = w.reshape(sp, -1, w.shape[1])
    out = fn(w3, 1)
    return out.reshape(-1, w.shape[1])


def down(g, ucfg, ccfg):
    """Global -> client: To-Narrower (Alg. 3) then To-Shallower."""
    p = _copy(g)
    nodes = chain(ucfg)
    sp = flat_spatial(ucfg)
    for i, node in enumerate(nodes[:-1]):
        if node[0] == "conv":
            if node[2] >= len(ccfg["stages"][node[1]]):
                continue
            new = ccfg["stages"][node[1]][node[2]]
        else:
            new = ccfg["classifier"][node[1]]
        old = width(ucfg, node)
        if new == old:
            continue
        layer = dict(_get(p, node))
        out_axis = 3 if node[0] == "conv" else 1
        layer["w"] = jax.lax.slice_in_dim(layer["w"], 0, new, axis=out_axis)
        layer["b"] = layer["b"][:new]
        _set(p, node, layer)
        nxt = nodes[i + 1]
        nl = dict(_get(p, nxt))
        axis, flatten = _in_axis(node, nxt)

        def narrow(w, ax, new=new):
            kept = jax.lax.slice_in_dim(w, 0, new, axis=ax)
            dropped = jax.lax.slice_in_dim(w, new, w.shape[ax], axis=ax)
            return kept + dropped.sum(axis=ax, keepdims=True) / new

        nl["w"] = _rows(nl["w"], axis, flatten, sp, narrow)
        _set(p, nxt, nl)
    for si, ws in enumerate(ccfg["stages"]):
        for li in range(len(ws), len(ucfg["stages"][si])):
            del p["stages"][f"s{si}"][f"c{li}"]
    return p


def up(c, ccfg, ucfg, seed: int):
    """Client -> global: To-Deeper (identity convs) then To-Wider (Alg. 2)."""
    p = _copy(c)
    mid = {}
    for si, uws in enumerate(ucfg["stages"]):
        cws = ccfg["stages"][si]
        ch = cws[-1]
        for li in range(len(uws)):
            mid[("conv", si, li)] = cws[li] if li < len(cws) else ch
        for li in range(len(cws), len(uws)):
            eye = jnp.zeros((3, 3, ch, ch), jnp.float32)
            p["stages"][f"s{si}"][f"c{li}"] = {
                "w": eye.at[1, 1].set(jnp.eye(ch, dtype=jnp.float32)),
                "b": jnp.zeros((ch,), jnp.float32)}
    for fi, w in enumerate(ccfg["classifier"]):
        mid[("fc", fi)] = w
    nodes = chain(ucfg)
    sp = flat_spatial(ucfg)
    for i, node in enumerate(nodes[:-1]):
        old, new = mid[node], width(ucfg, node)
        if new == old:
            continue
        m = dup_mapping(old, new, "/".join(map(str, node)), seed)
        layer = dict(_get(p, node))
        out_axis = 3 if node[0] == "conv" else 1
        layer["w"] = jnp.take(layer["w"], m, axis=out_axis)
        layer["b"] = jnp.take(layer["b"], m, axis=0)
        _set(p, node, layer)
        nxt = nodes[i + 1]
        nl = dict(_get(p, nxt))
        axis, flatten = _in_axis(node, nxt)
        scale = (1.0 / np.bincount(m, minlength=old)[m]).astype(np.float32)

        def split(w, ax, m=m, scale=scale):
            shape = [1] * w.ndim
            shape[ax] = -1
            return jnp.take(w, m, axis=ax) * scale.reshape(shape)

        nl["w"] = _rows(nl["w"], axis, flatten, sp, split)
        _set(p, nxt, nl)
    return p


# ---------------------------------------------------------------- round
def fedadp_round(g, ucfg, client_cfgs, n_samples, batches, *, round_idx,
                 base_seed, lr, momentum, dtype=jnp.float32,
                 store=jnp.float32):
    """One round from global ``g``; ``batches[k]`` is client k's
    ``(xs, ys, valid)`` stacked over its local steps; ``dtype`` is the
    compute type of local training and ``store`` the type its parameters
    and momentum are kept in. Returns the new global model."""
    n = np.asarray(n_samples, np.float64)
    w = (n / n.sum()).astype(np.float32)
    acc = None
    for k, ccfg in enumerate(client_cfgs):
        xs, ys, vs = batches[k]
        c = train(down(g, ucfg, ccfg), xs, ys, vs, lr, momentum,
                  dtype=dtype, store=store)
        u = up(c, ccfg, ucfg, round_seed(base_seed, round_idx, k))
        acc = (jax.tree.map(lambda a: w[k] * a, u) if acc is None else
               jax.tree.map(lambda s, a: s + w[k] * a, acc, u))
    return acc
