"""NetChange generalized to the transformer families of the assigned pool
(beyond-paper: the paper only treats VGG; see DESIGN.md §2).

Client variants of a family vary in
  * depth        — number of pattern units (stacked leading axis),
  * FFN width    — d_ff / d_ff_expert / shared width,
  * expert count — MoE routed experts,
  * d_rnn        — RG-LRU recurrent width.
d_model / heads / vocab are held fixed within a family: widening d_model
through an RMSNorm is NOT function preserving (the rms denominator changes
under channel duplication) — recorded in DESIGN.md §Arch-applicability.

Transforms:
  up():   To-Wider (Net2Net duplicate+split, exact) + To-Deeper (all-zero
          blocks => identity under pre-norm residual, exact).
  down(): To-Narrower (paper Alg. 3 mass-redistribution, lossy; or the
          beyond-paper ``fold`` inverse) + To-Shallower (slice the stack).

MoE expert duplication copies expert weights and shifts duplicated router
columns by -log(group size): exact under soft routing, approximate under
top-k (noted).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import netchange as nc
from repro.core import segments as sg
from repro.models import transformer as T


# ----------------------------------------------------------------- variants

def make_variant(cfg: ModelConfig, *, n_units: Optional[int] = None,
                 ffn_scale: float = 1.0, n_experts: Optional[int] = None,
                 d_rnn: Optional[int] = None) -> ModelConfig:
    kw: Dict[str, Any] = {}
    if n_units is not None:
        assert 1 <= n_units <= cfg.n_units
        kw["n_layers"] = n_units * cfg.pattern_len + len(cfg.rem_kinds)
    if ffn_scale != 1.0 and cfg.d_ff:
        kw["d_ff"] = _round8(cfg.d_ff * ffn_scale)
    if cfg.moe is not None:
        m = cfg.moe
        # ffn_scale=1.0 must be the identity: rounding an unscaled width
        # through _round8 would silently mutate the config (and push the
        # cohort out of the segment-representable domain)
        kw["moe"] = dataclasses.replace(
            m,
            n_experts=n_experts if n_experts is not None else m.n_experts,
            top_k=min(m.top_k, n_experts if n_experts is not None else m.n_experts),
            d_ff_expert=(_round8(m.d_ff_expert * ffn_scale)
                         if ffn_scale != 1.0 else m.d_ff_expert),
            d_ff_shared=(_round8(m.d_ff_shared * ffn_scale)
                         if ffn_scale != 1.0 and m.n_shared else m.d_ff_shared),
        )
    if d_rnn is not None and cfg.ssm is not None:
        kw["ssm"] = dataclasses.replace(cfg.ssm, d_rnn=d_rnn)
    name = cfg.name + f"-u{n_units or cfg.n_units}f{ffn_scale}e{n_experts or 0}"
    return dataclasses.replace(cfg, name=name, **kw)


def _round8(x: float) -> int:
    return max(8, int(round(x / 8) * 8))


def union(cfgs) -> ModelConfig:
    """Global architecture = elementwise max (paper §III.B)."""
    base = max(cfgs, key=lambda c: c.n_layers)
    kw: Dict[str, Any] = {
        "n_layers": max(c.n_layers for c in cfgs),
        "d_ff": max(c.d_ff for c in cfgs),
        "name": cfgs[0].name.split("-u")[0] + "-union",
    }
    if base.moe is not None:
        kw["moe"] = dataclasses.replace(
            base.moe,
            n_experts=max(c.moe.n_experts for c in cfgs),
            top_k=max(c.moe.top_k for c in cfgs),
            d_ff_expert=max(c.moe.d_ff_expert for c in cfgs),
            d_ff_shared=max(c.moe.d_ff_shared for c in cfgs),
        )
    if base.ssm is not None:
        kw["ssm"] = dataclasses.replace(
            base.ssm, d_rnn=max(c.d_rnn for c in cfgs))
    return dataclasses.replace(base, **kw)


# ----------------------------------------------------- per-block transforms

def _ffn_leaves(block: Dict) -> Dict[str, Any]:
    """Return {key: (container, in/out role, axis-from-end)} for FFN width."""
    roles = {}
    if "mlp" in block:
        roles["mlp"] = block["mlp"]
    if "moe" in block and "shared" in block["moe"]:
        roles["shared"] = block["moe"]["shared"]
    return roles


_MLP_SPEC = {"wg": ("in", -1), "wu": ("in", -1), "wi": ("in", -1),
             "bi": ("in", -1), "wd": ("out", -2)}
# bd (output bias) is width-invariant.


def _apply_width(w, role, axis, mapping, old, mode):
    if mode == "widen":
        return (nc.widen_in(w, mapping, axis=axis) if role == "in"
                else nc.widen_out(w, mapping, old, axis=axis))
    return (nc.narrow_fold_in(w, mapping, old, axis=axis) if role == "in"
            else nc.narrow_fold_out(w, mapping, old, axis=axis))


# Every width transform below reads its To-Wider mapping from ``maps``
# (``width_mappings``: tag -> mapping, possibly traced); "narrow_fold"
# reads the mapping of the reverse widening, whose base is the client
# width ``new``.

def _transform_mlp(mlp, old: int, new: int, tag: str, maps, mode: str):
    out = dict(mlp)
    for k, (role, ax) in _MLP_SPEC.items():
        if k not in out:
            continue
        if mode == "narrow_paper":
            out[k] = (nc.narrow_in(out[k], new, axis=ax) if role == "in"
                      else nc.narrow_out_paper(out[k], new, axis=ax))
        else:
            out[k] = _apply_width(out[k], role, ax, maps[tag],
                                  old if mode == "widen" else new, mode)
    return out


_EXPERT_AXIS = {"wg": -3, "wu": -3, "wd": -3}


def _transform_experts(moe, old_e: int, new_e: int, tag: str, maps,
                       mode: str):
    """Expert-count change: duplicate whole experts; router columns get a
    -log(group size) shift (exact under soft routing)."""
    out = dict(moe)
    if mode == "widen":
        mapping = maps[tag + "/exp"]
        counts = nc.mapping_counts(mapping, old_e)
        for k, ax in _EXPERT_AXIS.items():
            out[k] = nc.widen_in(out[k], mapping, axis=ax)
        out["router"] = nc.widen_in(out["router"], mapping, axis=-1)
        # logit shift lives in the router BIAS: softmax mass of a duplicate
        # group equals the original expert's mass (exact under soft routing)
        b = nc.widen_in(out["router_b"], mapping, axis=-1)
        shift = jnp.log(counts[jnp.asarray(mapping)].astype(jnp.float32))
        out["router_b"] = b - shift.astype(b.dtype)
    elif mode == "narrow_paper":
        for k, ax in _EXPERT_AXIS.items():
            out[k] = nc.narrow_in(out[k], new_e, axis=ax)
        out["router"] = nc.narrow_in(out["router"], new_e, axis=-1)
        out["router_b"] = nc.narrow_in(out["router_b"], new_e, axis=-1)
    else:
        mapping = maps[tag + "/exp"]
        counts = nc.mapping_counts(mapping, new_e)
        for k, ax in _EXPERT_AXIS.items():
            out[k] = nc.narrow_fold_in(out[k], mapping, new_e, axis=ax)
        out["router"] = nc.narrow_fold_in(out["router"], mapping, new_e,
                                          axis=-1)
        b = nc.narrow_fold_in(out["router_b"], mapping, new_e, axis=-1)
        shift = jnp.log(counts.astype(jnp.float32))
        out["router_b"] = b + shift.astype(b.dtype)
    return out


_RG_SPEC = {"win": ("in", -1), "wgate": ("in", -1), "conv": ("in", -1),
            "ba": ("in", -1), "bx": ("in", -1), "lam": ("in", -1),
            "wa": ("both", None), "wx": ("both", None),
            "wout": ("out", -2)}


def _transform_rg(rg, old: int, new: int, tag: str, maps, mode: str):
    out = dict(rg)
    if mode == "narrow_paper":
        for k, (role, ax) in _RG_SPEC.items():
            if role == "in":
                out[k] = nc.narrow_in(out[k], new, axis=ax)
            elif role == "out":
                out[k] = nc.narrow_out_paper(out[k], new, axis=ax)
            else:  # both: rows redistribute, cols drop
                out[k] = nc.narrow_in(nc.narrow_out_paper(out[k], new, axis=-2),
                                      new, axis=-1)
        return out
    mapping = maps[tag + "/rnn"]
    if mode == "widen":
        base = old
        fn_in = lambda w, ax: nc.widen_in(w, mapping, axis=ax)
        fn_out = lambda w, ax: nc.widen_out(w, mapping, base, axis=ax)
    else:
        base = new
        fn_in = lambda w, ax: nc.narrow_fold_in(w, mapping, base, axis=ax)
        fn_out = lambda w, ax: nc.narrow_fold_out(w, mapping, base, axis=ax)
    for k, (role, ax) in _RG_SPEC.items():
        if role == "in":
            out[k] = fn_in(out[k], ax)
        elif role == "out":
            out[k] = fn_out(out[k], ax)
        else:
            out[k] = fn_in(fn_out(out[k], -2), -1)
    return out


def _width_pairs(from_cfg: ModelConfig, to_cfg: ModelConfig):
    """(from, to) of every width ``_transform_block`` moves, by kind."""
    mf, mt = from_cfg.moe, to_cfg.moe
    moe = mf is not None and mt is not None
    return {"ffn": (from_cfg.d_ff, to_cfg.d_ff),
            "effn": (mf.d_ff_expert, mt.d_ff_expert) if moe else (0, 0),
            "sffn": ((mf.n_shared * mf.d_ff_shared,
                      mt.n_shared * mt.d_ff_shared) if moe else (0, 0)),
            "exp": (mf.n_experts, mt.n_experts) if moe else (0, 0),
            "rnn": ((from_cfg.d_rnn, to_cfg.d_rnn)
                    if from_cfg.ssm and to_cfg.ssm else (0, 0))}


def _transform_block(block, from_cfg: ModelConfig, to_cfg: ModelConfig,
                     tag: str, maps, mode: str):
    out = dict(block)
    if "mlp" in out and from_cfg.d_ff != to_cfg.d_ff:
        out["mlp"] = _transform_mlp(out["mlp"], from_cfg.d_ff, to_cfg.d_ff,
                                    tag + "/ffn", maps, mode)
    if "moe" in out:
        mf, mt = from_cfg.moe, to_cfg.moe
        moe = dict(out["moe"])
        if mf.d_ff_expert != mt.d_ff_expert:
            sub = {k: moe[k] for k in ("wg", "wu", "wd")}
            sub = _transform_mlp(sub, mf.d_ff_expert, mt.d_ff_expert,
                                 tag + "/effn", maps, mode)
            moe.update(sub)
        if "shared" in moe and mf.d_ff_shared != mt.d_ff_shared:
            moe["shared"] = _transform_mlp(
                moe["shared"], mf.n_shared * mf.d_ff_shared,
                mt.n_shared * mt.d_ff_shared, tag + "/sffn", maps, mode)
        if mf.n_experts != mt.n_experts:
            moe = _transform_experts(moe, mf.n_experts, mt.n_experts,
                                     tag, maps, mode)
        out["moe"] = moe
    if "rg" in out and from_cfg.d_rnn != to_cfg.d_rnn:
        out["rg"] = _transform_rg(out["rg"], from_cfg.d_rnn, to_cfg.d_rnn,
                                  tag, maps, mode)
    return out


@functools.lru_cache(maxsize=32)
def _param_shapes(cfg: ModelConfig):
    # configs are frozen/hashable and per-round seed-keyed callers would
    # otherwise re-trace the full model every round
    return jax.eval_shape(lambda k: T.init_params(k, cfg),
                          jax.random.PRNGKey(0))


def width_mappings(from_cfg: ModelConfig, to_cfg: ModelConfig, *,
                   seed: int = 0):
    """Every To-Wider mapping of ``up(·, from_cfg, to_cfg, seed=seed)``,
    keyed by the tag ``_transform_block`` gives it (``u/<block>/ffn``,
    ``.../effn``, ``.../sffn``, ``.../exp``, ``.../rnn``, ``e/ffn``): the
    only seed-dependent input of ``up``, ``segment_spec`` and fold-mode
    ``down``."""
    pairs = _width_pairs(from_cfg, to_cfg)
    out = {}
    if all(a == b for a, b in pairs.values()):
        return out
    shapes = _param_shapes(to_cfg)

    def add(tag, kind):
        old, new = pairs[kind]
        if old != new:
            out[tag] = nc.dup_mapping(old, new, tag=tag, seed=seed)

    for top, pre in (("units", "u"), ("rem", "r")):
        for k, block in shapes.get(top, {}).items():
            tag0 = f"{pre}/{k}"
            if "mlp" in block:
                add(tag0 + "/ffn", "ffn")
            if "moe" in block:
                add(tag0 + "/effn", "effn")
                if "shared" in block["moe"]:
                    add(tag0 + "/sffn", "sffn")
                add(tag0 + "/exp", "exp")
            if "rg" in block:
                add(tag0 + "/rnn", "rnn")
    if "encoder" in shapes:
        add("e/ffn", "ffn")
    return out


def segment_spec(from_cfg: ModelConfig, to_cfg: ModelConfig, *,
                 seed: int = 0):
    """Width-segment metadata of ``up(·, from_cfg, to_cfg, seed=seed)``
    (``core.segments``) for every LINEAR width dimension
    ``_transform_block`` moves: FFN d_ff, MoE expert width d_ff_expert,
    shared-expert width, RG-LRU d_rnn. Per widened leaf: in-role
    duplication on the hidden axis (−1), out-role split on the
    down-projection rows (−2), both on the recurrent square matrices —
    with each block's own deterministic mapping (same tags
    ``_transform_block`` uses, so the ids match ``up`` exactly).

    Expert-COUNT duplication is NOT emitted: its router-bias −log(group)
    shift makes the embedding affine per expert group, so cohorts
    differing there carry no segment metadata (multiplicity stays 1 on
    expert-duplicated coordinates; the unified engine's
    ``segment_representable`` excludes them anyway)."""
    spec = {}
    maps = width_mappings(from_cfg, to_cfg, seed=seed)
    if not maps:
        return spec
    shapes = _param_shapes(to_cfg)

    def segs(role, ax, mapping):
        if role == "both":
            return [sg.AxisSeg(-2, mapping, out_role=True),
                    sg.AxisSeg(-1, mapping, out_role=False)]
        return [sg.AxisSeg(ax, mapping, out_role=(role == "out"))]

    def visit(path, leaf):
        keys = sg.path_keys(path)
        if (keys[:2] == ("encoder", "units") and len(keys) == 4
                and keys[2] == "mlp" and keys[3] in _MLP_SPEC):
            # whisper encoder FFN rides cfg.d_ff too — one mapping shared
            # by all (stacked) encoder layers, same tag ``up()`` uses
            if "e/ffn" in maps:
                role, ax = _MLP_SPEC[keys[3]]
                spec[keys] = segs(role, ax, maps["e/ffn"])
            return leaf
        if len(keys) < 3 or keys[0] not in ("units", "rem"):
            return leaf
        tag0 = ("u" if keys[0] == "units" else "r") + f"/{keys[1]}"
        rest = keys[2:]
        hit = None
        if rest[0] == "mlp" and len(rest) == 2 and rest[1] in _MLP_SPEC:
            hit = (tag0 + "/ffn", _MLP_SPEC[rest[1]])
        elif (rest[0] == "moe" and len(rest) == 2
                and rest[1] in ("wg", "wu", "wd")):
            hit = (tag0 + "/effn", _MLP_SPEC[rest[1]])
        elif (len(rest) == 3 and rest[:2] == ("moe", "shared")
                and rest[2] in _MLP_SPEC):
            hit = (tag0 + "/sffn", _MLP_SPEC[rest[2]])
        elif rest[0] == "rg" and len(rest) == 2 and rest[1] in _RG_SPEC:
            hit = (tag0 + "/rnn", _RG_SPEC[rest[1]])
        if hit is not None and hit[0] in maps:
            spec[keys] = segs(*hit[1], maps[hit[0]])
        return leaf

    jax.tree_util.tree_map_with_path(visit, shapes)
    return spec


# ------------------------------------------------------------------ up/down

def _transform_encoder(params, from_cfg: ModelConfig, to_cfg: ModelConfig,
                       maps, mode: str):
    """The whisper encoder's FFN is sized by ``cfg.d_ff`` like the
    decoder blocks, so width transforms must move it too (found by the
    ``repro.analysis`` contract checker: ``up`` used to pass the
    ``encoder`` subtree through untouched, leaving d_ff-heterogeneous
    encoder cohorts shape-broken). Encoder DEPTH lives in
    ``cfg.encoder.n_layers`` and never varies inside a family, so only
    the MLP width moves — one shared mapping (tag ``e/ffn``) across the
    stacked encoder layers, matching ``segment_spec``."""
    if "encoder" not in params or from_cfg.d_ff == to_cfg.d_ff:
        return params
    enc = dict(params["encoder"])
    units = dict(enc["units"])
    units["mlp"] = _transform_mlp(units["mlp"], from_cfg.d_ff, to_cfg.d_ff,
                                  "e/ffn", maps, mode)
    enc["units"] = units
    params["encoder"] = enc
    return params


def _zeros_block_like(cfg: ModelConfig, kind: str):
    shapes = jax.eval_shape(
        lambda: T.block_init(jax.random.PRNGKey(0), cfg, kind,
                             jnp.dtype(cfg.dtype)))
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def up(params, from_cfg: ModelConfig, to_cfg: ModelConfig, *, seed: int = 0,
       mappings=None):
    """Client -> global: To-Wider (exact) + To-Deeper (zero blocks, exact).
    ``mappings`` (``width_mappings``' dict, its arrays possibly traced)
    replaces the draw at ``seed``."""
    assert from_cfg.layer_pattern == to_cfg.layer_pattern
    maps = (width_mappings(from_cfg, to_cfg, seed=seed) if mappings is None
            else mappings)
    params = jax.tree.map(lambda x: x, params)
    # widths first (existing blocks), at client depth
    if "units" in params:
        params["units"] = {
            k: _transform_block(v, from_cfg, to_cfg, f"u/{k}", maps, "widen")
            for k, v in params["units"].items()}
    if "rem" in params:
        params["rem"] = {
            k: _transform_block(v, from_cfg, to_cfg, f"r/{k}", maps, "widen")
            for k, v in params["rem"].items()}
    params = _transform_encoder(params, from_cfg, to_cfg, maps, "widen")
    # depth: pad the stacked axis with zero blocks (identity via residual)
    nu_from, nu_to = from_cfg.n_units, to_cfg.n_units
    if nu_to > nu_from:
        for i, kind in enumerate(to_cfg.layer_pattern):
            zb = _zeros_block_like(to_cfg, kind)
            pad = jax.tree.map(
                lambda z: jnp.broadcast_to(z[None], (nu_to - nu_from,) + z.shape),
                zb)
            params["units"][f"b{i}"] = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], axis=0),
                params["units"][f"b{i}"], pad)
    return params


def down(params, from_cfg: ModelConfig, to_cfg: ModelConfig, *, seed: int = 0,
         mode: str = "paper", mappings=None):
    """Global -> client: To-Shallower (slice) + To-Narrower (Alg.3 | fold).
    Fold mode reads the To-Wider mappings of ``up(·, to_cfg, from_cfg)``:
    ``mappings`` when given, else the draw at ``seed``."""
    assert from_cfg.layer_pattern == to_cfg.layer_pattern
    nmode = "narrow_paper" if mode == "paper" else "narrow_fold"
    maps = mappings
    if nmode == "narrow_fold" and maps is None:
        maps = width_mappings(to_cfg, from_cfg, seed=seed)
    params = jax.tree.map(lambda x: x, params)
    nu_to = to_cfg.n_units
    if nu_to < from_cfg.n_units:
        params["units"] = jax.tree.map(lambda x: x[:nu_to], params["units"])
    if "units" in params:
        params["units"] = {
            k: _transform_block(v, from_cfg, to_cfg, f"u/{k}", maps, nmode)
            for k, v in params["units"].items()}
    if "rem" in params:
        params["rem"] = {
            k: _transform_block(v, from_cfg, to_cfg, f"r/{k}", maps, nmode)
            for k, v in params["rem"].items()}
    return _transform_encoder(params, from_cfg, to_cfg, maps, nmode)
