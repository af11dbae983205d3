"""Family abstraction: the architecture lattice FedADP operates over.

A *family* knows how to (a) compute the union architecture of a cohort,
(b) move parameters up (client->global) and down (global->client) with
NetChange, and (c) init/evaluate members. ``up``/``down`` take the
seed's To-Wider mappings (``width_mappings``, keyed by tag) as an
optional ``mappings`` argument, which may be traced arrays, so one
compiled program per architecture serves every seed. Two concrete families:

  * VGGFamily          — the paper's own setting (conv chains).
  * TransformerFamily  — beyond-paper: any assigned architecture config,
                         variants over depth / FFN width / experts / d_rnn.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Sequence

import jax

from repro.core import tfamily, vggops
from repro.configs.vgg_family import VGGConfig, union_config


@dataclass(frozen=True)
class VGGFamily:
    def union(self, cfgs: Sequence[VGGConfig]) -> VGGConfig:
        return union_config(list(cfgs))

    def depth_only(self, cfgs: Sequence[VGGConfig]) -> bool:
        """True when the cohort differs ONLY in depth (layer counts): the
        unified-space engine is then exact (DESIGN.md §2). Width layers must
        agree wherever two clients both have them, and every non-stage
        config field (classifier, n_classes, in_channels, ...) must match."""
        for si in range(max(len(c.stages) for c in cfgs)):
            for li in range(max(len(c.stages[si]) for c in cfgs
                                if si < len(c.stages))):
                ws = {c.stages[si][li] for c in cfgs
                      if si < len(c.stages) and li < len(c.stages[si])}
                if len(ws) > 1:
                    return False
        norm = {dataclasses.replace(c, name="", stages=()) for c in cfgs}
        return len(norm) == 1

    def segment_representable(self, cfgs: Sequence[VGGConfig]) -> bool:
        """True when every client's embedding into the cohort union is a
        segment operator (``core.segments``) — the unified engine's
        eligibility domain, superseding the old ``depth_only`` gate.
        Depth and width may both vary; non-structural fields must match,
        stage/classifier arity must match, and trailing union positions
        a client doesn't own must carry the client's stage-final width
        (the regime ``down()``'s within-stage walk is defined on)."""
        cfgs = list(cfgs)
        norm = {dataclasses.replace(c, name="", stages=(), classifier=())
                for c in cfgs}
        if len(norm) != 1:
            return False
        if (len({len(c.stages) for c in cfgs}) != 1
                or len({len(c.classifier) for c in cfgs}) != 1):
            return False
        union = union_config(cfgs)
        for c in cfgs:
            for si, ws in enumerate(c.stages):
                uw = union.stages[si]
                if any(uw[li] != ws[-1] for li in range(len(ws), len(uw))):
                    return False
        return True

    def segment_spec(self, client_cfg: VGGConfig, global_cfg: VGGConfig, *,
                     seed: int = 0):
        return vggops.segment_spec(client_cfg, global_cfg, seed=seed)

    def chain_paths(self, cfg: VGGConfig):
        """Sequential chain as (layer-id, params-tree path) pairs — the
        engine's FlexiFed grouping uses the ids to find the shared prefix
        and the paths to locate each layer in the (stacked) union tree."""
        out = []
        for si, ws in enumerate(cfg.stages):
            for li, w in enumerate(ws):
                out.append((("conv", si, li, w), ("stages", f"s{si}", f"c{li}")))
        for fi, wd in enumerate(cfg.classifier):
            out.append((("fc", fi, wd), ("fc", f"f{fi}")))
        out.append((("out",), ("out",)))
        return out

    def init(self, key, cfg):
        from repro.models import vgg
        return vgg.init_params(key, cfg)

    def width_mappings(self, client_cfg, global_cfg, *, seed: int = 0):
        return vggops.width_mappings(client_cfg, global_cfg, seed=seed)

    def up(self, params, from_cfg, to_cfg, *, seed=0, mappings=None):
        return vggops.up(params, from_cfg, to_cfg, seed=seed,
                        mappings=mappings)

    def down(self, params, from_cfg, to_cfg, *, seed=0, mode="paper",
             mappings=None):
        return vggops.down(params, from_cfg, to_cfg, seed=seed, mode=mode,
                          mappings=mappings)

    def loss_and_grad(self, cfg):
        from repro.models import vgg

        def f(params, batch):
            return jax.value_and_grad(vgg.loss_fn, has_aux=True)(params, cfg, batch)
        return f

    def evaluate(self, params, cfg, batch):
        from repro.models import vgg
        logits = vgg.apply(params, cfg, batch["x"])
        return float((logits.argmax(-1) == batch["y"]).mean())


@dataclass(frozen=True)
class TransformerFamily:
    def union(self, cfgs):
        return tfamily.union(list(cfgs))

    def depth_only(self, cfgs) -> bool:
        """True when variants differ only in n_layers (zero-block padding is
        exact under pre-norm residuals); any other config difference makes
        the unified embedding approximate or invalid (DESIGN.md
        §Arch-applicability). Configs are frozen dataclasses, so normalize
        the depth-and-label fields away and compare whole."""
        norm = {dataclasses.replace(c, name="", n_layers=0) for c in cfgs}
        return len(norm) == 1

    def segment_representable(self, cfgs) -> bool:
        """Depth (n_layers) and FFN width (d_ff) may vary — both embed
        as segment operators (zero blocks / deterministic duplication).
        Expert count is affine (router-bias shift), d_rnn and d_model
        stay out of scope (DESIGN.md §Arch-applicability), so any other
        config difference keeps the loop."""
        norm = {dataclasses.replace(c, name="", n_layers=0, d_ff=0)
                for c in cfgs}
        return len(norm) == 1

    def segment_spec(self, client_cfg, global_cfg, *, seed: int = 0):
        return tfamily.segment_spec(client_cfg, global_cfg, seed=seed)

    def chain_paths(self, cfg):
        raise NotImplementedError(
            "FlexiFed's sequential-prefix grouping is defined for the VGG "
            "chain only (paper Section IV.A.3)")

    def init(self, key, cfg):
        from repro.models import transformer as T
        return T.init_params(key, cfg)

    def width_mappings(self, client_cfg, global_cfg, *, seed: int = 0):
        return tfamily.width_mappings(client_cfg, global_cfg, seed=seed)

    def up(self, params, from_cfg, to_cfg, *, seed=0, mappings=None):
        return tfamily.up(params, from_cfg, to_cfg, seed=seed,
                        mappings=mappings)

    def down(self, params, from_cfg, to_cfg, *, seed=0, mode="paper",
             mappings=None):
        return tfamily.down(params, from_cfg, to_cfg, seed=seed, mode=mode,
                          mappings=mappings)

    def loss_and_grad(self, cfg, *, ctx=None):
        from repro.launch.steps import lm_loss
        from repro.sharding.ctx import CPU_CTX
        ctx = CPU_CTX if ctx is None else ctx

        def f(params, batch):
            (loss, aux), g = jax.value_and_grad(lm_loss, has_aux=True)(
                params, cfg, batch, ctx=ctx)
            return (loss, aux), g
        return f

    def evaluate(self, params, cfg, batch):
        return float(_lm_eval_loss(params, cfg, batch))


@functools.partial(jax.jit, static_argnames=("cfg",))
def _lm_eval_loss(params, cfg, batch):
    """Jitted eval loss: an eager ``lm_loss`` call re-traces the unit
    scan (and pays an XLA compile) on EVERY evaluation; keying one jit
    on the static config makes round >= 2 evals compile-free."""
    from repro.launch.steps import lm_loss
    return lm_loss(params, cfg, batch)[0]
