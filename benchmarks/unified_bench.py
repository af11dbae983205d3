"""Per-client loop vs cohort-parallel unified engine wall clock, per
aggregation mode and cohort kind.

The unified engine (fl/engine.py) replaces the Python loop over K clients
with one stacked vmapped program; this bench measures the per-round wall
clock of both Simulator paths across cohort sizes K, both aggregation
modes (``filler`` — paper Eq. 1 — and ``coverage`` — the HeteroFL-style
renormalized average from core/aggregation.py) and both cohort kinds —
``depth`` (depth-only heterogeneity) and ``width`` (depth AND width mixed
via the paper's -Wider variants; ISSUE 4: segment-projected training +
per-round embed seeds) — where the two engines are numerically
equivalent (tests/test_unified.py, tests/test_federation.py). Compile
time is excluded by a 1-round warmup run on the SAME Simulator (grad fns
and the engine's jitted steps are cached per instance) before the timed
rounds. Numbers feed EXPERIMENTS.md §Perf.

On a single device the two paths are roughly wall-clock neutral on CPU
(the engine trades K dispatches for union-depth padding FLOPs); the win
is sharding the client axis. FEDADP_BENCH_DEVICES=N forces an N-device
host platform (set BEFORE jax initializes — works standalone or with
FEDADP_BENCH_ONLY=unified) and runs the unified path shard_map-ed over
a client mesh.

An ``agg_layout`` microbench (ISSUE 5, extended by ISSUE 8) times the
aggregation pass ALONE — ``fedavg_stacked`` on the union cohort with
coverage masks + fallback — in all three layouts: ``leaf`` (the
per-leaf reference dispatch, one kernel launch per union leaf) vs
``plane`` (the packed ``core.plane`` path, the whole model in ONE
fused kernel pass) vs ``stream`` (the O(P·k_chunk) chunked
``PlaneAccumulator`` path that scales the client axis past what a
resident ``(K, P)`` plane allows). The microbench sweeps the SCALE Ks
(64, 128 by default — training rounds there would be
wall-clock-prohibitive on CI, the aggregation pass is the part that
scales) and every row carries a ``peak_agg_bytes`` column
(``core.aggregation.last_agg_stats``) so the O(K·P) → O(P) memory drop
is diffable, not just the wall clock. Engine rows are tagged with the
layout their round actually ran (``engine.agg_stats()`` — "plane",
"stream" or "edge"; ``tree`` for the loop) plus the same peak-bytes
column.

A ``tffn`` training sweep (ISSUE 10) times TRANSFORMER unified rounds —
the width-heterogeneous reduced-glm4 cohort — across the attention
backend (``blockwise`` XLA vs ``flash``: Pallas kernels on TPU, the
vectorised jnp flash elsewhere) and the local-training compute dtype
(``f32`` vs ``bf16`` mixed precision). Every unified training row now
carries a ``us_train``/``us_agg`` split (``engine.phase_stats()``
wall-clocks the donated training steps; the remainder is round start +
embedding + aggregation) so attention/precision wins — which only touch
the training phase — are attributable, not diluted into the round total.

A ``wire`` microbench (ISSUE 9) times the COMPRESSED aggregation pass —
client-side error-feedback encode (``core.quant``) + the fused
dequantize-accumulate streaming kernel — for every wire format
(f32 / bf16 / int8 / int8+sparse) on the width cohort's coverage
average, and emits ``bytes_per_round`` (the client->server payload) and
``reduction`` columns next to the wall clock: the wire is a
bytes-on-the-network optimization first.

Outputs:
  * CSV rows ``unified/K{K}/{loop|unified}/{agg_mode},us_per_round,...``
    plus per-(K, agg_mode) speedups,
    ``unified/agg/K{K}/{leaf|plane|stream}/{agg_mode},us_per_call,...``
    for the aggregation-layout microbench, and
    ``unified/wire/K{K}/{wire},us_per_call,bytes_per_round=...`` for
    the wire-format microbench,
  * a machine-readable ``BENCH_unified.json`` (path override:
    FEDADP_BENCH_JSON) so the perf trajectory is diffable across PRs.

Env: FEDADP_BENCH_FULL=1 paper-scale protocol; FEDADP_BENCH_SMOKE=1
tiny-K single-round run for CI (seconds, not minutes — still includes
one K=64 streaming row). ``--K 4,8,64`` (comma list, validated before
any work runs) overrides both sweeps' cohort sizes.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys

_DEV = os.environ.get("FEDADP_BENCH_DEVICES")
if _DEV and "jax" not in sys.modules:
    os.environ["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={_DEV} "
                               + os.environ.get("XLA_FLAGS", ""))

from typing import List

from repro.configs.vgg_family import scaled, vgg
from repro.core import VGGFamily
from repro.data import EASY, ClientSampler, image_classification, iid_partition
from repro.fl import FLRunConfig, Simulator
from repro.sharding import cohort_mesh

DEPTH_ARCHS = ("vgg13", "vgg15", "vgg17", "vgg19")  # depth-only cohort
# depth AND width mixed: the -wider variants widen stage 4's first conv,
# a layer every depth variant owns, so the cohort stays
# segment-representable (family.segment_representable)
WIDTH_ARCHS = ("vgg13", "vgg16-wider", "vgg17", "vgg19-wider")
COHORTS = {"depth": DEPTH_ARCHS, "width": WIDTH_ARCHS}
AGG_MODES = ("filler", "coverage")


def _cohort(K: int, n_per_client: int, batch: int, archs=DEPTH_ARCHS):
    family = VGGFamily()
    cfgs = [scaled(vgg(archs[k % len(archs)]), 0.125, 64)
            for k in range(K)]
    n = n_per_client * K
    data = image_classification(EASY, n, seed=0)
    test = image_classification(EASY, 64, seed=99)
    parts = iid_partition(n, K, seed=0)

    def samplers():
        return [ClientSampler(data, p, round_fraction=0.5, batch_size=batch,
                              seed=i) for i, p in enumerate(parts)]

    return family, cfgs, samplers, test


def _per_round(family, cfgs, samplers, test, engine: str, rounds: int,
               base: FLRunConfig = None, agg_modes=AGG_MODES) -> dict:
    """{agg_mode: (seconds-per-round, engine agg stats | None, train
    seconds-per-round | None)}; one Simulator per engine so grad fns /
    engine steps stay warm across the agg_mode sweep. The unified stats
    come from ``engine.agg_stats()`` — the layout the round ACTUALLY ran
    plus its peak aggregation footprint (DESIGN.md §9) — and the train
    split from ``engine.phase_stats()`` (``timing=True`` syncs after the
    local-training steps; ``us_agg`` = round minus train, i.e. round
    start + embedding + aggregation)."""
    if base is None:
        base = FLRunConfig(method="fedadp", rounds=1, local_epochs=1,
                           lr=0.05, momentum=0.9, eval_every=10 ** 9,
                           engine=engine)
    mesh = cohort_mesh(len(cfgs)) if engine == "unified" else None
    sim = Simulator(family, cfgs, samplers(), base, test, mesh=mesh)
    out = {}
    for agg_mode in agg_modes:
        sim.cfg = dataclasses.replace(base, agg_mode=agg_mode)
        sim.samplers = samplers()
        sim.run()                               # warmup: pays compilation
        be = None
        if engine == "unified":
            be = next(b for k, b in sim._backends.items()
                      if k[0] == "unified")
            be.engine.timing = True
            be.engine.phase_stats(reset=True)
        sim.cfg = dataclasses.replace(sim.cfg, rounds=rounds)
        sim.samplers = samplers()
        sec = sim.run()["wall_s"] / rounds
        stats = train_s = None
        if be is not None:
            stats = be.engine.agg_stats()
            train_s = be.engine.phase_stats(reset=True)["train"] / rounds
        out[agg_mode] = (sec, stats, train_s)
    return out


# transformer training rounds: the flash-attention backend and the bf16
# compute policy (ISSUE 10) on the tffn cohort — reduced glm4-9b with
# full-width and half-FFN variants, the width-heterogeneous transformer
# analogue of the VGG -wider sweep
TFFN_ATTN = ("blockwise", "flash")
TFFN_DTYPES = ("f32", "bf16")


def _tffn_cohort(K: int, S: int = 64, batch: int = 8,
                 n_per_client: int = 16):
    import numpy as np

    from repro.configs import get_config, reduced
    from repro.core import TransformerFamily, tfamily

    base = reduced(get_config("glm4-9b"), n_units=2, d_model=64)
    cfgs = [tfamily.make_variant(base, ffn_scale=0.5) if k % 2
            else tfamily.make_variant(base) for k in range(K)]
    family = TransformerFamily()
    n = n_per_client * K
    rng = np.random.default_rng(0)
    toks = rng.integers(0, base.vocab_size, size=(n, S + 1)).astype(np.int32)
    data = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    test = {"tokens": toks[:16, :-1], "labels": toks[:16, 1:]}
    parts = iid_partition(n, K, seed=0)

    def samplers():
        return [ClientSampler(data, p, round_fraction=0.5, batch_size=batch,
                              seed=i) for i, p in enumerate(parts)]

    return family, cfgs, samplers, test


def _tffn_bench(csv: List[str], records: List[dict], Ks, rounds: int):
    """Unified training rounds on the tffn cohort, attention backend x
    compute dtype. ``us_train``/``us_agg`` split every row: the flash
    path only touches the local-training step, so the split shows WHERE
    the win lands. Off-TPU the "flash" backend runs the vectorised jnp
    flash (online-softmax, O(block) memory), on TPU the Pallas kernels
    — either way the same one-entry dispatch the model layer uses."""
    for K in Ks:
        family, cfgs, samplers, test = _tffn_cohort(K)
        train_us = {}
        for attn in TFFN_ATTN:
            for dtype in TFFN_DTYPES:
                base = FLRunConfig(method="fedadp", rounds=1,
                                   local_epochs=1, lr=0.05, momentum=0.9,
                                   eval_every=10 ** 9, engine="unified",
                                   attn_backend=attn, compute_dtype=dtype)
                sec, stats, train_s = _per_round(
                    family, cfgs, samplers, test, "unified", rounds,
                    base=base, agg_modes=("filler",))["filler"]
                train_us[(attn, dtype)] = train_s * 1e6
                csv.append(f"unified/tffn/K{K}/{attn}/{dtype},"
                           f"{sec * 1e6:.0f},us_train={train_s * 1e6:.0f} "
                           f"rounds={rounds}")
                records.append({"cohort": "tffn", "K": K,
                                "engine": "unified", "agg_mode": "filler",
                                "attn": attn, "compute_dtype": dtype,
                                "agg_layout": (stats or {}).get("layout"),
                                "us_per_round": round(sec * 1e6),
                                "us_train": round(train_s * 1e6),
                                "us_agg": round((sec - train_s) * 1e6),
                                "rounds": rounds})
        for dtype in TFFN_DTYPES:
            csv.append(
                f"unified/tffn/K{K}/flash_speedup/{dtype},"
                f"{train_us[('blockwise', dtype)] / max(train_us[('flash', dtype)], 1e-9):.2f},x")


AGG_LAYOUTS = ("leaf", "plane", "stream")
STREAM_K_CHUNK = 16                      # aggregation.default_k_chunk


def _agg_microbench(csv: List[str], records: List[dict], Ks, reps: int):
    """Aggregation-dominated rounds, all three layouts, each timed the
    way a ROUND actually executes it: ``leaf`` aggregates the resident
    stacked trees per leaf (the loop/tree path), ``plane`` runs one
    fused ``plane_agg`` pass on the RESIDENT packed plane, ``stream``
    consumes the resident plane in ``(k_chunk, P)`` row chunks through
    a ``PlaneAccumulator``. The unified engine trains in packed space
    and keeps the plane resident across rounds (packing is a one-time
    embed cost, not a per-round one — fl/engine.py), so pre-packing
    outside the timed loop is the per-round truth; the tree-interface
    adapter (``fedavg_stacked`` layout="plane"/"stream" on a stacked
    TREE) pays one pack per call on top. All on the union cohort's
    coverage average (masks + fallback — the heaviest variant the
    fused layouts fuse). This sweep carries the SCALE Ks (training
    rounds at K=128 are CI-prohibitive; the aggregation pass is the
    part the streaming layout scales) and the ``peak_agg_bytes``
    column."""
    import time

    import jax

    from repro.core import plane as planemod
    from repro.core.aggregation import (fedavg_stacked, global_shapes,
                                        stack_trees, subset_weights)
    from repro.fl.engine import UnifiedEngine
    from repro.kernels.fedavg import ops as kops
    from repro.kernels.fedavg.fedavg import on_tpu

    use_kernel = on_tpu()
    for K in Ks:
        # large-K cells keep the wall clock sane by cutting reps, not
        # coverage — every (K, agg_mode, layout) cell still runs
        reps_k = reps if K <= 16 else max(3, reps // 6)
        cfgs = [scaled(vgg(DEPTH_ARCHS[k % len(DEPTH_ARCHS)]), 0.125, 64)
                for k in range(K)]
        eng = UnifiedEngine(VGGFamily(), cfgs, [1] * K, method="fedadp",
                            agg_mode="coverage")
        shapes = global_shapes(eng.family, eng.global_cfg)
        n_leaves = len(jax.tree.leaves(shapes))
        key = jax.random.PRNGKey(0)

        def rand(i):
            leaves, td = jax.tree.flatten(shapes)
            return jax.tree.unflatten(td, [
                jax.random.normal(jax.random.fold_in(key, 97 * i + j),
                                  s.shape).astype(s.dtype)
                for j, s in enumerate(leaves)])

        stacked = stack_trees([rand(i) for i in range(K)])
        fallback = rand(K)
        w = subset_weights([1] * K)
        wj = jax.numpy.asarray(w, jax.numpy.float32)
        spec, _ = planemod.PlaneSpec.from_stacked(stacked)
        P = spec.size
        x_p = planemod.pack_stacked(stacked, spec, what="bench/x")
        m_p = planemod.pack_stacked(eng.cov_masks, spec, what="bench/m")
        fb_p = planemod.pack(fallback, spec, what="bench/fb")
        jax.block_until_ready((x_p, m_p, fb_p))
        kc = min(STREAM_K_CHUNK, K)

        def run_leaf(agg_mode):
            kw = ({} if agg_mode == "filler"
                  else dict(masks=eng.cov_masks, fallback=fallback))
            return fedavg_stacked(stacked, w, layout="leaf", **kw)

        def run_plane(agg_mode):
            kw = ({} if agg_mode == "filler"
                  else dict(masks=m_p, fallback=fb_p))
            return kops.plane_agg(x_p, wj, use_kernel=use_kernel, **kw)

        stream_stats = {}

        def run_stream(agg_mode):
            acc = kops.PlaneAccumulator(P, use_kernel=use_kernel,
                                        k_hint=kc)
            cov = agg_mode == "coverage"
            for lo in range(0, K, kc):
                hi = min(lo + kc, K)
                acc.update(x_p[lo:hi], wj[lo:hi],
                           masks=m_p[lo:hi] if cov else None)
            out = acc.finish(renorm=cov, fallback=fb_p if cov else None)
            stream_stats.update(acc.stats())
            return out

        for agg_mode in AGG_MODES:
            per = {}
            for layout in AGG_LAYOUTS:
                run = {"leaf": run_leaf, "plane": run_plane,
                       "stream": run_stream}[layout]
                out = run(agg_mode)
                jax.block_until_ready(out)          # pay compilation
                t0 = time.perf_counter()
                for _ in range(reps_k):
                    out = run(agg_mode)
                jax.block_until_ready(out)
                sec = (time.perf_counter() - t0) / reps_k
                per[layout] = sec
                dispatches = n_leaves if layout == "leaf" else 1
                peak = (stream_stats["peak_bytes"]
                        if layout == "stream" else 4 * K * P)
                csv.append(f"unified/agg/K{K}/{layout}/{agg_mode},"
                           f"{sec * 1e6:.0f},reps={reps_k}")
                records.append({"cohort": "agg", "K": K, "engine": "agg",
                                "agg_mode": agg_mode, "agg_layout": layout,
                                "us_per_call": round(sec * 1e6),
                                "dispatches": dispatches, "reps": reps_k,
                                "k_chunk": kc if layout == "stream"
                                else None,
                                "peak_agg_bytes": peak})
            csv.append(
                f"unified/agg/K{K}/speedup/{agg_mode},"
                f"{per['leaf'] / max(per['plane'], 1e-9):.2f},x")
            csv.append(
                f"unified/agg/K{K}/stream_speedup/{agg_mode},"
                f"{per['leaf'] / max(per['stream'], 1e-9):.2f},x")


WIRES = ("f32", "bf16", "int8", "int8+sparse")
WIRE_TILE = 256


def _wire_microbench(csv: List[str], records: List[dict], Ks, reps: int):
    """The quantized wire (ISSUE 9, DESIGN.md §10), timed the way the
    compressed round actually runs it: per ``(k_chunk, P)`` chunk, the
    client-side error-feedback encode (``engine._wire_encode`` — the
    same jit the round uses) then the server-side fold — ``update_q``
    (fused dequantize-accumulate, int8) or ``update`` (bf16/f32) — and
    one ``finish``. On the WIDTH cohort under the coverage average, so
    the sparse wire has real uncovered coordinates to drop. Every row
    carries ``bytes_per_round`` (client->server payload:
    ``core.quant.payload_nbytes``) next to ``us_per_call`` and
    ``peak_agg_bytes`` — the wire is a bytes-on-the-network
    optimization first, a wall-clock one second."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.core import plane as planemod
    from repro.core import quant
    from repro.core.aggregation import subset_weights
    from repro.fl.engine import UnifiedEngine, _wire_encode
    from repro.kernels.fedavg import ops as kops
    from repro.kernels.fedavg.fedavg import on_tpu

    use_kernel = on_tpu()
    for K in Ks:
        reps_k = reps if K <= 16 else max(3, reps // 6)
        cfgs = [scaled(vgg(WIDTH_ARCHS[k % len(WIDTH_ARCHS)]), 0.125, 64)
                for k in range(K)]
        eng = UnifiedEngine(VGGFamily(), cfgs, [1] * K, method="fedadp",
                            agg_mode="coverage")
        spec = eng.plane_spec
        P = spec.size
        key = jax.random.PRNGKey(0)
        x_p = jax.random.normal(jax.random.fold_in(key, K), (K, P),
                                jnp.float32)
        m_p = planemod.pack_stacked(eng.cov_masks, spec, what="bench/m")
        fb_p = jnp.zeros((P,), jnp.float32)
        wj = jnp.asarray(subset_weights([1] * K), jnp.float32)
        res = jnp.zeros((K, P), jnp.float32)
        covered = [int(c) for c in jax.device_get(m_p.sum(axis=1))]
        jax.block_until_ready((x_p, m_p, res))
        kc = min(STREAM_K_CHUNK, K)

        def run(wire):
            fmt = "int8" if wire.startswith("int8") else wire
            sparse = wire.endswith("sparse")
            acc = kops.PlaneAccumulator(
                P, use_kernel=use_kernel, k_hint=kc,
                q_tile=WIRE_TILE if fmt == "int8" else None)
            for lo in range(0, K, kc):
                hi = min(lo + kc, K)
                m = m_p[lo:hi]
                if fmt == "f32":
                    acc.update(x_p[lo:hi], wj[lo:hi], masks=m)
                    continue
                vals, scales, _ = _wire_encode(
                    x_p[lo:hi], res[lo:hi], m if sparse else None,
                    fmt=fmt, tile=WIRE_TILE)
                if fmt == "int8":
                    acc.update_q(vals, scales, wj[lo:hi], masks=m)
                else:
                    acc.update(vals, wj[lo:hi], masks=m)
            out = acc.finish(renorm=True, fallback=fb_p)
            return out, acc.stats()

        f32_bytes = 4 * K * P
        base_row = None
        for wire in WIRES:
            fmt = "int8" if wire.startswith("int8") else wire
            sparse = wire.endswith("sparse")
            out, stats = run(wire)
            jax.block_until_ready(out)              # pay compilation
            t0 = time.perf_counter()
            for _ in range(reps_k):
                out, stats = run(wire)
            jax.block_until_ready(out)
            sec = (time.perf_counter() - t0) / reps_k
            bytes_round = sum(
                quant.payload_nbytes(fmt, P, tile=WIRE_TILE,
                                     covered=covered[k] if sparse else None)
                for k in range(K))
            red = f32_bytes / bytes_round
            base_row = base_row if base_row is not None else sec
            csv.append(f"unified/wire/K{K}/{wire},{sec * 1e6:.0f},"
                       f"bytes_per_round={bytes_round} "
                       f"reduction={red:.2f}x")
            records.append({"cohort": "wire", "K": K, "engine": "agg",
                            "agg_mode": "coverage", "wire": wire,
                            "sparse": sparse,
                            "tile": WIRE_TILE if fmt == "int8" else None,
                            "us_per_call": round(sec * 1e6),
                            "bytes_per_round": bytes_round,
                            "f32_bytes": f32_bytes,
                            "reduction": round(red, 3), "reps": reps_k,
                            "k_chunk": kc,
                            "peak_agg_bytes": stats["peak_bytes"]})


def parse_ks(text: str):
    """Eagerly validate a ``--K`` comma list — bad input dies at
    argparse time, before any cohort builds or compiles."""
    import argparse
    parts = [p.strip() for p in text.split(",") if p.strip()]
    if not parts:
        raise argparse.ArgumentTypeError(
            f"--K {text!r}: expected a comma list of cohort sizes, "
            "e.g. --K 4,8,64")
    out = []
    for p in parts:
        try:
            k = int(p)
        except ValueError:
            raise argparse.ArgumentTypeError(
                f"--K {text!r}: {p!r} is not an int")
        if k < 1:
            raise argparse.ArgumentTypeError(
                f"--K {text!r}: cohort size {k} must be >= 1")
        out.append(k)
    return tuple(out)


def main(csv: List[str], Ks=None):
    import jax
    if _DEV and len(jax.devices()) != int(_DEV):
        # jax was initialized before this module could set XLA_FLAGS
        # (e.g. an earlier benchmarks/run.py section imported it) —
        # flag it so single-device rows aren't mistaken for sharded ones.
        csv.append(f"unified/devices,0,WARN=requested {_DEV} devices but "
                   f"jax has {len(jax.devices())}; run standalone or with "
                   "FEDADP_BENCH_ONLY=unified")
    smoke = os.environ.get("FEDADP_BENCH_SMOKE")
    full = os.environ.get("FEDADP_BENCH_FULL")
    if smoke:
        train_Ks, (n_per_client, batch, rounds) = (2,), (32, 16, 1)
        agg_Ks, agg_reps = (2, 64), 5     # K=64: one CI streaming row
        tffn_Ks, tffn_rounds = (8,), 2    # the CI flash-vs-blockwise cell
                                          # (2 timed rounds halve noise on
                                          # the us_train <= assertion)
    elif full:
        train_Ks, (n_per_client, batch, rounds) = (4, 8, 16), (256, 64, 5)
        agg_Ks, agg_reps = (4, 8, 16, 64, 128), 50
        tffn_Ks, tffn_rounds = (4, 8, 16), 5
    else:
        train_Ks, (n_per_client, batch, rounds) = (4, 8, 16), (64, 32, 3)
        agg_Ks, agg_reps = (4, 8, 16, 64, 128), 30
        tffn_Ks, tffn_rounds = (4, 8), 3
    if Ks:                               # --K overrides ALL sweeps
        train_Ks = agg_Ks = tffn_Ks = tuple(Ks)
    records = []
    for cohort, archs in COHORTS.items():
        prefix = "unified" if cohort == "depth" else f"unified/{cohort}"
        for K in train_Ks:
            family, cfgs, samplers, test = _cohort(K, n_per_client, batch,
                                                   archs)
            per = {}
            for engine in ("loop", "unified"):
                per[engine] = _per_round(family, cfgs, samplers, test,
                                         engine, rounds)
                for agg_mode, (sec, stats, train_s) in per[engine].items():
                    stats = stats or {}
                    split = ("" if train_s is None
                             else f"us_train={train_s * 1e6:.0f} ")
                    csv.append(f"{prefix}/K{K}/{engine}/{agg_mode},"
                               f"{sec * 1e6:.0f},{split}rounds={rounds}")
                    row = {"cohort": cohort, "K": K,
                           "engine": engine, "agg_mode": agg_mode,
                           "agg_layout": stats.get("layout", "tree"),
                           "us_per_round": round(sec * 1e6),
                           "rounds": rounds,
                           "k_chunk": stats.get("k_chunk"),
                           "peak_agg_bytes": stats.get("peak_bytes")}
                    if train_s is not None:
                        row["us_train"] = round(train_s * 1e6)
                        row["us_agg"] = round((sec - train_s) * 1e6)
                    records.append(row)
            for agg_mode in AGG_MODES:
                csv.append(
                    f"{prefix}/K{K}/speedup/{agg_mode},"
                    f"{per['loop'][agg_mode][0] / max(per['unified'][agg_mode][0], 1e-9):.2f},x")
    _tffn_bench(csv, records, tffn_Ks, tffn_rounds)
    _agg_microbench(csv, records, agg_Ks, agg_reps)
    _wire_microbench(csv, records, agg_Ks, agg_reps)
    path = os.environ.get("FEDADP_BENCH_JSON", "BENCH_unified.json")
    with open(path, "w") as f:
        json.dump({"bench": "unified_bench",
                   "protocol": {"rounds": rounds,
                                "n_per_client": n_per_client,
                                "batch": batch, "local_epochs": 1,
                                "smoke": bool(smoke), "full": bool(full),
                                "devices": len(jax.devices()),
                                "backend": jax.default_backend()},
                   "rows": records}, f, indent=1)
    csv.append(f"unified/json,0,{path}")
    return csv


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--K", type=parse_ks, default=None, metavar="K1,K2,...",
                    help="comma list of cohort sizes (overrides the "
                         "smoke/full/default sweeps; validated before "
                         "any work runs)")
    args = ap.parse_args()
    from repro import compile_cache
    compile_cache.enable()
    rows = main(["name,us_per_call,derived"], Ks=args.K)
    print("\n".join(rows))
