#!/usr/bin/env python3
"""Chip smoke test: the paper's full-width 20-client VGG cohort on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the client-sharded path, four chips

One process drives the main path through its public entry points
(``Simulator`` -> ``UnifiedBackend`` -> ``UnifiedEngine``). A phase that
fails raises and the script exits non-zero; nothing is caught. Off a TPU
it exits before any work: it never falls back to the CPU. The last line
of stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``.

One chip:
  kernels  every main-path Pallas kernel once at the cohort's width
           (P = 40,717,642, VGG-19-Wider — the union of the paper's
           cohort) against its jnp reference run under
           ``jax.default_matmul_precision("highest")``; the compiled HLO
           must hold the kernel as a ``tpu_custom_call``;
  cohort   FedADP on the paper's cohort — 20 clients over 8 VGG variants
           at published widths (6x VGG-19, 2x each other), 32x32
           synthetic images: one round with the Pallas aggregation and
           one with the jnp reference (they must agree), then two rounds
           with per-round accuracy, loss, aggregation layout and peak
           device memory.
Four chips (``--chips 4``, this phase only): the same cohort with the
client axis sharded over the chips (``sharding.cohort_mesh``: 5 clients
per chip) under the streaming and the whole-plane (edge reduce)
layouts, each against the unsharded round.

Numbers printed here come from one smoke run; they are not a benchmark.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

P_UNION = 40_717_642      # VGG-19-Wider at 32x32, classifier (4096, 4096)
N_CLIENTS = 20
# Streaming chunk rows. The v5e compile of the cohort's training step
# needs 13.2 GB of its 15.75 GB of HBM at the default 16 rows, before
# the engine's mask store and accumulators; 8 rows need 6.6 GB.
K_CHUNK = 8
N_TRAIN, N_TEST = 4000, 800
# Kernel vs reference, relative to max(1, max |reference|): an f32 sum
# of at most 20 terms errs by at most 20 * 2^-24 ~ 1.2e-6, while a
# bf16-rounded operand (the TPU's default matmul precision) errs by
# ~2^-9 ~ 2e-3 — 1e-5 passes the first and catches the second.
AGG_TOL = 1e-5
# Flash attention's in-kernel dots may run bf16 MXU passes on f32
# inputs (~2^-9 relative per operand, a few such roundings per output);
# a wrong tile, mask or layout errs by O(1).
FLASH_TOL = 2e-2
# Sharded vs unsharded cohort: the training programs differ in their
# per-device batch of clients, so float reassociation enters the
# trained weights too; the CPU 4-device tests hold the same 1e-4.
MESH_TOL = 1e-4
FLASH_DIMS = dict(B=2, S=512, KV=2, G=16, hd=128)   # glm4-9b's heads


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    if info["platform"] != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{info['platform']!r}); it runs on the chip only")
    if info["count"] < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, "
                 f"JAX found {info['count']}")
    return info


def peak_gb(dev) -> float:
    return (dev.memory_stats() or {}).get("peak_bytes_in_use", 0) / 1e9


def leaves(tree):
    import jax
    return jax.tree_util.tree_leaves(tree)


def max_abs_diff(a, b) -> float:
    import jax.numpy as jnp
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(leaves(a), leaves(b)))


def max_abs(tree) -> float:
    import jax.numpy as jnp
    return max(float(jnp.max(jnp.abs(x))) for x in leaves(tree))


def all_finite(tree) -> bool:
    import jax.numpy as jnp
    return all(bool(jnp.all(jnp.isfinite(x))) for x in leaves(tree))


# ------------------------------------------------------------- kernels
def check_kernel(name, kern, ref, args, tol):
    """Compile ``kern``, require the Pallas kernel in its HLO, run that
    executable and compare it with ``ref`` at full f32 precision."""
    import jax
    compiled = jax.jit(kern).lower(*args).compile()
    n_calls = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    t0 = time.perf_counter()
    got = jax.block_until_ready(compiled(*args))
    t_kern = time.perf_counter() - t0
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref)(*args)
    err = max_abs_diff(got, want) / max(1.0, max_abs(want))
    finite = all_finite(got)
    log(f"kernel {name}: tpu_custom_call x{n_calls} rel_err={err:.3e} "
        f"(tol {tol:g}) finite={finite} first_call_s={t_kern:.4f}")
    assert n_calls > 0, f"{name}: no tpu_custom_call in the compiled HLO"
    assert finite, f"{name}: non-finite output"
    assert err <= tol, f"{name}: rel_err {err:.3e} > {tol:g}"


def kernel_phase():
    import jax
    import jax.numpy as jnp
    from repro.kernels.fedavg import ops
    from repro.kernels.fedavg import ref as kref
    from repro.kernels.flash_attention import flash_attention

    n, tile = P_UNION, 256
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 64))

    def normal(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32)

    def weights(k):
        return jax.nn.softmax(normal(k))

    def mask(k):
        return (jax.random.uniform(next(keys), (k, n)) < 0.7
                ).astype(jnp.float32)

    def mult(k):
        return jax.random.randint(next(keys), (k, n), 1, 4
                                  ).astype(jnp.float32)

    def int8(k):
        return jax.random.randint(next(keys), (k, n), -127, 128, jnp.int8)

    def scales(k):
        return jax.random.uniform(next(keys), (k, -(-n // tile)),
                                  minval=1e-3, maxval=2e-2)

    cases = [
        # the whole-plane pass of the paper cohort (filler mode)
        ("plane_agg K=20",
         lambda x, w: ops.plane_agg(x, w, use_kernel=True),
         lambda x, w: kref.plane_agg_ref(x, w),
         lambda: (normal(20, n), weights(20))),
        # coverage mode; K=8 because K=20 masks + mult + plane and the
        # reference's temporaries overrun 16 GB
        ("plane_agg K=8 masks+mult+fallback",
         lambda x, w, m, mu, fb: ops.plane_agg(
             x, w, masks=m, mult=mu, fallback=fb, use_kernel=True),
         lambda x, w, m, mu, fb: kref.plane_agg_ref(
             x, w, masks=m, mult=mu, fallback=fb),
         lambda: (normal(8, n), weights(8), mask(8), mult(8), normal(n))),
        # the streaming chunk of the cohort round (k_chunk rows)
        (f"plane_accum k={K_CHUNK}",
         lambda a, b, c, x, w: ops.plane_accum(a, b, c, x, w,
                                               use_kernel=True),
         lambda a, b, c, x, w: kref.plane_accum_ref(a, b, c, x, w),
         lambda: (normal(n), normal(n), normal(n), normal(K_CHUNK, n),
                  weights(K_CHUNK))),
        (f"plane_accum k={K_CHUNK} masks+mult",
         lambda a, b, c, x, w, m, mu: ops.plane_accum(
             a, b, c, x, w, masks=m, mult=mu, use_kernel=True),
         lambda a, b, c, x, w, m, mu: kref.plane_accum_ref(
             a, b, c, x, w, m, mu),
         lambda: (normal(n), normal(n), normal(n), normal(K_CHUNK, n),
                  weights(K_CHUNK), mask(K_CHUNK), mult(K_CHUNK))),
        # the int8 wire's fused dequantize-accumulate
        (f"plane_accum_q k={K_CHUNK}",
         lambda a, b, c, q, s, w: ops.plane_accum_q(
             a, b, c, q, s, w, tile=tile, use_kernel=True),
         lambda a, b, c, q, s, w: kref.plane_accum_q_ref(
             a, b, c, q, s, w, tile=tile),
         lambda: (normal(n), normal(n), normal(n), int8(K_CHUNK),
                  scales(K_CHUNK), weights(K_CHUNK))),
        (f"plane_accum_q k={K_CHUNK} masks+mult",
         lambda a, b, c, q, s, w, m, mu: ops.plane_accum_q(
             a, b, c, q, s, w, masks=m, mult=mu, tile=tile,
             use_kernel=True),
         lambda a, b, c, q, s, w, m, mu: kref.plane_accum_q_ref(
             a, b, c, q, s, w, m, mu, tile=tile),
         lambda: (normal(n), normal(n), normal(n), int8(K_CHUNK),
                  scales(K_CHUNK), weights(K_CHUNK), mask(K_CHUNK),
                  mult(K_CHUNK))),
        ("plane_finish renorm+fallback",
         lambda a, b, c, fb: ops.plane_finish(a, b, c, fallback=fb,
                                              use_kernel=True),
         lambda a, b, c, fb: kref.plane_finish_ref(a, b, c, fb),
         lambda: (normal(n),
                  (jax.random.uniform(next(keys), (n,), minval=0.5,
                                      maxval=1.5)
                   * (jax.random.uniform(next(keys), (n,)) > 0.1)),
                  jax.random.randint(next(keys), (n,), 0, 3
                                     ).astype(jnp.float32),
                  normal(n))),
    ]
    for name, kern, ref, make in cases:
        args = make()
        check_kernel(name, kern, ref, args, AGG_TOL)
        del args
        gc.collect()

    d = FLASH_DIMS
    pos = jnp.arange(d["S"])

    def flash(use_kernel):
        def f(q, k, v, cot):
            out, vjp = jax.vjp(
                lambda q, k, v: flash_attention(q, k, v, pos, pos,
                                                causal=True,
                                                use_kernel=use_kernel),
                q, k, v)
            return out, vjp(cot)
        return f

    args = (normal(d["B"], d["S"], d["KV"], d["G"], d["hd"]),
            normal(d["B"], d["S"], d["KV"], d["hd"]),
            normal(d["B"], d["S"], d["KV"], d["hd"]),
            normal(d["B"], d["S"], d["KV"] * d["G"], d["hd"]))
    check_kernel("flash fwd+bwd " + " ".join(f"{k}={v}"
                                             for k, v in d.items()),
                 flash(True), flash(False), args, FLASH_TOL)


# -------------------------------------------------------------- cohort
def paper_cohort():
    from repro.configs.vgg_family import paper_client_archs, vgg
    from repro.data import (EASY, ClientSampler, image_classification,
                            iid_partition)
    cfgs = [vgg(a) for a in paper_client_archs()]
    data = image_classification(EASY, N_TRAIN, seed=0)
    test = image_classification(EASY, N_TEST, seed=999)
    parts = iid_partition(N_TRAIN, len(cfgs), seed=0)

    def samplers():
        return [ClientSampler(data, p, round_fraction=0.2, batch_size=64,
                              seed=i) for i, p in enumerate(parts)]
    return cfgs, samplers, test


def run_cfg(rounds: int, **kw):
    from repro.fl import FLRunConfig
    return FLRunConfig(method="fedadp", rounds=rounds, local_epochs=2,
                       lr=0.01, momentum=0.9, seed=0, **kw)


def global_loss_acc(params, gcfg, test):
    import jax
    from repro.models import vgg as vgg_model
    loss, acc = jax.jit(vgg_model.loss_fn, static_argnums=1)(params, gcfg,
                                                             test)
    return float(loss), float(acc)


def checked_run(sim, **kw):
    """``sim.run`` that insists on the unified engine at full width."""
    res = sim.run(**kw)
    assert sim.backend.name == "unified", (
        f"engine='auto' resolved to {sim.backend.name!r}, not the unified "
        "engine")
    assert sim.backend.engine.plane_spec.size == P_UNION, \
        sim.backend.engine.plane_spec.size
    assert all_finite(res["global_params"]), "non-finite global model"
    return res


def cohort_phase():
    import jax
    from repro.core import VGGFamily
    from repro.fl import Simulator

    cfgs, samplers, test = paper_cohort()
    fam = VGGFamily()
    gcfg = fam.union(cfgs)
    dev = jax.devices()[0]
    log(f"cohort: {len(cfgs)} clients, archs "
        f"{sorted({c.name for c in cfgs})}, union stages {gcfg.stages} "
        f"classifier {gcfg.classifier}, k_chunk={K_CHUNK}")

    # one round with the Pallas aggregation, one with the jnp reference
    sim = Simulator(fam, cfgs, samplers(), run_cfg(1, k_chunk=K_CHUNK), test)
    t0 = time.perf_counter()
    g_kernel = checked_run(sim)["global_params"]
    log(f"round 1 (Pallas aggregation, compiles included): "
        f"{time.perf_counter() - t0:.1f}s; engine=unified "
        f"P={sim.backend.engine.plane_spec.size} "
        f"agg={sim.backend.engine.agg_stats()}")
    gc.collect()
    sim_ref = Simulator(fam, cfgs, samplers(),
                        run_cfg(1, k_chunk=K_CHUNK, use_kernel=False), test)
    t0 = time.perf_counter()
    g_ref = checked_run(sim_ref)["global_params"]
    log(f"round 1 (jnp aggregation): {time.perf_counter() - t0:.1f}s")
    diff, scale = max_abs_diff(g_kernel, g_ref), max_abs(g_ref)
    log(f"Pallas vs jnp aggregation, one round: max|diff|={diff:.3e} "
        f"max|param|={scale:.3e} (tol {AGG_TOL:g} x max(1, max|param|))")
    assert diff <= AGG_TOL * max(1.0, scale), diff
    loss1, acc1 = global_loss_acc(g_kernel, gcfg, test)
    del sim_ref, g_ref
    gc.collect()

    # two rounds on the same engine (its compiled programs are reused)
    sim.cfg.rounds = 2
    sim.samplers = samplers()
    records = []
    t0 = time.perf_counter()
    res = checked_run(sim, callbacks=[records.append])
    total = time.perf_counter() - t0
    loss2, acc2 = global_loss_acc(res["global_params"], gcfg, test)
    prev = 0.0
    for rec, (loss, gacc) in zip(records, ((loss1, acc1), (loss2, acc2))):
        # Federation's wall_s runs from the first round's start to this
        # round's end, so a later round's share also holds the previous
        # round's evaluation
        log(f"round {rec['round']}: wall_s={rec['wall_s']:.2f} "
            f"(+{rec['wall_s'] - prev:.2f}) client_acc={rec['acc']:.4f} "
            f"global_test_loss={loss:.4f} global_test_acc={gacc:.4f}")
        prev = rec["wall_s"]
    log(f"two rounds + evaluations: {total:.1f}s; "
        f"agg={sim.backend.engine.agg_stats()}; "
        f"peak_bytes_in_use={peak_gb(dev):.2f} GB")
    assert all(abs(x) < float("inf") for x in (loss1, loss2)), (loss1, loss2)


# ---------------------------------------------------------- four chips
def sharded_phase(n_chips: int):
    import jax
    from repro.core import VGGFamily
    from repro.fl import Simulator
    from repro.sharding import cohort_mesh

    cfgs, samplers, test = paper_cohort()
    fam = VGGFamily()
    devs = jax.devices()[:n_chips]
    mesh = cohort_mesh(N_CLIENTS)
    assert mesh is not None and mesh.devices.size == n_chips, mesh
    log(f"mesh: {dict(mesh.shape)} -> {N_CLIENTS // n_chips} clients per "
        f"chip")

    def one_round(mesh, layout):
        sim = Simulator(fam, cfgs, samplers(),
                        run_cfg(1, k_chunk=K_CHUNK, agg_layout=layout),
                        test, mesh=mesh)
        t0 = time.perf_counter()
        g = checked_run(sim)["global_params"]
        log(f"  mesh={'none' if mesh is None else dict(mesh.shape)} "
            f"layout={layout}: {time.perf_counter() - t0:.1f}s "
            f"agg={sim.backend.engine.agg_stats()} "
            f"peak_GB={[round(peak_gb(d), 2) for d in devs]}")
        del sim               # free this engine's stores before the next
        gc.collect()
        return g

    ref = one_round(None, "auto")
    idle = [peak_gb(d) for d in devs[1:]]
    for layout in ("auto", "plane"):
        g = one_round(mesh, layout)
        diff, scale = max_abs_diff(g, ref), max_abs(ref)
        log(f"sharded ({layout}) vs unsharded, one round: "
            f"max|diff|={diff:.3e} max|param|={scale:.3e} "
            f"(tol {MESH_TOL:g} x max(1, max|param|))")
        assert diff <= MESH_TOL * max(1.0, scale), diff
        del g
        gc.collect()
    busy = [peak_gb(d) for d in devs[1:]]
    log(f"peak GB on devices 1..{n_chips - 1}: after the unsharded round "
        f"{[round(x, 2) for x in idle]}, after the sharded rounds "
        f"{[round(x, 2) for x in busy]}")
    assert all(b > 1.0 for b in busy), "sharded rounds left a chip idle"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the client-sharded phase")
    args = ap.parse_args()
    from repro import compile_cache
    log(f"compile cache: {compile_cache.enable()}")
    info = device_info(args.chips)
    if args.chips == 4:
        sharded_phase(args.chips)
    else:
        kernel_phase()
        cohort_phase()
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
