"""One run of one cell: set-up, the measured window, the output check.

The system under test is driven through its own entry points:
``Simulator(..., FLRunConfig(engine="auto"))`` builds the strategy and
backend, and each round is ``backend.run_round(state, r, selected)`` —
the call ``Federation.run`` makes — so a round covers host batch
stacking, round start, local training and aggregation. The benchmark
supplies the data (``traffic``), the initial weights (the reference's
initializer, one jitted call from the seed) and nothing else.

Set-up runs the first ``CHECKED_ROUNDS`` rounds through that same call;
they compile every program the window uses, and their global models are
what the output check compares with the plain reference once the window
has closed.
"""
from __future__ import annotations

import gc
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from check import compare, verdict
from registry import Cell, load_cell
import roofline
import traffic
import tracing

CHECKED_ROUNDS = 3
TRACED_S = 2.0         # the profiled part of a --trace 1 run (>= 2 rounds)
PROBE = 256            # probe rows scored by the loss comparison, unless
                       # the mix's "probe" says


def log(msg: str) -> None:
    print(msg, flush=True)


def setup_env(root: Path) -> None:
    """Point JAX at the checkout before it is imported: the persistent
    compilation cache at ``<root>/.jax_cache`` and the TPU runtime's logs
    under ``<root>/bench/out/tpu_logs``, whatever the environment says.

    The cache is part of the yardstick: its path is part of the cache
    key, so it sits at one fixed place inside the checkout, and only a
    cell's first run there compiles. The program's own rule
    (``repro.compile_cache.enable``) takes ``JAX_COMPILATION_CACHE_DIR``
    when it is set, so both agree on this directory."""
    cache = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ["TPU_LOG_DIR"] = str(root / "bench" / "out" / "tpu_logs")
    for p in (str(root / "src"), str(root / "bench")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": min(len(devs), chips)}
    log(f"device: platform={info['platform']} kind={info['kind']} "
        f"found={len(devs)} used={info['count']}")
    if require_tpu and info["platform"] != "tpu":
        raise NoDevice(f"JAX found no TPU (platform {info['platform']!r}); "
                       "the benchmark runs on the chip only")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devs)}")
    return info


def peak_bytes(chips: int) -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


# ------------------------------------------------------------- the system
class Program:
    """The system under test, built for one cell and one seed."""

    def __init__(self, cell: Cell, seed: int, *, compute: str = "f32"):
        import jax
        from repro.fl import FLRunConfig, Simulator
        cfg, mix = cell.config, cell.mix
        fam = cell.family()
        self.clients = fam.client_dicts(cfg)
        family, client_cfgs = fam.program_cohort(cfg)
        t = time.perf_counter()
        self.data = traffic.make_data(mix, self.clients[0], seed)
        self.parts = traffic.partition(traffic.n_rows(self.data),
                                       len(self.clients), seed)
        samplers = [traffic.Sampler(self.data, p, mix, seed, k)
                    for k, p in enumerate(self.parts)]
        self.t_data = time.perf_counter() - t
        t = time.perf_counter()
        self.embed_seed = traffic.derived_seed(seed, 3)
        self.sim = Simulator(
            family, client_cfgs, samplers,
            FLRunConfig(method=cfg["method"], filler=cfg["filler"],
                        agg_mode=cfg["agg_mode"],
                        narrow_mode=cfg["narrow_mode"],
                        local_epochs=int(mix["local_epochs"]),
                        lr=cfg["lr"], momentum=cfg["momentum"],
                        k_chunk=cfg["k_chunk"], seed=self.embed_seed,
                        compute_dtype=compute, engine="auto"),
            eval_batch=None)
        self.fed = self.sim._build()
        self.backend = self.fed.backend
        if self.backend.name != "unified":
            raise RuntimeError(f"engine='auto' resolved to "
                               f"{self.backend.name!r}, not the unified "
                               "engine")
        size = self.backend.engine.plane_spec.size
        if size != cfg["plane_size"]:
            raise RuntimeError(f"unified engine plane has {size} columns, "
                               f"the cohort's union has {cfg['plane_size']}")
        self.selected = list(range(len(self.clients)))
        self.finite = jax.jit(_finite)
        self.t_build = time.perf_counter() - t

    def round(self, state, r: int):
        return self.backend.run_round(state, r, self.selected)

    def close(self):
        for name in ("fed", "sim", "backend", "data", "finite"):
            setattr(self, name, None)


def init_weights(cell: Cell, seed: int):
    """The union model's initial weights, on the device, from the seed."""
    import jax
    ref = cell.reference()
    ucfg = ref.union(cell.family().client_dicts(cell.config))
    key = jax.random.PRNGKey(traffic.derived_seed(seed, 4))
    return jax.jit(lambda k: ref.init_params(k, ucfg))(key)


def host_copy(tree):
    import jax
    return jax.tree.map(np.asarray, jax.device_get(tree))


def all_finite(tree) -> bool:
    return all(bool(np.all(np.isfinite(x)))
               for x in _leaf_values(tree))


def _leaf_values(tree):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _leaf_values(tree[k])
    else:
        yield tree


def run_checked_rounds(prog: Program, state, n: int = CHECKED_ROUNDS):
    """Rounds 0..n-1 through the window's own call; returns the live
    state and host copies of the global model after each round."""
    import jax
    snaps = []
    for r in range(n):
        state = prog.round(state, r)
        jax.block_until_ready(state)
        snaps.append(host_copy(state))
    return state, snaps


# ------------------------------------------------------------ reference
def reference_models(cell: Cell, seed: int, data, parts,
                     n: int = CHECKED_ROUNDS, dtype=None, store=None):
    """The plain reference's global models ``[g0, ..., gn]`` (host).
    ``dtype``: the compute type of local training; ``store``: the type
    its parameters and momentum are kept in (float32 unless given)."""
    import jax
    import jax.numpy as jnp
    ref = cell.reference()
    clients = cell.family().client_dicts(cell.config)
    ucfg = ref.union(clients)
    cfg, mix = cell.config, cell.mix
    g = init_weights(cell, seed)
    models = [host_copy(g)]
    n_samples = [len(p) for p in parts]
    for r in range(n):
        batches = [tuple(jnp.asarray(a) for a in traffic.padded_round(
            data, mix, parts[k], seed, k, r)) for k in range(len(clients))]
        g = ref.fedadp_round(
            g, ucfg, clients, n_samples, batches, round_idx=r,
            base_seed=traffic.derived_seed(seed, 3), lr=cfg["lr"],
            momentum=cfg["momentum"],
            dtype=jnp.float32 if dtype is None else dtype,
            store=jnp.float32 if store is None else store)
        jax.block_until_ready(g)
        models.append(host_copy(g))
        del batches
    return models


def probe_losses(cell: Cell, models, data, seed: int) -> List[float]:
    """Each model's loss on the mix's probe rows (``probe``, drawn from
    the seed), by the reference forward; ``valid`` marks rows."""
    import jax
    import jax.numpy as jnp
    ref = cell.reference()
    n = traffic.n_rows(data)
    idx = np.random.default_rng([int(seed) % 2 ** 64, 5]).choice(
        n, size=min(int(cell.mix.get("probe", PROBE)), n), replace=False)
    x, y = (jnp.asarray(data[k][idx]) for k in traffic.keys(cell.mix))
    valid = jnp.ones(y.shape[:1], jnp.float32)
    score = jax.jit(lambda p: ref.loss(p, x, y, valid))
    return [float(score(jax.tree.map(jnp.asarray, m))) for m in models]


def check(cell: Cell, seed: int, data, parts, sys_models,
          ref_models=None) -> Dict[str, dict]:
    """Compare the system's ``[g1..gn]`` with the reference's."""
    if ref_models is None:
        ref_models = reference_models(cell, seed, data, parts)
    sys_all = [ref_models[0]] + list(sys_models)
    nums = compare(sys_all, ref_models,
                   probe_losses(cell, sys_all[1:], data, seed),
                   probe_losses(cell, ref_models[1:], data, seed))
    for k, v in nums.items():
        v["limit"] = cell.limits.get(k)      # None: read, not compared
    return nums


# --------------------------------------------------------------- window
def _finite(tree):
    import jax
    import jax.numpy as jnp
    return jnp.all(jnp.stack([jnp.all(jnp.isfinite(x))
                              for x in jax.tree.leaves(tree)]))


def timed_rounds(prog: Program, state, r0: int, seconds: float,
                 min_rounds: int = 1, span: bool = False):
    """Rounds from ``r0`` until ``seconds`` have passed (at least
    ``min_rounds``), each ending in ``block_until_ready``. Returns
    ``(state, rounds, elapsed_s, failed, finite_flags)``."""
    import jax
    finite = prog.finite
    flags, failed, n = [], 0, 0
    t0 = time.perf_counter()
    while True:
        try:
            if span:
                with jax.profiler.TraceAnnotation(tracing.SPAN):
                    state = prog.round(state, r0 + n)
                    jax.block_until_ready(state)
            else:
                state = prog.round(state, r0 + n)
                jax.block_until_ready(state)
        except Exception as e:                    # a round that raises
            log(f"round {r0 + n} raised {type(e).__name__}: {e}")
            failed += 1
            n += 1
            break
        flags.append(finite(state))
        n += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds and n >= min_rounds:
            break
    elapsed = time.perf_counter() - t0
    return state, n, elapsed, failed, flags


def model_flops_per_round(cell: Cell) -> float:
    """FLOPs of one round's local training in each client's own
    architecture (union padding not counted); a sample is one row of the
    mix's data."""
    fam = cell.family()
    clients = fam.client_dicts(cell.config)
    total = sum(fam.train_flops_per_sample(c, cell.mix) for c in clients)
    n_client = int(cell.mix["n_train"]) // len(clients)
    samples = (traffic.round_take(cell.mix, n_client)
               * int(cell.mix["local_epochs"]))
    return total * samples


def _metric(value: float, unit: str) -> dict:
    return {"value": value, "unit": unit}


# ------------------------------------------------------------------ run
def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = None, t_start: Optional[float] = None,
             require_tpu: bool = True, out_dir: Optional[str] = None,
             patch: Optional[Callable] = None) -> dict:
    """One run of ``workload``; returns the result object. ``patch``
    (tests only) is called with the built ``Program`` before any round."""
    import jax
    from repro.analysis.retrace import RetraceDetector
    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(workload, root) if root else load_cell(workload)
    info = device_info(cell.chips, require_tpu)
    log(f"set-up: imports and device {time.perf_counter() - t_start:.3f}s")
    prog = Program(cell, seed)
    log(f"set-up: data {prog.t_data:.3f}s ({traffic.n_rows(prog.data)} "
        f"rows of {traffic.kind(cell.mix)}), "
        f"system build {prog.t_build:.3f}s; engine=unified "
        f"P={prog.backend.engine.plane_spec.size} "
        f"clients={len(prog.clients)} k_chunk={cell.config['k_chunk']}")
    if patch is not None:
        patch(prog)
    t = time.perf_counter()
    state = init_weights(cell, seed)
    jax.block_until_ready(state)
    log(f"set-up: weights {time.perf_counter() - t:.3f}s")
    t = time.perf_counter()
    with RetraceDetector() as det:
        state, snaps = run_checked_rounds(prog, state)
        prog.finite(state).block_until_ready()
    log(f"set-up: first {CHECKED_ROUNDS} rounds (compiles included) "
        f"{time.perf_counter() - t:.3f}s, {det.compiles} compiles")
    log(f"agg_stats: {prog.backend.engine.agg_stats()}")
    setup_s = time.perf_counter() - t_start
    attempted, failed = CHECKED_ROUNDS, sum(not all_finite(s) for s in snaps)
    metrics: Dict[str, dict] = {}
    extra: Dict[str, object] = {}
    r = CHECKED_ROUNDS
    with RetraceDetector() as det:
        if not trace:
            state, n, el, f, flags = timed_rounds(prog, state, r, seconds)
            metrics["round_s"] = _metric(el / n, "s")
            window = {"rounds": n, "seconds": el}
        else:
            state, n, el, f, flags = timed_rounds(prog, state, r,
                                                  0.5 * seconds)
            plain = {"rounds": n, "seconds": el}
            r += n
            tdir = os.path.join(out_dir or os.getcwd(), "trace")
            shutil.rmtree(tdir, ignore_errors=True)
            with tracing.capture(tdir) as found:
                state, n2, el2, f2, fl2 = timed_rounds(
                    prog, state, r, TRACED_S, min_rounds=2, span=True)
            r += n2
            prog.backend.engine.timing = True
            prog.backend.engine.phase_stats(reset=True)
            state, n3, el3, f3, fl3 = timed_rounds(prog, state, r,
                                                   0.3 * seconds,
                                                   min_rounds=2)
            timed = {"rounds": n3, "seconds": el3,
                     "train_s": prog.backend.engine.phase_stats()["train"]}
            prog.backend.engine.timing = False
            n, f, flags = n + n2 + n3, f + f2 + f3, flags + fl2 + fl3
            window = {"rounds": n, "seconds": el + el2 + el3}
            t = time.perf_counter()
            red = tracing.reduce_trace(found[0]) if found else None
            log(f"trace: {found[0] if found else 'none'} reduced in "
                f"{time.perf_counter() - t:.3f}s")
            extra.update(plain=plain, timed=timed, trace=red)
    log(f"window: {window['rounds']} rounds in {window['seconds']:.4f}s, "
        f"{det.compiles} compiles inside the window")
    attempted += n
    failed += f + sum(not bool(x) for x in flags)
    peak = peak_bytes(cell.chips)
    info["memory_peak_bytes"] = peak
    if not trace:
        metrics["peak_hbm_gb"] = _metric(peak / 1e9, "GB")
        metrics["setup_s"] = _metric(setup_s, "s")
    else:
        ctx = dict(extra, cell=cell, device=info,
                   peaks=roofline.peaks(info["kind"]),
                   model_flops_per_round=model_flops_per_round(cell),
                   agg_bytes_per_round=roofline.agg_bytes(
                       roofline.stream_chunks(len(prog.clients),
                                              cell.config["k_chunk"]),
                       cell.config["plane_size"]))
        for m in cell.per_layer:
            v = cell.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = _metric(v, m["unit"])
        red = extra.get("trace")
        if red:
            info["busy_s"], info["window_s"] = red["busy_s"], red["window_s"]
    result = {"attempted": attempted, "failed": failed, "metrics": metrics,
              "device": info}
    if trace and extra.get("trace"):
        result["breakdown"] = {"device_ops": extra["trace"]["device_ops"],
                               "idle_gaps": extra["trace"]["idle_gaps"]}
    # the output check: the program's state freed first
    data, parts = prog.data, prog.parts
    del state
    prog.close()
    del prog
    gc.collect()
    t = time.perf_counter()
    nums = check(cell, seed, data, parts, snaps)
    log(f"reference check {time.perf_counter() - t:.3f}s")
    result["correct"] = bool(failed == 0 and verdict(nums, cell.limits))
    result["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                        for k, v in nums.items() if v["limit"] is not None}
    for k, v in nums.items():
        log(f"check {k}: {v['value']:.6g} (limit {v['limit']}) "
            f"worst leaf {v.get('leaf')}")
    return result


def order(result: dict) -> dict:
    """Key order of the printed line: ``checks`` last."""
    keys = ["correct", "attempted", "failed", "metrics", "device",
            "breakdown", "checks"]
    return {k: result[k] for k in keys if k in result}
