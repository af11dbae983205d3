"""Streaming O(P·k_chunk) + two-level hierarchical aggregation (ISSUE 8).

Four layers of evidence:
  * layout equivalence — ``fedavg_stacked`` under "stream" equals the
    "plane" and "leaf" layouts on REAL coverage cohorts (width+depth
    heterogeneous VGG and Transformer-FFN: family-built masks and
    multiplicities, renorm + fallback),
  * hierarchy exactness — ``fedavg_hierarchical`` equals the flat
    aggregation for every edge-group split of the cohort (the masked
    weighted sum is associative; groups may be uneven, reordered,
    singleton or the whole cohort),
  * the memory envelope — ``PlaneAccumulator``'s accounted peak is
    O(P·k_chunk): INDEPENDENT of how many total rows stream through,
    and far below the O(P·K) resident plane it replaces,
  * the engine — a chunked streaming round (``agg_layout="stream"``,
    pinned ``k_chunk``) reproduces the plane-layout round bit-for-bit
    modulo float reassociation, and the shard_mapped edge reduce over a
    real 4-device mesh (subprocess — the suite's own jax is pinned to
    one device) matches the single-device round.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.vgg_family import VGGConfig
from repro.core import (TransformerFamily, VGGFamily, coverage_mask,
                        fedavg_stacked, multiplicity, stack_trees, tfamily)
from repro.core.aggregation import (fedavg_hierarchical, last_agg_stats,
                                    subset_weights)
from repro.core.netchange import round_embed_seed
from repro.data import EASY, ClientSampler, image_classification, iid_partition
from repro.fl import FLRunConfig, Simulator
from repro.kernels.fedavg import ops as kops

ATOL = 5e-6          # reassociation headroom on ~1e-7 kernels


def _tiny_vgg(name, stages):
    return VGGConfig(name=name, stages=stages, classifier=(16,),
                     n_classes=4, image_size=8)


def _vgg_width_cohort(K=6):
    family = VGGFamily()
    base = [_tiny_vgg("w1", ((8,), (8,))),
            _tiny_vgg("w2", ((8,), (12, 8))),
            _tiny_vgg("w3", ((12, 8), (12, 8)))]
    return family, [base[k % len(base)] for k in range(K)]


def _tffn_width_cohort(K=4):
    family = TransformerFamily()
    base = reduced(get_config("glm4-9b"), n_units=2, d_model=32)
    vs = [tfamily.make_variant(base, n_units=2, ffn_scale=0.5),
          tfamily.make_variant(base, n_units=1, ffn_scale=1.0)]
    return family, [vs[k % len(vs)] for k in range(K)]


def _coverage_fixture(family, cfgs, *, seed=0):
    """Stacked global-shaped trees + family-built masks/mult + fallback
    — the heaviest aggregation variant, on a real union architecture."""
    gcfg = family.union(list(cfgs))
    key = jax.random.PRNGKey(11)
    trees = [family.init(jax.random.fold_in(key, k), gcfg)
             for k in range(len(cfgs))]
    masks, mults = [], []
    for k, c in enumerate(cfgs):
        s = round_embed_seed(seed, 0, k)
        masks.append(coverage_mask(family, c, gcfg, policy="loose", seed=s))
        mults.append(multiplicity(family, c, gcfg, seed=s))
    fallback = family.init(jax.random.fold_in(key, 999), gcfg)
    w = subset_weights([k + 1 for k in range(len(cfgs))])
    return (stack_trees(trees), w, stack_trees(masks), stack_trees(mults),
            fallback)


def _assert_trees_close(a, b, *, atol, msg):
    for pa, pb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(pa), np.asarray(pb),
                                   atol=atol, err_msg=msg)


@pytest.mark.parametrize("cohort", ["vgg", "tffn"])
def test_stream_equals_plane_equals_leaf_width_cohorts(cohort):
    """The three layouts are the same math on family-real coverage
    inputs (masks + mult + renorm + fallback), for every chunk size —
    including one that does NOT divide K (ragged tail chunk)."""
    family, cfgs = (_vgg_width_cohort() if cohort == "vgg"
                    else _tffn_width_cohort())
    stacked, w, masks, mult, fb = _coverage_fixture(family, cfgs)
    kw = dict(masks=masks, mult=mult, renorm=True, fallback=fb)
    leaf = fedavg_stacked(stacked, w, layout="leaf", **kw)
    plane = fedavg_stacked(stacked, w, layout="plane", **kw)
    _assert_trees_close(leaf, plane, atol=ATOL, msg=f"{cohort}: plane")
    for kc in (1, 2, len(cfgs) - 1, len(cfgs)):
        stream = fedavg_stacked(stacked, w, layout="stream", k_chunk=kc,
                                **kw)
        _assert_trees_close(plane, stream, atol=ATOL,
                            msg=f"{cohort}: stream kc={kc}")
        stats = last_agg_stats()
        assert stats["layout"] == "stream" and stats["k_chunk"] == kc


def test_stream_layout_plain_eq1():
    """Unmasked Eq. 1 (no coverage): stream == plane == leaf too — the
    dot-product fast path of the streaming oracle is the same sum."""
    family, cfgs = _vgg_width_cohort(K=5)
    stacked, w, _, _, _ = _coverage_fixture(family, cfgs)
    leaf = fedavg_stacked(stacked, w, layout="leaf")
    for layout, kw in (("plane", {}), ("stream", dict(k_chunk=2))):
        got = fedavg_stacked(stacked, w, layout=layout, **kw)
        _assert_trees_close(leaf, got, atol=ATOL, msg=layout)


def test_hierarchical_equals_flat_for_every_split():
    """Two-level edge reduce == flat aggregation for every partition of
    the cohort into edge groups: even, uneven, reordered, singleton,
    whole-cohort. Exact up to reassociation — no renormalization happens
    per group (weights stay GLOBAL subset weights)."""
    family, cfgs = _vgg_width_cohort(K=6)
    stacked, w, masks, mult, fb = _coverage_fixture(family, cfgs)
    kw = dict(masks=masks, mult=mult, renorm=True, fallback=fb)
    flat = fedavg_stacked(stacked, w, layout="plane", **kw)
    splits = [
        [[0, 1, 2, 3, 4, 5]],                       # whole cohort
        [[0, 1], [2, 3], [4, 5]],                   # even edges
        [[0], [1, 2, 3, 4, 5]],                     # uneven
        [[5, 3, 1], [0, 2, 4]],                     # reordered rows
        [[0], [1], [2], [3], [4], [5]],             # one client per edge
    ]
    for groups in splits:
        got = fedavg_hierarchical(stacked, w, groups=groups, k_chunk=2,
                                  **kw)
        _assert_trees_close(flat, got, atol=ATOL, msg=f"groups={groups}")


def test_hierarchical_rejects_bad_groups():
    family, cfgs = _vgg_width_cohort(K=4)
    stacked, w, *_ = _coverage_fixture(family, cfgs)
    for bad in ([[0, 1], [2]],          # missing a client
                [[0, 1], [1, 2, 3]],    # duplicated client
                [[0, 1, 2, 3, 4]]):     # out-of-range client
        with pytest.raises(ValueError):
            fedavg_hierarchical(stacked, w, groups=bad)


def test_accumulator_peak_memory_is_o_p_kchunk():
    """The accounted aggregation footprint is O(P·k_chunk): streaming
    8 rows and 64 rows through the same accumulator shape reports the
    SAME peak, and that peak stays far below the O(P·K) resident plane
    the whole-plane layout would allocate at K=64."""
    n, kc = 50_000, 4
    rng = np.random.default_rng(0)

    def stream(total_rows):
        acc = kops.PlaneAccumulator(n, use_kernel=False, k_hint=kc)
        for _ in range(total_rows // kc):
            chunk = jnp.asarray(rng.normal(size=(kc, n)), jnp.float32)
            wk = jnp.full((kc,), 1.0 / total_rows, jnp.float32)
            acc.update(chunk, wk)
        return acc.stats()

    s8, s64 = stream(8), stream(64)
    assert s8["peak_bytes"] == s64["peak_bytes"], (s8, s64)
    assert s64["rows"] == 64 and s64["peak_chunk_rows"] == kc
    whole_plane_bytes = 4 * 64 * n
    assert s64["peak_bytes"] < whole_plane_bytes / 4, (
        s64["peak_bytes"], whole_plane_bytes)
    # the envelope is exactly buffers + one chunk's streamed operands
    assert s64["peak_bytes"] == s64["buffer_bytes"] + s64["chunk_bytes"]


def _sim_cohort():
    import dataclasses
    cfgs = [_tiny_vgg("t2", ((8,), (8,))), _tiny_vgg("t3", ((8,), (8, 8))),
            _tiny_vgg("t4", ((8, 8), (8, 8))), _tiny_vgg("t2b", ((8,), (8,)))]
    spec = dataclasses.replace(EASY, image_size=8, n_classes=4)
    data = image_classification(spec, 64, seed=0)
    test = image_classification(spec, 32, seed=9)
    parts = iid_partition(64, len(cfgs), seed=0)

    def samplers():
        return [ClientSampler(data, p, round_fraction=0.5, batch_size=8,
                              seed=i) for i, p in enumerate(parts)]

    return cfgs, samplers, test


def test_engine_streaming_round_matches_plane_round():
    """A full Simulator run with agg_layout="stream" (chunked training
    + PlaneAccumulator aggregation, k_chunk=2 over K=4) reproduces the
    plane-layout run — history and global params."""
    cfgs, samplers, test = _sim_cohort()
    out = {}
    for layout, kc in (("plane", None), ("stream", 2)):
        cfg = FLRunConfig(method="fedadp", rounds=2, local_epochs=1,
                          lr=0.05, momentum=0.9, engine="unified",
                          agg_layout=layout, k_chunk=kc)
        sim = Simulator(VGGFamily(), cfgs, samplers(), cfg, test)
        out[layout] = sim.run()
    np.testing.assert_allclose(out["plane"]["history"],
                               out["stream"]["history"], atol=1e-5)
    _assert_trees_close(out["plane"]["global_params"],
                        out["stream"]["global_params"], atol=1e-5,
                        msg="global params")


def test_engine_stream_agg_stats_report_chunked_peak():
    """The engine's ``agg_stats()`` surface: a streaming round reports
    layout/k_chunk and a peak below the whole-plane footprint."""
    cfgs, samplers, test = _sim_cohort()
    cfg = FLRunConfig(method="fedadp", rounds=1, local_epochs=1, lr=0.05,
                      engine="unified", agg_layout="stream", k_chunk=1)
    sim = Simulator(VGGFamily(), cfgs, samplers(), cfg, test)
    sim.run()
    be = next(b for k, b in sim._backends.items() if k[0] == "unified")
    stats = be.engine.agg_stats()
    assert stats["layout"] == "stream" and stats["k_chunk"] == 1
    assert stats["peak_chunk_rows"] == 1 and stats["rows"] == len(cfgs)
    # the envelope carries NO K term: three (padded) buffers plus one
    # k_chunk-row chunk's streamed operands (≤ 3 streams), whatever the
    # cohort size
    assert stats["buffer_bytes"] == 3 * 4 * stats["padded"]
    assert stats["chunk_bytes"] <= 3 * 4 * stats["padded"] * stats["k_chunk"]
    assert stats["peak_bytes"] == stats["buffer_bytes"] + stats["chunk_bytes"]


_EDGE_SCRIPT = textwrap.dedent("""
    import os
    import jax
    assert jax.device_count() == 4, jax.device_count()
    import numpy as np
    from repro.core import VGGFamily
    from repro.configs.vgg_family import VGGConfig
    from repro.data import (EASY, ClientSampler, image_classification,
                            iid_partition)
    from repro.fl import FLRunConfig, Simulator
    from repro.sharding import cohort_mesh
    import dataclasses

    def tiny(name, stages):
        return VGGConfig(name=name, stages=stages, classifier=(16,),
                         n_classes=4, image_size=8)

    cfgs = [tiny("t2", ((8,), (8,))), tiny("t3", ((8,), (8, 8))),
            tiny("t4", ((8, 8), (8, 8))), tiny("t2b", ((8,), (8,)))]
    spec = dataclasses.replace(EASY, image_size=8, n_classes=4)
    data = image_classification(spec, 64, seed=0)
    test = image_classification(spec, 32, seed=9)
    parts = iid_partition(64, len(cfgs), seed=0)

    def samplers():
        return [ClientSampler(data, p, round_fraction=0.5, batch_size=8,
                              seed=i) for i, p in enumerate(parts)]

    cfg = FLRunConfig(method="fedadp", rounds=2, local_epochs=1, lr=0.05,
                      momentum=0.9, engine="unified", agg_mode="coverage")
    outs = {}
    for tag, mesh in (("flat", None), ("mesh", cohort_mesh(len(cfgs)))):
        sim = Simulator(VGGFamily(), cfgs, samplers(), cfg, test, mesh=mesh)
        outs[tag] = sim.run()
        if tag == "mesh":
            assert mesh is not None, "cohort_mesh gave no mesh on 4 devices"
            be = next(b for k, b in sim._backends.items()
                      if k[0] == "unified")
            stats = be.engine.agg_stats()
            assert stats["layout"] == "edge", stats
            assert stats["edges"] == 4, stats
    np.testing.assert_allclose(outs["flat"]["history"],
                               outs["mesh"]["history"], atol=1e-4)
    import jax.tree_util as jtu
    for a, b in zip(jax.tree.leaves(outs["flat"]["global_params"]),
                    jax.tree.leaves(outs["mesh"]["global_params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-4)
    print("EDGE-REDUCE-OK")
""")


def test_edge_reduce_on_four_device_mesh_subprocess():
    """The two-level hierarchical reduce under a REAL 4-device client
    mesh: the shard_mapped edge pre-reduce (one partial triple per mesh
    slot, psum to the global reduce) matches the flat single-device
    round to 1e-4. Runs in a subprocess because this suite's jax is
    pinned to the real single-device topology (tests/conftest.py)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + env.get("XLA_FLAGS", ""))
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", _EDGE_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-4000:])
    assert "EDGE-REDUCE-OK" in proc.stdout, proc.stdout[-2000:]


_STREAM_MESH_SCRIPT = textwrap.dedent("""
    import jax
    assert jax.device_count() == 4, jax.device_count()
    import dataclasses
    import numpy as np
    from repro.core import VGGFamily
    from repro.configs.vgg_family import VGGConfig
    from repro.data import (EASY, ClientSampler, image_classification,
                            iid_partition)
    from repro.fl import FLRunConfig, Simulator
    from repro.sharding import cohort_mesh

    def tiny(name, stages):
        return VGGConfig(name=name, stages=stages, classifier=(16,),
                         n_classes=4, image_size=8)

    # depth (stage 1) and width (stage 0) heterogeneous: coverage rounds
    # stream masks AND multiplicities
    cfgs = [tiny("a", ((8,), (8,))), tiny("b", ((12,), (8,))),
            tiny("c", ((8,), (8, 8))), tiny("d", ((12,), (8, 8)))]
    spec = dataclasses.replace(EASY, image_size=8, n_classes=4)
    data = image_classification(spec, 64, seed=0)
    test = image_classification(spec, 32, seed=9)
    parts = iid_partition(64, len(cfgs), seed=0)

    def samplers():
        return [ClientSampler(data, p, round_fraction=0.5, batch_size=8,
                              seed=i) for i, p in enumerate(parts)]

    # k_chunk=4: each chunk's rows split over the 4 devices (one partial
    # triple per device, psum); k_chunk=2: rows replicated
    for kc in (4, 2):
        cfg = FLRunConfig(method="fedadp", rounds=2, local_epochs=1,
                          lr=0.05, momentum=0.9, engine="unified",
                          agg_mode="coverage", agg_layout="stream",
                          k_chunk=kc, use_kernel=True)
        outs = {}
        for tag, mesh in (("flat", None), ("mesh", cohort_mesh(len(cfgs)))):
            sim = Simulator(VGGFamily(), cfgs, samplers(), cfg, test,
                            mesh=mesh)
            outs[tag] = sim.run()
            assert sim.backend.engine.agg_stats()["layout"] == "stream"
        np.testing.assert_allclose(outs["flat"]["history"],
                                   outs["mesh"]["history"], atol=1e-4)
        for a, b in zip(jax.tree.leaves(outs["flat"]["global_params"]),
                        jax.tree.leaves(outs["mesh"]["global_params"])):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), atol=1e-4)
    print("STREAM-MESH-OK")
""")


def test_stream_accumulate_on_four_device_mesh_subprocess():
    """The streaming round under a REAL 4-device client mesh with the
    Pallas accumulate (interpret mode): chunks split over the mesh
    accumulate one partial triple per device inside ``shard_map`` (the
    TPU compiler cannot partition a Pallas call), replicated chunks
    accumulate on every device, and both match the single-device round
    to 1e-4."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + env.get("XLA_FLAGS", ""))
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", _STREAM_MESH_SCRIPT],
                          env=env, capture_output=True, text=True,
                          timeout=1200)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-4000:])
    assert "STREAM-MESH-OK" in proc.stdout, proc.stdout[-2000:]
