"""The compiled width round start (``UnifiedEngine._width_start``) against
the eager literal path it replaces: every chunk row equals
``plane.pack(family.up(family.down(g)))`` at the round's seed, and the
``E Eᵀ`` matrices built on the device from segment ids equal numpy
``segments.client_matrices(kind="grad")`` — on a VGG cohort mixing depth
and width, in both narrow modes, and on a d_ff transformer cohort
through the same mapping hook.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.vgg_family import scaled, vgg
from repro.core import TransformerFamily, VGGFamily, plane, tfamily
from repro.core import segments as sg
from repro.fl.engine import UnifiedEngine

VGG_ARCHS = ("vgg13", "vgg16-wider", "vgg19", "vgg19-wider")
ROUNDS = (0, 1, 7)
CHUNK = 2


def _vgg_cohort():
    return VGGFamily(), [scaled(vgg(a), 0.125, 32) for a in VGG_ARCHS]


def _tf_cohort():
    base = reduced(get_config("glm4-9b"), n_units=2, d_model=32)
    return TransformerFamily(), [
        tfamily.make_variant(base, n_units=2, ffn_scale=0.5),
        tfamily.make_variant(base, n_units=1, ffn_scale=1.0),
        tfamily.make_variant(base, n_units=2, ffn_scale=0.75)]


def _engine(family, cfgs, narrow_mode):
    return UnifiedEngine(family, cfgs, [1] * len(cfgs), embed_seed=5,
                         narrow_mode=narrow_mode)


def _eager_row(eng, g, k, seed):
    """The literal path: ``up(down(g))`` eagerly, packed."""
    cfg, gcfg = eng.client_cfgs[k], eng.global_cfg
    down = eng.family.down(g, gcfg, cfg, seed=seed, mode=eng.narrow_mode)
    return np.asarray(plane.pack(eng.family.up(down, cfg, gcfg, seed=seed),
                                 eng.plane_spec))


def _numpy_mats(eng, ks, seeds):
    """The numpy reference: ``client_matrices`` per client, stacked."""
    return sg.stack_matrices([
        sg.client_matrices(eng.family.segment_spec(
            eng.client_cfgs[k], eng.global_cfg, seed=s),
            eng._axes_map, eng._gshapes, kind="grad")
        for k, s in zip(ks, seeds)])


def _check_round_start(family, cfgs, narrow_mode):
    eng = _engine(family, cfgs, narrow_mode)
    g = eng.init_global(jax.random.PRNGKey(3))
    gp = plane.pack(g, eng.plane_spec)
    for r in ROUNDS:
        for lo in range(0, len(cfgs), CHUNK):
            ks = list(range(lo, min(lo + CHUNK, len(cfgs))))
            seeds = [eng._round_seed(r, k) for k in ks]
            rows, mats, sent = eng._width_start(gp, ks, seeds)
            rows = np.asarray(rows)
            for j, (k, s) in enumerate(zip(ks, seeds)):
                want = _eager_row(eng, g, k, s)
                scale = max(float(np.abs(want).max()), 1e-30)
                assert np.abs(rows[j] - want).max() / scale <= 1e-6, (r, k)
            ref = _numpy_mats(eng, ks, seeds)
            assert sorted(mats) == sorted(ref)
            for p in ref:
                for got, want in zip(mats[p], ref[p]):
                    np.testing.assert_allclose(np.asarray(got),
                                               np.asarray(want), rtol=0,
                                               atol=1e-7)
            # the host sends mappings and ids, never dense matrices
            dense = sum(np.asarray(m).nbytes for v in ref.values()
                        for m in v)
            assert 0 < sent < dense
    return eng


@pytest.mark.parametrize("narrow_mode", ["paper", "fold"])
def test_vgg_width_round_start_matches_eager(narrow_mode):
    eng = _check_round_start(*_vgg_cohort(), narrow_mode)
    stats = eng.step_stats()["round_start"]
    # one program per architecture: seeds are data, not new programs
    assert set(stats["traces"]) == set(range(len(VGG_ARCHS)))
    assert all(n == 1 for n in stats["traces"].values()), stats
    assert stats["rows"] == len(ROUNDS) * len(VGG_ARCHS)


def test_transformer_dff_round_start_matches_eager():
    _check_round_start(*_tf_cohort(), "paper")


@pytest.mark.parametrize("narrow_mode", ["paper", "fold"])
def test_round_start_views_use_the_compiled_path(narrow_mode):
    """``round_start`` (the evaluation views) returns the compiled rows,
    unpacked: equal to the eager literal path, client by client."""
    family, cfgs = _vgg_cohort()
    eng = _engine(family, cfgs, narrow_mode)
    g = eng.init_global(jax.random.PRNGKey(1))
    views = eng.round_start(g, selected=[1, 3], round_idx=2)
    got = np.asarray(plane.pack_stacked(views, eng.plane_spec))
    for j, k in enumerate((1, 3)):
        want = _eager_row(eng, g, k, eng._round_seed(2, k))
        scale = float(np.abs(want).max())
        assert np.abs(got[j] - want).max() / scale <= 1e-6
    assert eng.step_stats()["round_start"]["rows"] == 2


def test_width_mappings_are_the_seed_draws():
    """``width_mappings`` is bit-identical to the ``dup_mapping`` draws
    ``up`` made before the hook, tag by tag, for both families."""
    from repro.core import netchange as nc
    from repro.core import vggops
    family, cfgs = _vgg_cohort()
    gcfg = family.union(cfgs)
    mid = vggops._mid_widths(cfgs[0], gcfg)
    maps = family.width_mappings(cfgs[0], gcfg, seed=11)
    assert maps
    for tag, m in maps.items():
        node = tuple(int(x) if x.isdigit() else x for x in tag.split("/"))
        want = nc.dup_mapping(mid[node], vggops._width_of(gcfg, node),
                              tag=tag, seed=11)
        assert m.dtype == np.int32
        np.testing.assert_array_equal(m, want)
    tfam, tcfgs = _tf_cohort()
    tg = tfam.union(tcfgs)
    tmaps = tfam.width_mappings(tcfgs[0], tg, seed=11)
    assert tmaps and all(t.endswith("/ffn") for t in tmaps)
    for tag, m in tmaps.items():
        np.testing.assert_array_equal(
            m, nc.dup_mapping(tcfgs[0].d_ff, tg.d_ff, tag=tag, seed=11))
