"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (every other kernel test) cannot see what the TPU
compiler refuses: blocks whose last two dims are not (8, 128) tiles,
more VMEM than a kernel may use, more HBM than the chip has, a Pallas
call outside ``shard_map`` on a mesh. These tests compile — nothing
runs — against a described ``v5e:2x2`` topology, at the paper cohort's
plane width (P = 40,717,642, VGG-19-Wider) and glm4-9b's attention
heads, and require the kernel to be in the compiled program as a
``tpu_custom_call``.

The topology is described inside a fixture, never at import time: only
one process may hold the TPU library, and each test worker imports every
test file. The persistent compilation cache is off around these
compiles (a program compiled for a described chip cannot be read back
without one). Off a TPU the wrappers would pick interpret mode, so every
call here passes ``interpret=False``.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.fedavg import ops
from repro.kernels.flash_attention import flash_attention

P_UNION = 40_717_642     # the paper cohort's union plane (VGG-19-Wider)
TILE = 256               # core.quant.DEFAULT_TILE
FLASH = dict(B=2, S=512, KV=2, G=16, hd=128)


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs on disk
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *avals) -> str:
    return jax.jit(fn).lower(*avals).compile().as_text()


def _fedavg_case(name, k, sds):
    """(fn, avals) of one aggregation kernel call on the cohort plane."""
    n = P_UNION
    x, w, a = sds(k, n), sds(k), sds(n)
    q, s = sds(k, n, dtype=jnp.int8), sds(k, -(-n // TILE))
    on_chip = dict(use_kernel=True, interpret=False)
    if name == "plane_agg":
        return (lambda x, w: ops.plane_agg(x, w, **on_chip), (x, w))
    if name == "plane_agg_masks_mult_fallback":
        return (lambda x, w, m, mu, fb: ops.plane_agg(
            x, w, masks=m, mult=mu, fallback=fb, **on_chip),
            (x, w, x, x, a))
    if name == "plane_accum":
        return (lambda n0, d0, c0, x, w: ops.plane_accum(
            n0, d0, c0, x, w, **on_chip), (a, a, a, x, w))
    if name == "plane_accum_masks_mult":
        return (lambda n0, d0, c0, x, w, m, mu: ops.plane_accum(
            n0, d0, c0, x, w, masks=m, mult=mu, **on_chip),
            (a, a, a, x, w, x, x))
    if name == "plane_accum_q":
        return (lambda n0, d0, c0, q, s, w: ops.plane_accum_q(
            n0, d0, c0, q, s, w, tile=TILE, **on_chip),
            (a, a, a, q, s, w))
    if name == "plane_accum_q_masks_mult":
        return (lambda n0, d0, c0, q, s, w, m, mu: ops.plane_accum_q(
            n0, d0, c0, q, s, w, masks=m, mult=mu, tile=TILE, **on_chip),
            (a, a, a, q, s, w, x, x))
    if name == "plane_accum_q_fold":
        return (lambda n0, d0, c0, q, s, w, m, b: ops.plane_accum_q(
            n0, d0, c0, q, s, w, masks=m, base=b, tile=TILE, **on_chip),
            (a, a, a, q, s, w, x, a))
    assert name == "plane_finish", name
    return (lambda n0, d0, c0, fb: ops.plane_finish(
        n0, d0, c0, fallback=fb, **on_chip), (a, a, a, a))


@pytest.mark.parametrize("name,k", [
    ("plane_agg_masks_mult_fallback", 4),
    ("plane_agg_masks_mult_fallback", 16),
    ("plane_agg_masks_mult_fallback", 20),
    ("plane_agg", 20),
    ("plane_accum", 16),
    ("plane_accum_masks_mult", 16),
    ("plane_accum_q", 16),
    ("plane_accum_q_masks_mult", 16),
    ("plane_accum_q_fold", 16),
    ("plane_finish", 1),
])
def test_fedavg_kernel_compiles_for_v5e(one_chip, name, k):
    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    fn, avals = _fedavg_case(name, k, sds)
    assert "tpu_custom_call" in _compiled_text(fn, *avals)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_attention_compiles_for_v5e(one_chip, direction):
    B, S, KV, G, hd = (FLASH[k] for k in ("B", "S", "KV", "G", "hd"))

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fwd(q, k, v, pos):
        return flash_attention(q, k, v, pos, pos, causal=True,
                               use_kernel=True, interpret=False)

    def bwd(q, k, v, pos):
        return jax.grad(lambda q, k, v: fwd(q, k, v, pos).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    text = _compiled_text(fwd if direction == "fwd" else bwd,
                          sds(B, S, KV, G, hd), sds(B, S, KV, hd),
                          sds(B, S, KV, hd), sds(S, dtype=jnp.int32))
    # fwd: one kernel; bwd: the forward's residuals plus dQ and dK/dV
    assert text.count('custom_call_target="tpu_custom_call"') == (
        1 if direction == "fwd" else 3)


def test_sharded_stream_accumulate_compiles_for_v5e_2x2(topo):
    """Under a 4-chip client mesh the streaming accumulate and finish run
    inside ``shard_map`` (the TPU compiler cannot partition a Pallas
    call): 20 client rows split 5 per chip, psum of partial triples."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(topo.devices), ("clients",))
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("clients"))
    n, k = P_UNION, 20
    shard = (mesh, ("clients",), True)
    block = ops.select_block(n, k, row_bytes=(4, 4, 4), col_streams=7)

    def sds(shape, sharding):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    def accum(n0, d0, c0, x, w):
        return ops._accum_step(n0, d0, c0, x, w, None, None, block=block,
                               interpret=False, use_kernel=True,
                               shard=shard)

    def finish(n0, d0, c0, fb):
        return ops._accum_finish(n0, d0, c0, fb, n=n, renorm=True,
                                 block=block, interpret=False,
                                 use_kernel=True, shard=shard)

    acc = sds((1, n), rep)
    text = _compiled_text(accum, acc, acc, acc, sds((k, n), rows),
                          sds((k,), rows))
    assert "tpu_custom_call" in text and "all-reduce" in text
    assert "tpu_custom_call" in _compiled_text(finish, acc, acc, acc,
                                               sds((n,), rep))
