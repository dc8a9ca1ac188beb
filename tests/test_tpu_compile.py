"""Compiles for a described TPU v5e: the main path lowers for the chip.

The TPU compiler is installed even where no chip is attached, and it
compiles for a topology that is only described.  These tests compile the
fused Pallas pool-step kernel (``repro.kernels.pool_step``) at the
shapes the simulator runs it at, and the default ``gather``-mode chunk
program of a replay, so a block shape or an op that Mosaic or XLA:TPU
refuses fails here instead of on the chip.  Nothing runs: a passing
compile says nothing about results or times.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.  All of these tests live
in this one file so that one worker loads it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.cluster.engine import ClusterEvent, _chunk_runner, init_cluster
from repro.kernels.pool_step import _column, fused_evict_place_impl
from repro.sim import Scenario


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compile
    cache off (an entry compiled for a described chip cannot be read
    back here)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.mark.parametrize("w", [1, 8, 256])
def test_column_trick_matches_reshape(w):
    """The kernel turns a row into a column with a diagonal max on the
    TPU and a reshape in interpret mode; both must give the same bits
    (``inf``, ``-0.0`` and the smallest normal included)."""
    row = jax.random.normal(jax.random.key(w), (1, w), jnp.float32) * 1e3
    row = row.at[0, : min(w, 4)].set(jnp.asarray(
        [jnp.inf, -0.0, 1.1754944e-38, -jnp.inf][: min(w, 4)]))
    want = _column(row, interpret=True)
    got = _column(row, interpret=False)
    assert got.shape == want.shape == (w, 1)
    assert jnp.array_equal(jax.lax.bitcast_convert_type(got, jnp.int32),
                           jax.lax.bitcast_convert_type(want, jnp.int32))


def _pool_shapes(sharding, p: int, s: int, lanes: tuple = ()):
    def sds(dtype, shape):
        return jax.ShapeDtypeStruct(lanes + shape, dtype, sharding=sharding)
    return (sds(jnp.float32, (p, s)), sds(jnp.float32, (p, s)),
            sds(jnp.float32, (p, s)), sds(jnp.bool_, (p, s)),
            sds(jnp.bool_, (p, s)), sds(jnp.float32, (p,)))


def _kernel(*args):
    return fused_evict_place_impl(*args, interpret=False)


@pytest.mark.parametrize("p,s", [
    (8, 256),     # the 4-node replay cluster, max_slots=256
    (32, 256),    # het16: 16 nodes x 2 pools, max_slots=256
    (2, 1024),    # one node at the Scenario default max_slots=1024
])
def test_fused_kernel_compiles(one_chip, p, s):
    compiled = jax.jit(_kernel).lower(*_pool_shapes(one_chip, p, s)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_kernel_compiles_under_vmap(one_chip):
    """A fused ``sweep`` vmaps the kernel over lanes: 16 lanes of the
    replay cluster."""
    compiled = jax.jit(jax.vmap(_kernel)).lower(
        *_pool_shapes(one_chip, 8, 256, lanes=(16,))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_gather_chunk_program_compiles(one_chip):
    """The default-mode replay program: one 65536-event chunk on the
    4-node replay cluster (``benchmarks/replay.py``)."""
    chunk = 65536
    scn = Scenario.cluster((2048.0, 2048.0, 4096.0, 8192.0),
                           routing="size_aware", max_slots=256)
    cfg = scn.to_cluster_config()

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def col(dtype):
        return jax.ShapeDtypeStruct((chunk,), dtype, sharding=one_chip)

    f32, i32 = jnp.float32, jnp.int32
    pools = jax.tree_util.tree_map(
        sds, jax.eval_shape(lambda: init_cluster(cfg)))
    events = ClusterEvent(t=col(f32), func_id=col(i32), size=col(f32),
                          cls=col(i32), warm=col(f32), cold=col(f32),
                          h1=col(i32), h2=col(i32))
    compiled = _chunk_runner(cfg.n_nodes, "gather").lower(
        pools, events, sds(jax.ShapeDtypeStruct((), i32)),
        sds(jax.ShapeDtypeStruct((cfg.n_nodes,), jnp.bool_)),
        sds(jax.ShapeDtypeStruct((2,), f32))).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 * 2**30
