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
import collections
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.cluster.engine import (Carry, ClusterEvent, EventXs,
                                  _chunk_runner, init_cluster)
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


def _lower_chunk(sharding, scn: Scenario, chunk: int):
    """The ``gather``-mode chunk program of ``scn`` for ``chunk`` events,
    lowered for ``sharding``'s device (the default device if ``None``)."""
    cfg = scn.to_cluster_config()

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    def col(dtype):
        return jax.ShapeDtypeStruct((chunk,), dtype, sharding=sharding)

    f32, i32 = jnp.float32, jnp.int32
    pools = jax.tree_util.tree_map(
        sds, jax.eval_shape(lambda: init_cluster(cfg)))
    events = ClusterEvent(t=col(f32), func_id=col(i32), size=col(f32),
                          cls=col(i32), warm=col(f32), cold=col(f32),
                          h1=col(i32), h2=col(i32))
    return _chunk_runner(cfg.n_nodes, "gather").lower(
        Carry(pools), EventXs(events), sds(jax.ShapeDtypeStruct((), i32)),
        sds(jax.ShapeDtypeStruct((cfg.n_nodes,), jnp.bool_)),
        sds(jax.ShapeDtypeStruct((2,), f32)), None)


def test_gather_chunk_program_compiles(one_chip):
    """The default-mode replay program: one 65536-event chunk on the
    4-node replay cluster (``benchmarks/replay.py``)."""
    scn = Scenario.cluster((2048.0, 2048.0, 4096.0, 8192.0),
                           routing="size_aware", max_slots=256)
    compiled = _lower_chunk(one_chip, scn, 65536).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 * 2**30


# one KiSS edge node of 10 GB split 80/20 with 1,024 slots per pool, in
# 65,536-event chunks: the benchmark's stress replay
_STRESS = dict(node_mb=(10240.0,), small_frac=0.8, unified=False,
               routing="sticky", replacement="lru", max_slots=1024)
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = .*? ([a-z][\w\-]*)\(")


def _evict_opcodes(hlo: str) -> collections.Counter:
    """Opcodes of the compiled instructions whose ``op_name`` carries the
    ``pool.evict`` scope (a fusion counts under its own opcode, its fused
    instructions under theirs)."""
    out = collections.Counter()
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m and "pool.evict" in line:
            out[m.group(1)] += 1
    return out


def _assert_one_sort(hlo: str) -> None:
    ops = _evict_opcodes(hlo)
    assert ops["sort"] == 1, ops
    assert not any(op.startswith(("gather", "scatter")) for op in ops), ops


_HEADER = re.compile(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_BRANCHES = re.compile(r" conditional\(.*branch_computations=\{([^}]*)\}")
_BODY = re.compile(r" while\(.*body=%?([\w.\-]+)")


def _computations(hlo: str) -> dict:
    """Each computation of the compiled text by name: its instruction
    lines."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = _HEADER.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def _assert_evict_in_a_branch(hlo: str) -> None:
    """The eviction sort runs in a branch of a ``conditional`` of the scan
    body, so a step that cannot evict skips it, and not in the body."""
    comps = _computations(hlo)
    (sort_in,) = [c for c, lines in comps.items() for ln in lines
                  if " sort(" in ln and "pool.evict" in ln]
    bodies = {m.group(1) for lines in comps.values() for ln in lines
              for m in [_BODY.search(ln)] if m}
    assert sort_in not in bodies
    owners = [c for c, lines in comps.items() for ln in lines
              for m in [_BRANCHES.search(ln)] if m
              and sort_in in [b.strip().lstrip("%")
                              for b in m.group(1).split(",")]]
    assert len(owners) == 1 and owners[0] in bodies, (sort_in, owners,
                                                       bodies)


@pytest.fixture(scope="module")
def stress_hlo(one_chip):
    """The stress replay's chunk program compiled for a v5e."""
    return _lower_chunk(one_chip, Scenario(**_STRESS),
                        65536).compile().as_text()


@pytest.fixture(scope="module")
def stress_hlo_cpu():
    """The same program compiled for the default (CPU) device, where no
    TPU topology can be described."""
    return _lower_chunk(None, Scenario(**_STRESS), 65536).compile().as_text()


def test_stress_replay_evict_is_one_sort(stress_hlo):
    """In the stress replay's chunk program compiled for a v5e, the
    eviction holds one sort and no gather or scatter: on the TPU a
    dynamic gather or scatter over 1,024 slots costs more than the sort."""
    _assert_one_sort(stress_hlo)


def test_stress_replay_evict_is_one_sort_cpu(stress_hlo_cpu):
    """The same structure compiled for the CPU."""
    _assert_one_sort(stress_hlo_cpu)


def test_stress_replay_evict_runs_in_a_branch(stress_hlo):
    """Compiled for a v5e, the eviction sort sits in a branch of a
    conditional in the scan body: XLA:TPU keeps the branch."""
    _assert_evict_in_a_branch(stress_hlo)


def test_stress_replay_evict_runs_in_a_branch_cpu(stress_hlo_cpu):
    _assert_evict_in_a_branch(stress_hlo_cpu)
