"""The fused Pallas pool-step backend vs the argsort composite and the
numpy oracle.

The acceptance bar of the step-backend layer: ``mode="fused"`` must be
*bitwise* identical to ``mode="vmap"`` and to the sequential oracle —
across every registered routing x replacement policy, all three scan
shapes (static, failure-injected, autoscaled), chunked scans, and mixed
fused/vmap sweep lanes.  Plus interpret-mode unit tests of the kernel's
rank-by-counting against the argsort order of ``_evict_place_lax`` and
against ``_evict_prefix``'s keyed sort, and the
pinned GreedyDual no-eviction clock regression.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pool_jax import (Event, PoolConfig, _evict_place_lax,
                                 _evict_prefix, get_step_backend, init_pool,
                                 pool_step, pool_step_batch, step_backends)
from repro.core.registry import replacement_policies, routing_policies
from repro.core.types import MISS, Policy
from repro.kernels.pool_step import fused_evict_place_impl
from repro.sim import Scenario, simulate, sweep

from conftest import quantized_trace

# built-ins only: other test modules register throwaway replacement
# policies (no Policy enum member), which must not leak into this matrix
REPLACEMENTS = tuple(n for n in replacement_policies()
                     if n.upper() in Policy.__members__)


def _scn(routing: str, replacement: str, **kw) -> Scenario:
    """Heterogeneous 4-node cluster incl. a unified node — small enough
    that misses actually evict."""
    return Scenario.cluster((1024.0, 1024.0, 2048.0, 4096.0),
                            small_frac=(0.8, 0.8, 0.8, 0.5),
                            unified=(False, True, False, False),
                            routing=routing, replacement=replacement,
                            max_slots=16, **kw)


def _assert_bitwise(a, b, what: str) -> None:
    assert np.array_equal(np.asarray(a.raw.node),
                          np.asarray(b.raw.node)), what
    assert np.array_equal(np.asarray(a.raw.outcome),
                          np.asarray(b.raw.outcome)), what
    assert a.summary() == b.summary(), what


# ---------------------------------------------------------------------------
# kernel unit tests (interpret mode, backend contract level)
# ---------------------------------------------------------------------------

def _random_batch(rng, p=8, s=24):
    pri = rng.integers(0, 4, (p, s)).astype(np.float32)   # heavy pri ties
    seq = rng.permutation(np.arange(1.0, p * s + 1, dtype=np.float32)
                          ).reshape(p, s)
    size = rng.integers(1, 64, (p, s)).astype(np.float32)
    valid = rng.random((p, s)) < 0.8
    idle = valid & (rng.random((p, s)) < 0.7)
    pri = np.where(idle, pri, np.inf).astype(np.float32)
    deficit = rng.integers(-40, 400, (p,)).astype(np.float32)
    return tuple(jnp.asarray(x)
                 for x in (pri, seq, size, idle, valid, deficit))


def test_rank_by_counting_matches_argsort_on_ties():
    """The kernel ranks by counting; ``_evict_place_lax`` double-argsorts.
    With heavy priority ties the (priority, seq) tie-break must still
    produce the identical evict set, bit for bit."""
    for seed in range(5):
        args = _random_batch(np.random.default_rng(seed))
        ref = _evict_place_lax(*args)
        got = fused_evict_place_impl(*args, interpret=True)
        for name, r, g in zip(("evict", "freed", "ins", "avail", "empty"),
                              ref, got):
            assert np.array_equal(np.asarray(r), np.asarray(g)), (seed, name)


def test_kernel_matches_evict_prefix_per_pool():
    """Same thing one pool at a time, against ``_evict_prefix`` itself
    (the keyed sort the engines run, on a real ``PoolState``)."""
    rng = np.random.default_rng(42)
    p = init_pool(PoolConfig(2048.0, Policy.LRU, 16))
    # warm the pool with a few inserts so seq/valid are realistic
    for i in range(12):
        ev = Event(jnp.float32(i / 64), jnp.int32(i), jnp.float32(100.0),
                   jnp.int32(0), jnp.float32(0.5), jnp.float32(2.0))
        p, _ = pool_step(p, ev)
    now = jnp.float32(100.0)
    idle = p.valid & (p.busy_until <= now)
    # equal last_use on every slot -> pure-seq tie-break for LRU
    p = p._replace(last_use=jnp.zeros_like(p.last_use))
    for deficit in (0.0, 150.0, 550.0, 1e6):
        ev_ref, freed_ref = _evict_prefix(p, idle, jnp.float32(deficit))
        pri = jnp.where(idle, p.last_use, jnp.inf)
        evict, freed, ins, avail, empty = fused_evict_place_impl(
            pri[None], p.seq[None], p.size[None], idle[None],
            p.valid[None], jnp.asarray([deficit], jnp.float32),
            interpret=True)
        assert np.array_equal(np.asarray(ev_ref), np.asarray(evict[0]))
        assert float(freed_ref) == float(freed[0])
        va = p.valid & ~ev_ref
        assert int(ins[0]) == int(jnp.argmax(~va))
        assert bool(empty[0]) == bool(jnp.any(~va))


def test_step_backend_registry():
    assert set(step_backends()) >= {"lax", "fused"}
    with pytest.raises(ValueError, match="unknown step backend"):
        get_step_backend("nope")
    from repro.core.pool_jax import register_step_backend
    with pytest.raises(ValueError, match="already registered"):
        register_step_backend("lax")(lambda *a: a)


def test_gd_clock_no_eviction():
    """Satellite regression pin: the GreedyDual clock guard collapsed to
    a single ``where`` — with no eviction ``max(where(evict, gd_pri,
    -inf))`` is ``-inf`` and ``maximum`` degrades to the old clock, so a
    miss that fits without evicting must NOT move the clock."""
    p = init_pool(PoolConfig(4096.0, Policy.GREEDY_DUAL, 8))
    p = p._replace(clock=jnp.float32(7.25))
    ev = Event(jnp.float32(1.0), jnp.int32(3), jnp.float32(128.0),
               jnp.int32(0), jnp.float32(0.5), jnp.float32(2.0))
    new, outcome = pool_step(p, ev)
    assert int(outcome) == MISS                   # placed, no eviction
    assert float(new.clock) == 7.25               # untouched
    # and the batched twin agrees, through both backends
    stacked = jax.tree_util.tree_map(lambda a: a[None], p)
    for backend in ("lax", "fused"):
        nb, ob = pool_step_batch(stacked, ev, get_step_backend(backend))
        assert int(ob[0]) == MISS, backend
        assert float(nb.clock[0]) == 7.25, backend


def test_pool_step_batch_matches_vmap_bitwise():
    """``pool_step_batch`` (through both backends) is bit-identical to
    ``jax.vmap(pool_step)`` on every state field, across all registered
    replacement policies stacked as data."""
    rng = np.random.default_rng(1)
    states = [init_pool(PoolConfig(512.0, Policy[n.upper()], 12))
              for n in REPLACEMENTS]
    pools = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)
    ref, lax_b, fus_b = pools, pools, pools
    lax_fn, fus_fn = get_step_backend("lax"), get_step_backend("fused")
    for i in range(60):
        ev = Event(jnp.float32(i * 0.25), jnp.int32(rng.integers(0, 6)),
                   jnp.float32(int(rng.integers(16, 200))), jnp.int32(0),
                   jnp.float32(0.5), jnp.float32(2.0))
        ref, o_r = jax.vmap(pool_step, in_axes=(0, None))(ref, ev)
        lax_b, o_l = pool_step_batch(lax_b, ev, lax_fn)
        fus_b, o_f = pool_step_batch(fus_b, ev, fus_fn)
        assert np.array_equal(np.asarray(o_r), np.asarray(o_l))
        assert np.array_equal(np.asarray(o_r), np.asarray(o_f))
    for name, a, b, c in zip(ref._fields, ref, lax_b, fus_b):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
        assert np.array_equal(np.asarray(a), np.asarray(c)), name


# ---------------------------------------------------------------------------
# full-engine equivalence matrix
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("replacement", REPLACEMENTS)
@pytest.mark.parametrize("routing", routing_policies())
def test_fused_matrix_static(routing, replacement):
    """fused == vmap == oracle, bitwise, over every registered routing x
    replacement pair on the static scan."""
    tr = quantized_trace(np.random.default_rng(0), 300)
    s = _scn(routing, replacement)
    f = simulate(s, tr, mode="fused")
    _assert_bitwise(f, simulate(s, tr, mode="vmap"), "fused-vs-vmap")
    _assert_bitwise(f, simulate(s, tr, engine="ref"), "fused-vs-oracle")


@pytest.mark.slow
@pytest.mark.parametrize("replacement", REPLACEMENTS)
@pytest.mark.parametrize("routing", routing_policies())
def test_fused_matrix_failures(routing, replacement):
    """Same matrix with a node outage: the fused step composes with the
    masked scan (down pools frozen, recovery invalidation) bit-exactly."""
    tr = quantized_trace(np.random.default_rng(1), 300)
    s = _scn(routing, replacement, failures=((100.0, 900.0, 2),))
    f = simulate(s, tr, mode="fused")
    _assert_bitwise(f, simulate(s, tr, mode="vmap"), "fused-vs-vmap")
    r = simulate(s, tr, engine="ref")
    _assert_bitwise(f, r, "fused-vs-oracle")
    assert np.array_equal(np.asarray(f.invalidated),
                          np.asarray(r.invalidated))


@pytest.mark.slow
@pytest.mark.parametrize("replacement", REPLACEMENTS)
@pytest.mark.parametrize("routing", routing_policies())
def test_fused_matrix_autoscale(routing, replacement):
    """Same matrix under the epoch scan: per-epoch ``pool_resize`` and
    the fused per-event step share the eviction order bit-exactly."""
    from repro.core.continuum import Autoscale
    tr = quantized_trace(np.random.default_rng(2), 300)
    s = _scn(routing, replacement, autoscale=Autoscale(epoch_events=64))
    f = simulate(s, tr, mode="fused")
    _assert_bitwise(f, simulate(s, tr, mode="vmap"), "fused-vs-vmap")
    r = simulate(s, tr, engine="ref")
    _assert_bitwise(f, r, "fused-vs-oracle")
    assert np.array_equal(np.asarray(f.epoch_fracs), np.asarray(r.epoch_fracs))


@pytest.mark.parametrize("chunk", [97, 128])
def test_fused_chunked_matches_monolithic(chunk):
    """Chunked fused scans (donated carries threading between chunks) are
    bit-identical to the monolithic fused scan."""
    tr = quantized_trace(np.random.default_rng(3), 500)
    s = _scn("size_aware", "greedy_dual")
    mono = simulate(s, tr, mode="fused")
    _assert_bitwise(mono, simulate(s, tr, mode="fused", chunk_events=chunk),
                    f"chunk={chunk}")
    sf = _scn("sticky", "lru", failures=((50.0, 800.0, 1),))
    monof = simulate(sf, tr, mode="fused")
    _assert_bitwise(monof,
                    simulate(sf, tr, mode="fused", chunk_events=chunk),
                    f"failures chunk={chunk}")


def test_mixed_mode_sweep_lanes():
    """One ``sweep`` call mixing fused and vmap lanes: per-lane modes
    bucket into separate programs but return bit-identical results, in
    input order, with the lane's mode recorded in ``run_info``."""
    tr = quantized_trace(np.random.default_rng(4), 300)
    scns = [_scn("sticky", "lru"), _scn("sticky", "lru"),
            _scn("size_aware", "greedy_dual"), _scn("size_aware",
                                                    "greedy_dual")]
    res = sweep(tr, scns, mode=["fused", "vmap", "fused", "gather"])
    _assert_bitwise(res[0], res[1], "lane 0 vs 1")
    _assert_bitwise(res[2], res[3], "lane 2 vs 3")
    assert [r.run_info["mode"] for r in res] == ["fused", "vmap", "fused",
                                                "gather"]
    with pytest.raises(ValueError, match="entries"):
        sweep(tr, scns, mode=["fused"])
    with pytest.raises(ValueError, match="mode must be one of"):
        sweep(tr, scns, mode=["fused", "vmap", "fused", "nope"])


def test_fused_vmapped_sweep_matches_per_lane():
    """A homogeneous fused sweep (many lanes, ONE vmapped program) equals
    lane-by-lane fused simulates."""
    tr = quantized_trace(np.random.default_rng(5), 300)
    scns = [_scn("sticky", r) for r in REPLACEMENTS]
    swept = sweep(tr, scns, mode="fused")
    for s, got in zip(scns, swept):
        _assert_bitwise(got, simulate(s, tr, mode="fused"), s.replacement)
