"""``pool_step`` runs only the path an event takes, bit for bit as the
compute-every-branch formulation it replaced.

The eviction sort sits under a ``lax.cond`` and the hit, miss and drop
updates under a ``lax.switch`` on the outcome.  ``_select_pool_step``
below keeps the former formulation (every branch computed, one kept by
``jnp.where``) as the reference.  Every state field and the outcome are
compared bitwise on crafted pools that steer one event down each path,
on random replays under every replacement policy, and under
``jax.vmap`` with lanes that take different paths, where JAX lowers the
conditionals to every branch plus a select.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pool_jax import (_INF, Event, _evict_prefix, _gd,
                                 _shrink_pass, init_pool, pool_step, put)
from repro.core.registry import RESIZE, replacement_policies
from repro.core.types import DROP, HIT, MISS, Policy, PoolConfig

from conftest import quantized_trace

# built-ins only: other test modules register throwaway replacement
# policies (no Policy enum member)
REPLACEMENTS = tuple(n for n in replacement_policies()
                     if n.upper() in Policy.__members__)
SLOTS, NOW = 8, 10.0


def _select_pool_step(p, ev):
    """The former ``pool_step``: the hit state, the eviction and the miss
    state are all computed, and ``jnp.where`` keeps one."""
    rz = p.alloc is not None
    idle = p.valid & (p.busy_until <= ev.t)
    match = idle & (p.func_id == ev.func_id)
    any_hit = jnp.any(match)
    cold_cost = ev.cold - ev.warm

    hit_slot = jnp.argmin(jnp.where(match, p.seq, _INF))
    new_freq = p.freq[hit_slot] + 1.0
    hit_extra = {} if not rz else dict(
        acc_used=p.acc_used + p.used[hit_slot],
        acc_alloc=p.acc_alloc + p.alloc[hit_slot],
        bneck=p.bneck + (p.alloc[hit_slot]
                         < p.size[hit_slot]).astype(jnp.int32),
    )
    hit_state = p._replace(
        last_use=put(p.last_use, hit_slot, ev.t),
        freq=put(p.freq, hit_slot, new_freq),
        gd_pri=put(p.gd_pri, hit_slot,
                   _gd(p.clock, new_freq, cold_cost, p.size[hit_slot])),
        busy_until=put(p.busy_until, hit_slot, ev.t + ev.warm),
        **hit_extra,
    )

    if rz:
        alloc1, reclaimed = _shrink_pass(p, idle, ev.size - p.free)
        free1 = p.free + reclaimed
    else:
        alloc1, free1 = None, p.free
    deficit = ev.size - free1
    evict, freed = _evict_prefix(p, idle, deficit, alloc1)
    total_evictable = jnp.sum(
        jnp.where(idle, p.size if alloc1 is None else alloc1, 0.0))

    valid_after = p.valid & ~evict
    empty_exists = jnp.any(~valid_after)
    can_place = ((ev.size <= p.capacity + 1e-9)
                 & (total_evictable >= deficit - 1e-9)
                 & empty_exists)

    ins = jnp.argmax(~valid_after)
    is_gd = p.policy == int(Policy.GREEDY_DUAL)
    new_clock = jnp.where(
        is_gd,
        jnp.maximum(p.clock, jnp.max(jnp.where(evict, p.gd_pri, -_INF))),
        p.clock)
    miss_extra = {} if not rz else dict(
        alloc=put(jnp.where(evict, 0.0, alloc1), ins, ev.size),
        used=put(jnp.where(evict, 0.0, p.used), ins, ev.used),
        acc_used=p.acc_used + ev.used,
        acc_alloc=p.acc_alloc + ev.size,
    )
    miss_state = p._replace(
        func_id=put(p.func_id, ins, ev.func_id),
        size=put(p.size, ins, ev.size),
        last_use=put(p.last_use, ins, ev.t),
        freq=put(p.freq, ins, 1.0),
        gd_pri=put(p.gd_pri, ins, _gd(new_clock, 1.0, cold_cost, ev.size)),
        busy_until=put(p.busy_until, ins, ev.t + ev.cold),
        seq=put(p.seq, ins, p.next_seq),
        valid=put(valid_after, ins, True),
        free=free1 + freed - ev.size,
        clock=new_clock,
        next_seq=p.next_seq + 1.0,
        **miss_extra,
    )

    outcome = jnp.where(any_hit, HIT, jnp.where(can_place, MISS, DROP))

    def pick(h, m, d):
        return jax.tree_util.tree_map(
            lambda a, b, c: jnp.where(
                outcome == HIT, a, jnp.where(outcome == MISS, b, c)),
            h, m, d)

    return pick(hit_state, miss_state, p), outcome


def _bits(x) -> np.ndarray:
    x = np.asarray(x)
    return x.view(np.int32) if x.dtype == np.float32 else x


def _assert_same(got, want):
    g_leaves, g_tree = jax.tree_util.tree_flatten(got)
    w_leaves, w_tree = jax.tree_util.tree_flatten(want)
    assert g_tree == w_tree
    names = list(got[0]._fields) if hasattr(got[0], "_fields") else []
    for i, (g, w) in enumerate(zip(g_leaves, w_leaves)):
        assert np.array_equal(_bits(g), _bits(w)), (
            names[i] if i < len(names) else i)


def _pool(capacity, slots, policy="lru", resize=None, free=None):
    """A pool of ``SLOTS`` slots whose first ones hold ``slots``: tuples
    ``(func_id, size, busy_until, last_use)``, launched in that order.
    ``free`` defaults to the capacity less the resident bytes."""
    rz = None if resize is None else RESIZE.resolve(resize)
    p = init_pool(PoolConfig(capacity, Policy[policy.upper()], SLOTS,
                             resize_policy=rz))
    n = len(slots)
    f32 = lambda col, fill=0.0: jnp.asarray(np.array(
        [s[col] for s in slots] + [fill] * (SLOTS - n), np.float32))
    size = f32(1)
    p = p._replace(
        func_id=jnp.asarray(np.array([s[0] for s in slots]
                                     + [-1] * (SLOTS - n), np.int32)),
        size=size, busy_until=f32(2), last_use=f32(3),
        freq=jnp.where(jnp.arange(SLOTS) < n, 2.0, 0.0),
        gd_pri=f32(3), seq=jnp.asarray(np.arange(1, SLOTS + 1, dtype=np.float32)
                                       * (np.arange(SLOTS) < n)),
        valid=jnp.arange(SLOTS) < n,
        free=jnp.float32(capacity - sum(s[1] for s in slots)
                         if free is None else free),
        next_seq=jnp.float32(n + 1))
    if resize is not None:
        # residents hold their full size and use 40% of it
        p = p._replace(alloc=size, used=jnp.round(size * 0.4))
    return p


def _event(func_id, size, resize=False):
    return Event(t=jnp.float32(NOW), func_id=jnp.int32(func_id),
                 size=jnp.float32(size), cls=jnp.int32(0),
                 warm=jnp.float32(1.5), cold=jnp.float32(4.0),
                 used=jnp.float32(round(size * 0.5)) if resize else None)


IDLE, BUSY = 5.0, 20.0       # busy_until before and after NOW
CASES = {
    # (pool, event, outcome)
    "hit": (lambda: _pool(1000.0, [(1, 100.0, IDLE, 1.0),
                                   (3, 200.0, IDLE, 2.0)]),
            lambda: _event(3, 200.0), HIT),
    "miss_without_eviction": (
        lambda: _pool(1000.0, [(1, 100.0, IDLE, 1.0),
                               (2, 200.0, BUSY, 2.0)]),
        lambda: _event(9, 300.0), MISS),
    "miss_with_eviction": (
        lambda: _pool(1000.0, [(1, 400.0, IDLE, 3.0), (2, 300.0, IDLE, 1.0),
                               (4, 250.0, IDLE, 2.0)]),
        lambda: _event(9, 200.0), MISS),
    "drop_evictable_below_deficit": (
        lambda: _pool(1000.0, [(1, 900.0, BUSY, 1.0), (2, 50.0, IDLE, 2.0)]),
        lambda: _event(9, 300.0), DROP),
    "drop_size_above_capacity": (
        lambda: _pool(1000.0, [(1, 100.0, IDLE, 1.0)]),
        lambda: _event(9, 2000.0), DROP),
    "drop_no_empty_slot": (
        lambda: _pool(10000.0, [(i, 10.0, IDLE if i % 2 else BUSY, i)
                                for i in range(SLOTS)]),
        lambda: _event(99, 10.0), DROP),
    "drop_no_empty_slot_all_busy": (
        lambda: _pool(10000.0, [(i, 10.0, BUSY, i) for i in range(SLOTS)]),
        lambda: _event(99, 10.0), DROP),
    # deficit in (0, 1e-9]: the prefix is empty and the miss places
    "deficit_below_tolerance": (
        lambda: _pool(300.0, [(1, 100.0, IDLE, 1.0), (2, 200.0, IDLE, 2.0)]),
        lambda: _event(9, 5e-10), MISS),
    "deficit_at_tolerance": (
        lambda: _pool(300.0, [(1, 100.0, IDLE, 1.0), (2, 200.0, IDLE, 2.0)]),
        lambda: _event(9, float(np.float32(1e-9))), MISS),
    # just above it: the first idle slot in LRU order goes
    "deficit_above_tolerance": (
        lambda: _pool(300.0, [(1, 100.0, IDLE, 1.0), (2, 200.0, IDLE, 2.0)]),
        lambda: _event(9, 4e-9), MISS),
    "nan_priorities": (
        lambda: _pool(1000.0, [(1, 400.0, IDLE, np.nan),
                               (2, 300.0, IDLE, 1.0),
                               (4, 250.0, IDLE, np.nan)]),
        lambda: _event(9, 200.0), MISS),
    "resize_hit": (
        lambda: _pool(1000.0, [(1, 100.0, IDLE, 1.0), (3, 200.0, IDLE, 2.0)],
                      resize="fair_share"),
        lambda: _event(3, 200.0, resize=True), HIT),
    "resize_miss_with_eviction": (
        lambda: _pool(1000.0, [(1, 400.0, IDLE, 3.0), (2, 300.0, IDLE, 1.0),
                               (4, 250.0, BUSY, 2.0)], resize="fair_share"),
        lambda: _event(9, 600.0, resize=True), MISS),
    "resize_drop": (
        lambda: _pool(1000.0, [(1, 900.0, BUSY, 1.0), (2, 50.0, IDLE, 2.0)],
                      resize="static"),
        lambda: _event(9, 300.0, resize=True), DROP),
}
EVICTING = {"miss_with_eviction", "deficit_above_tolerance",
            "nan_priorities", "resize_miss_with_eviction"}


@pytest.mark.parametrize("case", list(CASES))
def test_each_path_matches_the_select_formulation(case):
    make_pool, make_event, outcome = CASES[case]
    p, ev = make_pool(), make_event()
    got = jax.jit(pool_step)(p, ev)
    want = jax.jit(_select_pool_step)(p, ev)
    _assert_same(got, want)
    # the case takes the path it is named for
    assert int(got[1]) == outcome
    # (an evicted slot may take the new container, so count residents)
    evicted = (int(jnp.sum(got[0].valid))
               < int(jnp.sum(p.valid)) + (outcome == MISS))
    assert evicted == (case in EVICTING)


def _replay(step, p, trace):
    events = Event(t=jnp.asarray(trace.t), func_id=jnp.asarray(trace.func_id),
                   size=jnp.asarray(trace.size_mb), cls=jnp.asarray(trace.cls),
                   warm=jnp.asarray(trace.warm_dur),
                   cold=jnp.asarray(trace.cold_dur))
    return jax.jit(lambda p, e: jax.lax.scan(step, p, e))(p, events)


@pytest.mark.parametrize("policy", REPLACEMENTS)
def test_random_replay_matches_the_select_formulation(policy):
    """A saturated pool under each replacement policy: hits, misses that
    evict, misses that do not, and drops, every step compared."""
    trace = quantized_trace(np.random.default_rng(7), 600, horizon_s=600.0)
    p = init_pool(PoolConfig(1200.0, Policy[policy.upper()], 24))
    got = _replay(pool_step, p, trace)
    want = _replay(_select_pool_step, p, trace)
    _assert_same(got, want)
    counts = np.bincount(np.asarray(got[1]), minlength=3)
    assert (counts > 0).all(), counts


def _stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


@pytest.mark.parametrize("resize", [False, True], ids=["plain", "resize"])
def test_vmapped_lanes_on_different_paths(resize):
    """Lanes that hit, miss with and without eviction and drop, in one
    ``vmap``: the conditionals become selects, bit for bit the reference,
    and each lane equals its own unbatched step."""
    names = [n for n in CASES if n.startswith("resize_") == resize]
    pools = [CASES[n][0]() for n in names]
    events = [CASES[n][1]() for n in names]
    ps, evs = _stack(pools), _stack(events)
    got = jax.jit(jax.vmap(pool_step))(ps, evs)
    _assert_same(got, jax.jit(jax.vmap(_select_pool_step))(ps, evs))
    assert len(set(np.asarray(got[1]).tolist())) == 3
    for i, (p, ev) in enumerate(zip(pools, events)):
        lane = jax.tree_util.tree_map(lambda x: x[i], got)
        _assert_same(lane, jax.jit(pool_step)(p, ev))
