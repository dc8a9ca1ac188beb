"""``_evict_prefix`` (one keyed sort, no gather or scatter) against the
former two-argsort composite ``_evict_place_lax``, bit for bit.

The miss path and ``pool_resize`` both evict through ``_evict_prefix``;
``_evict_place_lax`` keeps the argsort formulation as the reference.  The
cases cover every built-in replacement policy, random seeded pools of 8,
64 and 1,024 slots with heavy priority ties, crafted priorities (ties,
``-0.0``, ``+-inf``, NaNs of either sign, zero-byte slots), deficits at
and around every prefix sum, all-busy pools, the function under
``jax.vmap`` as sweep lanes run it, and ``pool_resize`` against the
sequential oracle's ``WarmPool.resize``.
"""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pool_jax import (Event, PoolConfig, _evict_place_lax,
                                 _evict_prefix, _priority, init_pool,
                                 pool_resize, pool_step)
from repro.core.pool_ref import WarmPool, _f32
from repro.core.registry import replacement_policies
from repro.core.types import ClassMetrics, Policy

# built-ins only: other test modules register throwaway replacement
# policies (no Policy enum member)
REPLACEMENTS = tuple(n for n in replacement_policies()
                     if n.upper() in Policy.__members__)

_NEG_NAN = np.frombuffer(np.uint32(0xFFC00001).tobytes(), np.float32)[0]


def _bits(x) -> np.ndarray:
    x = np.asarray(x)
    return x.view(np.int32) if x.dtype == np.float32 else x


def _state(policy: str, pri, seq, size, valid, busy_until):
    """A pool whose every replacement field holds ``pri``, so that the
    policy's where-chain picks its branch out of equal candidates."""
    s = len(pri)
    p = init_pool(PoolConfig(1e9, Policy[policy.upper()], s))
    f = lambda x: jnp.asarray(np.asarray(x, np.float32))
    return p._replace(last_use=f(pri), freq=f(pri), gd_pri=f(pri),
                      seq=f(seq), size=f(size), busy_until=f(busy_until),
                      valid=jnp.asarray(np.asarray(valid, bool)))


def _idle(p, now=0.0):
    return p.valid & (p.busy_until <= now)


def _run_new(p, idle, deficit):
    return jax.jit(_evict_prefix)(p, idle, jnp.float32(deficit))


def _run_new_vmapped(ps, idles, deficits):
    return jax.jit(jax.vmap(_evict_prefix))(ps, idles, deficits)


def _reference(ps, idles, deficits):
    """The argsort composite on a stack of pools ``ps`` ([B, S])."""
    pri = jnp.where(idles, jax.vmap(_priority)(ps), jnp.inf)
    return _evict_place_lax(pri, ps.seq, ps.size, idles, ps.valid,
                            deficits)


def _assert_same(ps, idles, deficits, evict, freed, what):
    ref = _reference(ps, idles, deficits)
    valid_after = ps.valid & ~evict
    got = (evict, freed, jnp.argmax(~valid_after, axis=-1),
           jnp.sum(jnp.where(idles, ps.size, 0.0), axis=-1),
           jnp.any(~valid_after, axis=-1))
    for name, r, g in zip(("evict", "freed", "ins", "avail", "empty"),
                          ref, got):
        assert np.array_equal(_bits(r), _bits(g)), (what, name)


def _stack(ps):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ps)


def _deficits(p, idle, rng=None):
    """Deficits equal to prefix sums of the idle bytes in ``(priority,
    seq, slot)`` order, plus zero, negative, tiny, random and
    above-everything deficits."""
    idle = np.asarray(idle)
    pri = np.where(idle, np.asarray(_priority(p)), np.inf)
    order = np.lexsort((np.arange(len(pri)), np.asarray(p.seq), pri))
    sz = np.where(idle, np.asarray(p.size), np.float32(0))[order]
    sums = np.cumsum(sz, dtype=np.float32)
    out = [-5.0, 0.0, 1e-12, 0.5, float(sums[-1]) + 1.0, 1e9]
    out += [float(v) for v in sums[:: max(1, len(sums) // 8)]]
    if rng is not None:
        out += [float(v) for v in rng.integers(-40, int(sums[-1]) + 40, 4)]
    return np.asarray(out, np.float32)


def _random_pool(rng, policy, s):
    pri = rng.integers(0, 4, s).astype(np.float32)        # heavy ties
    seq = rng.permutation(np.arange(1.0, s + 1, dtype=np.float32))
    size = rng.integers(0, 64, s).astype(np.float32)      # zero bytes too
    valid = rng.random(s) < 0.8
    busy = np.where(rng.random(s) < 0.3, 1.0, 0.0)
    return _state(policy, pri, seq, size, valid, busy)


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("s", [8, 64, 1024])
@pytest.mark.parametrize("policy", REPLACEMENTS)
def test_random_pools_match_argsort(policy, s, seed):
    """Random seeded pools, each at a spread of deficits: the vmapped
    function (as sweep lanes run it) and the unbatched one on a few."""
    rng = np.random.default_rng([seed, s, len(policy)])
    pools = [_random_pool(rng, policy, s) for _ in range(4)]
    ps, ds = [], []
    for p in pools:
        for d in _deficits(p, _idle(p), rng):
            ps.append(p)
            ds.append(d)
    ps, ds = _stack(ps), jnp.asarray(ds)
    idles = _idle(ps)
    evict, freed = _run_new_vmapped(ps, idles, ds)
    _assert_same(ps, idles, ds, evict, freed, (policy, s, seed, "vmap"))
    for i in range(0, len(ds), 7):
        p = jax.tree_util.tree_map(lambda x: x[i], ps)
        e, f = _run_new(p, idles[i], ds[i])
        assert np.array_equal(np.asarray(e), np.asarray(evict[i])), i
        assert _bits(f) == _bits(freed[i]), i


_INF = np.inf
_NAN = np.nan

# name -> (priority, seq, size, valid, busy_until) of an 8-slot pool
_CRAFTED = {
    "pri_ties": ([2.0] * 8, [8, 3, 5, 1, 7, 2, 6, 4], [10] * 8,
                 [1] * 8, [0] * 8),
    "pri_and_seq_ties": ([1.0, 1, 0, 0, 1, 1, 0, 0], [3, 3, 3, 3, 1, 1, 2, 2],
                         [5, 6, 7, 8, 9, 10, 11, 12], [1] * 8, [0] * 8),
    "signed_zero": ([0.0, -0.0, 0.0, -0.0, 1.0, -1.0, -0.0, 0.0],
                    [1, 2, 3, 4, 5, 6, 7, 8], [4, 4, 4, 4, 4, 4, 4, 4],
                    [1] * 8, [0] * 8),
    "pos_inf_idle": ([_INF, 1.0, _INF, 2.0, _INF, 0.0, 3.0, _INF],
                     [8, 7, 6, 5, 4, 3, 2, 1], [1, 2, 4, 8, 16, 32, 64, 128],
                     [1] * 8, [0, 0, 1, 0, 0, 0, 1, 0]),
    "neg_inf_idle": ([-_INF, 1.0, -_INF, 2.0, 5.0, 0.0, -_INF, 7.0],
                     [1, 2, 3, 4, 5, 6, 7, 8], [9, 9, 9, 9, 9, 9, 9, 9],
                     [1] * 8, [0] * 8),
    "nan_idle": ([_NAN, 1.0, _NAN, _INF, 0.0, _NAN, 2.0, -0.0],
                 [2, 4, 6, 8, 1, 3, 5, 7], [3, 1, 4, 1, 5, 9, 2, 6],
                 [1] * 8, [0] * 8),
    "neg_nan_idle": ([_NEG_NAN, 1.0, _NAN, -_INF, _NEG_NAN, 3.0, _INF, 0.0],
                     [1, 2, 3, 4, 5, 6, 7, 8], [7, 7, 7, 7, 7, 7, 7, 7],
                     [1] * 8, [0, 0, 0, 0, 0, 0, 1, 0]),
    "all_nan": ([_NAN] * 8, [5, 6, 7, 8, 1, 2, 3, 4], [2] * 8,
                [1] * 8, [0] * 8),
    "zero_bytes": ([0.0, 1, 2, 3, 4, 5, 6, 7], [1, 2, 3, 4, 5, 6, 7, 8],
                   [0, 0, 5, 0, 5, 0, 0, 5], [1] * 8, [0] * 8),
    "all_busy": ([0.0, 1, 2, 3, 4, 5, 6, 7], [1, 2, 3, 4, 5, 6, 7, 8],
                 [10] * 8, [1] * 8, [1] * 8),
    "empty_slots": ([0.0, 1, 2, 3, 4, 5, 6, 7], [1, 2, 3, 4, 5, 6, 7, 8],
                    [10] * 8, [1, 0, 1, 0, 1, 1, 0, 1], [0] * 8),
}


@pytest.mark.parametrize("policy", REPLACEMENTS)
@pytest.mark.parametrize("case", sorted(_CRAFTED))
def test_crafted_pools_match_argsort(case, policy):
    """Crafted priorities at every deficit kind, unbatched and vmapped."""
    p = _state(policy, *_CRAFTED[case])
    idle = _idle(p)
    ds = _deficits(p, idle)
    ps = _stack([p] * len(ds))
    idles = _idle(ps)
    evict, freed = _run_new_vmapped(ps, idles, jnp.asarray(ds))
    _assert_same(ps, idles, jnp.asarray(ds), evict, freed, (case, policy))
    for i, d in enumerate(ds):
        e, f = _run_new(p, idle, d)
        assert np.array_equal(np.asarray(e), np.asarray(evict[i])), d
        assert _bits(f) == _bits(freed[i]), d


def test_nan_priorities_evict_in_sort_order():
    """Two NaN priorities: a float threshold compare would evict neither;
    the sort ranks them last, by seq, and the prefix takes them."""
    p = _state("lru", [_NAN, 1.0, _NAN, 0.0], [4, 3, 2, 1], [10] * 4,
               [1] * 4, [0] * 4)
    evict, freed = _run_new(p, _idle(p), 25.0)
    assert np.asarray(evict).tolist() == [False, True, True, True]
    assert float(freed) == 30.0
    evict, _ = _run_new(p, _idle(p), 1e9)
    assert np.asarray(evict).all()


def _drive(policy, seed, n=40, capacity=1024.0, slots=16):
    """The same quantized event stream through ``pool_step`` and the
    oracle's ``WarmPool.access``."""
    rng = np.random.default_rng(seed)
    cfg = PoolConfig(capacity, Policy[policy.upper()], slots)
    p, ref, m = init_pool(cfg), WarmPool(cfg), ClassMetrics()
    step = jax.jit(pool_step)
    for i in range(n):
        t, fid = i / 8, int(rng.integers(0, 24))
        size = float(rng.integers(16, 160))
        warm, cold = float(rng.integers(1, 16)) / 8, float(
            rng.integers(16, 64)) / 8
        p, _ = step(p, Event(jnp.float32(t), jnp.int32(fid),
                             jnp.float32(size), jnp.int32(0),
                             jnp.float32(warm), jnp.float32(cold)))
        ref.access(t, fid, size, warm, cold, m)
    return p, ref, n / 8


def _residents(p):
    v = np.asarray(p.valid)
    return sorted(zip(np.asarray(p.seq)[v].tolist(),
                      np.asarray(p.func_id)[v].tolist(),
                      np.asarray(p.size)[v].tolist()))


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("policy", REPLACEMENTS)
def test_pool_resize_matches_warm_pool(policy, seed):
    """``pool_resize`` evicts through ``_evict_prefix``: the survivors and
    ``free`` equal ``WarmPool.resize`` at capacities down to zero, at
    times when some residents are still busy."""
    p, ref, now = _drive(policy, seed)
    resize = jax.jit(pool_resize)
    for frac in (1.25, 1.0, 0.7, 0.4, 0.1, 0.0):
        cap = _f32(1024.0 * frac)
        for dt in (0.0, 2.0):
            pr = resize(p, jnp.float32(now + dt), jnp.float32(cap))
            oracle = copy.deepcopy(ref)
            oracle.resize(now + dt, cap)
            uids = sorted(c.uid for c in ref.containers)
            rank = {u: k for k, u in enumerate(uids)}
            seqs = sorted(np.asarray(p.seq)[np.asarray(p.valid)].tolist())
            want = sorted((seqs[rank[c.uid]], c.func_id, c.size_mb)
                          for c in oracle.containers)
            assert _residents(pr) == want, (frac, dt)
            assert float(pr.free) == _f32(oracle.free_mb), (frac, dt)
