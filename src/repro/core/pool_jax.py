"""JAX warm pool: fixed-slot state + one-event transition function.

This is the paper's warm pool re-expressed as a pure function over arrays so
that an entire trace is a single ``jax.lax.scan`` and whole *families* of
configurations (split ratios x policies x pool sizes) sweep in one ``vmap``
(see ``simulator_jax.py``).  Semantics are bit-compatible with the sequential
oracle in ``pool_ref.py`` (property-tested):

* greedy eviction in (priority, launch-seq) order == one keyed sort +
  prefix-sum over freed bytes, evicting the minimal prefix that covers the
  deficit;
* busy containers are never evicted;
* GreedyDual clock inflates to the max evicted priority.

The policy is carried *in the state* (``policy`` int32 scalar) rather than as
a static Python value, so a single jitted simulator can be vmapped across
every registered replacement policy as data (the priority expression is
built from ``core.registry.REPLACEMENT`` at trace time — register a new
policy and this pool ranks by it with no engine edits).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .ieee import ieee_div
from .registry import (REPLACEMENT, RESIZE, ROUTING, ResizeCtx, SlotStats,
                       replacement_priority, shrink_amounts)
from .types import DROP, HIT, MISS, Policy, PoolConfig

_INF = jnp.float32(jnp.inf)

# A newly registered policy must show up in already-jitted engines, whose
# compiled programs baked in the previous registry: drop the trace caches.
ROUTING.on_register(jax.clear_caches)
REPLACEMENT.on_register(jax.clear_caches)
RESIZE.on_register(jax.clear_caches)


class PoolState(NamedTuple):
    """Warm-pool scan state.

    The trailing fields are the vertical-scaling (resize) extension and
    default to ``None``: ``None`` leaves vanish from the JAX pytree, so a
    pool built without a resize policy flattens to the exact pre-resize
    leaves and every engine compiles the exact pre-resize programs — the
    ``resize=None`` fast path is not a runtime branch, it is the same
    jaxpr.
    """

    # per-slot arrays (S = max_slots)
    func_id: jax.Array    # i32[S], -1 = empty
    size: jax.Array       # f32[S] MB
    last_use: jax.Array   # f32[S]
    freq: jax.Array       # f32[S]
    gd_pri: jax.Array     # f32[S]
    busy_until: jax.Array # f32[S]
    seq: jax.Array        # f32[S] launch sequence (tie-break)
    valid: jax.Array      # bool[S]
    # scalars
    capacity: jax.Array   # f32
    free: jax.Array       # f32
    clock: jax.Array      # f32 GreedyDual inflation clock
    next_seq: jax.Array   # f32
    policy: jax.Array     # i32 (Policy enum value)
    # vertical scaling (all None when resize is off)
    alloc: jax.Array | None = None      # f32[S] current limit (MB)
    used: jax.Array | None = None       # f32[S] observed usage (MB)
    rz_policy: jax.Array | None = None  # i32 resize policy code
    rz_min: jax.Array | None = None     # f32 limit floor (MB)
    acc_used: jax.Array | None = None   # f32 sum of used over served events
    acc_alloc: jax.Array | None = None  # f32 sum of alloc over served events
    bneck: jax.Array | None = None      # i32 hits on shrunken residents


class Event(NamedTuple):
    t: jax.Array
    func_id: jax.Array
    size: jax.Array
    cls: jax.Array
    warm: jax.Array
    cold: jax.Array
    # observed usage of the launched container (``observed_usage``);
    # None when resize is off so chainless pytrees are unchanged
    used: jax.Array | None = None


def init_pool(cfg: PoolConfig) -> PoolState:
    s = cfg.max_slots
    rz = cfg.resize_policy is not None
    return PoolState(
        func_id=jnp.full((s,), -1, jnp.int32),
        size=jnp.zeros((s,), jnp.float32),
        last_use=jnp.zeros((s,), jnp.float32),
        freq=jnp.zeros((s,), jnp.float32),
        gd_pri=jnp.zeros((s,), jnp.float32),
        busy_until=jnp.zeros((s,), jnp.float32),
        seq=jnp.zeros((s,), jnp.float32),
        valid=jnp.zeros((s,), bool),
        capacity=jnp.float32(cfg.capacity_mb),
        free=jnp.float32(cfg.capacity_mb),
        clock=jnp.float32(0.0),
        next_seq=jnp.float32(1.0),
        policy=jnp.int32(int(cfg.policy)),
        alloc=jnp.zeros((s,), jnp.float32) if rz else None,
        used=jnp.zeros((s,), jnp.float32) if rz else None,
        rz_policy=jnp.int32(int(cfg.resize_policy)) if rz else None,
        rz_min=jnp.float32(cfg.resize_min_mb) if rz else None,
        acc_used=jnp.float32(0.0) if rz else None,
        acc_alloc=jnp.float32(0.0) if rz else None,
        bneck=jnp.int32(0) if rz else None,
    )


def _priority(p: PoolState) -> jax.Array:
    """Eviction priority per slot (lower = evicted first), built from the
    replacement-policy registry with the policy code as data."""
    stats = SlotStats(last_use=p.last_use, freq=p.freq, gd_pri=p.gd_pri,
                      size=p.size, busy_until=p.busy_until)
    return replacement_priority(jnp, p.policy, stats)


def put(x: jax.Array, i: jax.Array, v) -> jax.Array:
    """``x.at[i].set(v)`` along the last axis, written as a select: ``i``
    is one index (``x`` of shape ``[S]``) or one index per row (``x`` of
    shape ``[P, S]``, ``v`` scalar or ``[P]``).  The scan step updates
    slots this way and never by scatter: under a wide ``vmap`` XLA:TPU
    lost bool scatter updates (on a v5e at 1,024 sweep lanes, a newly
    placed slot's ``valid`` bit stayed False in some lanes)."""
    hit = jnp.arange(x.shape[-1]) == jnp.expand_dims(i, -1)
    return jnp.where(hit, jnp.expand_dims(v, -1) if jnp.ndim(v) else v, x)


def _unpermute(order: jax.Array, vals: jax.Array) -> jax.Array:
    """The bools ``vals``, given in permuted order ``order``, back in slot
    order.  The scatter runs on int32: XLA:TPU loses bool scatter updates
    under a wide ``vmap`` (see :func:`put`)."""
    return jnp.zeros(order.shape, jnp.int32).at[order].set(
        vals.astype(jnp.int32)) != 0


def _gd(clock, freq, cold_cost, size):
    return clock + ieee_div(freq * cold_cost, jnp.maximum(size, 1e-6))


def _sort_key(x: jax.Array) -> jax.Array:
    """f32 -> i32 keys whose signed order is the order ``lax.sort`` puts
    floats in: ``-0.0`` as ``+0.0`` and every NaN as one NaN, above
    ``+inf``.  Plain float ``<``/``==`` would not order NaNs at all."""
    x = jnp.where(x == 0, 0.0, x)
    x = jnp.where(jnp.isnan(x), jnp.nan, x)
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return b ^ ((b >> 31) & 0x7FFFFFFF)


def _evict_prefix(p: PoolState, idle: jax.Array, deficit: jax.Array,
                  bytes_per_slot: jax.Array | None = None):
    """The minimal ``(priority, seq)``-ordered prefix of idle slots whose
    eviction covers ``deficit``: greedy eviction == one keyed sort +
    prefix-sum over freed bytes.  Returns ``(evict bool[S], freed f32)``.
    Shared by the miss path of ``pool_step`` and by ``pool_resize`` —
    JAX<->oracle bit-equivalence depends on both sites evicting in the
    identical order.  ``bytes_per_slot`` is what an eviction actually
    frees (the post-shrink ``alloc`` when resize is on; defaults to
    ``size``) — the eviction *order* never depends on it.

    One stable sort on the keys ``(priority, seq, slot)`` carries the idle
    mask and the freed bytes along, so nothing is gathered into sorted
    order; the prefix goes back to slot order by comparing each slot's
    key with the key at its last position, so nothing is scattered back
    either (on the TPU a dynamic gather or scatter over the slots costs
    more than the sort).  Bitwise equal to the two stable argsorts of
    ``_evict_place_lax``, NaN and ``-0.0`` priorities included."""
    sz = p.size if bytes_per_slot is None else bytes_per_slot
    pri = jnp.where(idle, _priority(p), _INF)       # only idle are evictable
    slot = jnp.arange(pri.shape[-1], dtype=jnp.int32)
    kp, ks = _sort_key(pri), _sort_key(p.seq)
    kp_o, ks_o, slot_o, idle_o, sz_o = jax.lax.sort(
        (kp, ks, slot, idle, jnp.where(idle, sz, 0.0)),
        num_keys=3, is_stable=True)
    freed_before = jnp.cumsum(sz_o) - sz_o
    evict_ord = idle_o & (freed_before < deficit - 1e-9)
    # freed_before never falls along the sorted order, so the evicted
    # idle slots are those ranked up to the last evicted position; read
    # its key by a one-hot reduction (no dynamic index)
    last = jnp.max(jnp.where(evict_ord, slot, -1))
    at = slot == last

    def key_at(k):
        return jnp.max(jnp.where(at, k, jnp.iinfo(jnp.int32).min))

    lp, ls, lslot = key_at(kp_o), key_at(ks_o), key_at(slot_o)
    ranked = (kp < lp) | ((kp == lp) & ((ks < ls) | ((ks == ls)
                                                    & (slot <= lslot))))
    evict = idle & (last >= 0) & ranked
    freed = jnp.sum(jnp.where(evict, sz, 0.0))
    return evict, freed


def _shrink_pass(p: PoolState, idle: jax.Array, want: jax.Array):
    """Vertical-scaling shrink pass for the miss path: run the registered
    resize policy over the pool's slots and return ``(alloc_after f32[S],
    reclaimed f32)``.  Works on both the single-pool ``[S]`` layout and
    the batched ``[P, S]`` layout (scalars become ``[P, 1]`` columns so
    broadcasting and ``axis=-1`` reductions line up)."""
    batched = p.alloc.ndim == 2
    col = (lambda x: x[:, None]) if batched else (lambda x: x)
    ctx = ResizeCtx(used=p.used, alloc=p.alloc, size=p.size, idle=idle,
                    valid=p.valid, min_mb=col(p.rz_min),
                    deficit=col(jnp.maximum(want, 0.0)),
                    free=col(p.free), capacity=col(p.capacity))
    shrink = shrink_amounts(jnp, col(p.rz_policy), ctx)
    reclaimed = jnp.sum(shrink, axis=-1)
    return p.alloc - shrink, reclaimed


def pool_step(p: PoolState, ev: Event) -> tuple[PoolState, jax.Array]:
    """Process one invocation.  Returns (new_state, outcome code).

    Only the path the event takes runs: the eviction sort sits under a
    ``lax.cond`` on whether this miss can evict at all, and the hit, miss
    and drop updates under one ``lax.switch`` on the outcome.  Where the
    predicate is batched (``jax.vmap``: the ``"vmap"`` step mode, sweep
    lanes) JAX lowers each to all of its branches plus a select, the same
    work and the same bits as computing every branch."""
    rz = p.alloc is not None                        # resize on (trace-time)
    idle = p.valid & (p.busy_until <= ev.t)
    match = idle & (p.func_id == ev.func_id)
    any_hit = jnp.any(match)
    cold_cost = ev.cold - ev.warm

    # ---- the miss path's shrink pass (resize only) and its cheap tests:
    # can the container fit once every idle slot is evicted? ----
    if rz:
        alloc1, reclaimed = _shrink_pass(p, idle, ev.size - p.free)
        free1 = p.free + reclaimed
    else:
        alloc1, free1 = None, p.free
    deficit = ev.size - free1
    total_evictable = jnp.sum(
        jnp.where(idle, p.size if alloc1 is None else alloc1, 0.0))
    fits = ((ev.size <= p.capacity + 1e-9)
            & (total_evictable >= deficit - 1e-9))

    # ---- evict the minimal (priority, seq)-prefix, on a miss that needs
    # room and can get it.  Skipping is exact: at deficit - 1e-9 <= 0 the
    # prefix is empty (the bytes freed before a slot are never negative),
    # and otherwise the step hits or drops and its eviction is unused ----
    with jax.named_scope("pool.evict"):
        evict, freed = jax.lax.cond(
            ~any_hit & (deficit - 1e-9 > 0) & fits,
            lambda: _evict_prefix(p, idle, deficit, alloc1),
            lambda: (jnp.zeros_like(p.valid), jnp.float32(0.0)))
    valid_after = p.valid & ~evict
    can_place = fits & jnp.any(~valid_after)
    outcome = jnp.where(any_hit, HIT, jnp.where(can_place, MISS, DROP))

    def hit():
        # touch the matching idle container with lowest seq
        hit_slot = jnp.argmin(jnp.where(match, p.seq, _INF))
        new_freq = p.freq[hit_slot] + 1.0
        hit_extra = {} if not rz else dict(
            acc_used=p.acc_used + p.used[hit_slot],
            acc_alloc=p.acc_alloc + p.alloc[hit_slot],
            # a resident serving from a shrunken limit is a bottleneck
            bneck=p.bneck + (p.alloc[hit_slot]
                             < p.size[hit_slot]).astype(jnp.int32),
        )
        return p._replace(
            last_use=put(p.last_use, hit_slot, ev.t),
            freq=put(p.freq, hit_slot, new_freq),
            gd_pri=put(p.gd_pri, hit_slot,
                       _gd(p.clock, new_freq, cold_cost, p.size[hit_slot])),
            busy_until=put(p.busy_until, hit_slot, ev.t + ev.warm),
            **hit_extra,
        )

    def miss():
        # insert into the first empty slot left after the eviction
        ins = jnp.argmax(~valid_after)
        is_gd = p.policy == int(Policy.GREEDY_DUAL)
        # with no eviction the inner max is -inf and maximum() degrades
        # to p.clock, so no extra any(evict) guard is needed
        # (regression-pinned by test_pool_kernel.test_gd_clock_no_eviction)
        new_clock = jnp.where(
            is_gd,
            jnp.maximum(p.clock,
                        jnp.max(jnp.where(evict, p.gd_pri, -_INF))),
            p.clock)
        miss_extra = {} if not rz else dict(
            alloc=put(jnp.where(evict, 0.0, alloc1), ins, ev.size),
            used=put(jnp.where(evict, 0.0, p.used), ins, ev.used),
            acc_used=p.acc_used + ev.used,
            acc_alloc=p.acc_alloc + ev.size,
        )
        return p._replace(
            func_id=put(p.func_id, ins, ev.func_id),
            size=put(p.size, ins, ev.size),
            last_use=put(p.last_use, ins, ev.t),
            freq=put(p.freq, ins, 1.0),
            gd_pri=put(p.gd_pri, ins,
                       _gd(new_clock, 1.0, cold_cost, ev.size)),
            busy_until=put(p.busy_until, ins, ev.t + ev.cold),
            seq=put(p.seq, ins, p.next_seq),
            valid=put(valid_after, ins, True),
            free=free1 + freed - ev.size,
            clock=new_clock,
            next_seq=p.next_seq + 1.0,
            **miss_extra,
        )

    # branches indexed by the outcome codes HIT, MISS, DROP = 0, 1, 2
    return jax.lax.switch(outcome, (hit, miss, lambda: p)), outcome


# ---------------------------------------------------------------------------
# Step backends: pluggable implementations of the miss-path
# evict-and-place decision over the stacked [pools, slots] axes.
#
# The contract (all arrays batched over a leading pool axis P):
#
#   backend(pri f32[P,S], seq f32[P,S], size f32[P,S], idle bool[P,S],
#           valid bool[P,S], deficit f32[P])
#       -> (evict bool[P,S], freed f32[P], ins i32[P],
#           avail f32[P], empty_exists bool[P])
#
# where ``pri`` is already masked to +inf on non-idle slots, ``size`` is
# the bytes an eviction frees (the post-shrink per-slot ``alloc`` on
# resize-enabled lanes — it feeds byte accounting only, never the
# eviction order), ``deficit`` is the bytes that must be freed (may be
# <= 0), ``evict`` is the minimal
# (priority, seq)-ordered idle prefix covering the deficit (identical
# order to ``_evict_prefix``), ``freed``/``avail`` are evicted / total
# evictable bytes, and ``ins``/``empty_exists`` locate the first slot
# that is empty after eviction.  Every backend must be *bitwise*
# equivalent to ``_evict_prefix`` — the numpy oracle stays the
# semantics-of-record and the equivalence tests compare exactly.
_STEP_BACKENDS: dict = {}


def register_step_backend(name: str):
    """Register a miss-path evict-and-place backend (see the contract
    above).  Mirrors the policy registries: registering drops JIT caches
    so already-compiled engines pick the new backend table up."""
    def deco(fn):
        if name in _STEP_BACKENDS:
            raise ValueError(f"step backend {name!r} already registered")
        _STEP_BACKENDS[name] = fn
        jax.clear_caches()
        return fn
    return deco


def step_backends() -> tuple[str, ...]:
    """Names of the registered step backends (import-order stable)."""
    get_step_backend("fused")   # make sure the lazy default is in
    return tuple(_STEP_BACKENDS)


def get_step_backend(name: str):
    """Resolve a backend by name; ``"fused"`` lazily imports the Pallas
    kernel module (kernels -> core is the only import direction)."""
    if name not in _STEP_BACKENDS and name == "fused":
        from ..kernels import pool_step as _  # noqa: F401  (registers)
    try:
        return _STEP_BACKENDS[name]
    except KeyError:
        raise ValueError(f"unknown step backend {name!r}; registered: "
                         f"{tuple(_STEP_BACKENDS)}") from None


@register_step_backend("lax")
def _evict_place_lax(pri, seq, size, idle, valid, deficit):
    """Reference backend: the former ``_evict_prefix`` formulation (two
    stable argsorts, gathers into sorted order and a scatter back),
    vmapped over the pool axis.  No engine path runs it by default: it is
    kept as the reference that ``_evict_prefix`` and the fused kernel are
    tested against bit for bit, and the jaxpr the fused kernel is priced
    against in ``benchmarks/pool_step.py``."""
    def one(pri, seq, size, idle, valid, deficit):
        by_seq = jnp.argsort(seq, stable=True)
        order = by_seq[jnp.argsort(pri[by_seq], stable=True)]
        sz_ord = jnp.where(idle[order], size[order], 0.0)
        freed_before = jnp.cumsum(sz_ord) - sz_ord
        evict_ord = idle[order] & (freed_before < deficit - 1e-9)
        evict = _unpermute(order, evict_ord)
        freed = jnp.sum(jnp.where(evict, size, 0.0))
        avail = jnp.sum(jnp.where(idle, size, 0.0))
        valid_after = valid & ~evict
        return (evict, freed, jnp.argmax(~valid_after), avail,
                jnp.any(~valid_after))

    return jax.vmap(one)(pri, seq, size, idle, valid, deficit)


def pool_step_batch(p: PoolState, ev: Event, evict_place):
    """Process one invocation against *all* stacked pools at once.

    The batched twin of ``pool_step``: ``p`` carries a leading pool axis
    ``P`` on every field and the hit/miss/drop decision is computed for
    every pool against the same event; the caller keeps only the routed
    pool's new state (exactly like the ``"vmap"`` step mode).  The miss
    path's evict-and-place decision is delegated to ``evict_place`` (a
    registered step backend) — everything else is plain batched jnp, so a
    backend swap cannot perturb the hit path.  Bitwise-identical to
    ``jax.vmap(pool_step)`` when the backend honours its contract.
    """
    rz = p.alloc is not None                         # resize on (trace-time)
    P = p.func_id.shape[0]
    rows = jnp.arange(P)
    idle = p.valid & (p.busy_until <= ev.t)          # [P, S]
    match = idle & (p.func_id == ev.func_id)
    any_hit = jnp.any(match, axis=-1)                # [P]
    cold_cost = ev.cold - ev.warm

    # ---- HIT branch: touch the matching idle container with lowest seq ----
    hit_slot = jnp.argmin(jnp.where(match, p.seq, _INF), axis=-1)
    new_freq = p.freq[rows, hit_slot] + 1.0
    hit_extra = {} if not rz else dict(
        acc_used=p.acc_used + p.used[rows, hit_slot],
        acc_alloc=p.acc_alloc + p.alloc[rows, hit_slot],
        bneck=p.bneck + (p.alloc[rows, hit_slot]
                         < p.size[rows, hit_slot]).astype(jnp.int32),
    )
    hit_state = p._replace(
        last_use=put(p.last_use, hit_slot, ev.t),
        freq=put(p.freq, hit_slot, new_freq),
        gd_pri=put(p.gd_pri, hit_slot,
                   _gd(p.clock, new_freq, cold_cost, p.size[rows, hit_slot])),
        busy_until=put(p.busy_until, hit_slot, ev.t + ev.warm),
        **hit_extra,
    )

    # ---- MISS branch: shrink pass (resize only), then the backend
    # evicts the (priority, seq)-prefix.  The backend's ``size`` argument
    # is the bytes an eviction frees — the post-shrink ``alloc`` when
    # resize is on — and never feeds the eviction *order*, so every
    # registered backend (incl. the fused Pallas kernel) serves
    # resize-enabled lanes unchanged. --------------------------------------
    if rz:
        alloc1, reclaimed = _shrink_pass(p, idle, ev.size - p.free)
        free1 = p.free + reclaimed
    else:
        alloc1, free1 = None, p.free
    deficit = ev.size - free1                        # [P]
    with jax.named_scope("pool.evict"):
        stats = SlotStats(last_use=p.last_use, freq=p.freq, gd_pri=p.gd_pri,
                          size=p.size, busy_until=p.busy_until)
        pri = jnp.where(idle,
                        replacement_priority(jnp, p.policy[:, None], stats),
                        _INF)
        evict, freed, ins, avail, empty_exists = evict_place(
            pri, p.seq, p.size if alloc1 is None else alloc1, idle,
            p.valid, deficit)

    can_place = ((ev.size <= p.capacity + 1e-9)
                 & (avail >= deficit - 1e-9)
                 & empty_exists)
    is_gd = p.policy == int(Policy.GREEDY_DUAL)
    new_clock = jnp.where(
        is_gd,
        jnp.maximum(p.clock,
                    jnp.max(jnp.where(evict, p.gd_pri, -_INF), axis=-1)),
        p.clock)
    valid_after = p.valid & ~evict
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

    # ---- select ----
    outcome = jnp.where(any_hit, HIT,
                        jnp.where(can_place, MISS, DROP))   # [P]

    def pick(h, m, d):
        return jax.tree_util.tree_map(
            lambda a, b, c: jnp.where(
                outcome.reshape((-1,) + (1,) * (a.ndim - 1)) == HIT, a,
                jnp.where(outcome.reshape(
                    (-1,) + (1,) * (a.ndim - 1)) == MISS, b, c)),
            h, m, d)

    new_state = pick(hit_state, miss_state, p)
    return new_state, outcome


def pool_resize(p: PoolState, now: jax.Array,
                new_capacity: jax.Array) -> PoolState:
    """Change pool capacity between autoscaler epochs.

    Evicts lowest-priority *idle* containers (same ``(priority, seq)``
    order as ``pool_step``) until the new capacity is respected; busy
    containers are never killed, so a hard shrink can leave ``free``
    negative, which naturally blocks admissions until they drain.  Unlike
    the miss path of ``pool_step``, eviction here does not inflate the
    GreedyDual clock.  ``now`` is the epoch-boundary time.  Pure per-pool:
    the cluster engine vmaps it over the stacked ``[pools, slots]`` axes,
    and ``WarmPool.resize`` is its sequential float32-mirrored twin.
    """
    rz = p.alloc is not None
    bytes_ = p.size if not rz else p.alloc           # what eviction frees
    used = jnp.sum(jnp.where(p.valid, bytes_, 0.0))
    deficit = used - new_capacity
    idle = p.valid & (p.busy_until <= now)
    evict, freed = _evict_prefix(p, idle, deficit, None if not rz else bytes_)
    extra = {} if not rz else dict(
        alloc=jnp.where(evict, 0.0, p.alloc),
        used=jnp.where(evict, 0.0, p.used),
    )
    return p._replace(
        valid=p.valid & ~evict,
        capacity=new_capacity,
        free=new_capacity - (used - freed),
        **extra,
    )
