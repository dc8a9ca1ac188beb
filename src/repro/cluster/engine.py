"""Batched JAX cluster engine: N heterogeneous nodes, one ``lax.scan``.

Every node owns two warm pools (a unified node uses pool 0 with the whole
node memory and a zero-capacity pool 1), and all ``2N`` pools of the
cluster are stacked on one leading axis of a single ``PoolState``.  Each
event of the trace is one step of a ``lax.scan``:

1. per-node load signals (``free``/``capacity`` of the pool that would
   serve this request) are read across the stacked axis;
2. the routing policy — carried as *data* (an int32 code) so sweeps can
   vmap over it — picks a node via a ``lax.switch`` whose branch table is
   *built from the routing registry at trace time* (``core.registry``):
   every ``@register_routing`` policy, built-in or third-party, becomes a
   branch with no engine edits;
3. the chosen pool takes the ``pool_step`` transition.

Cloud pricing (``cloud_rtt_s``, ``cloud_cold_prob``) rides along as f32
data so cost-model-style policies can read it inside the scan and sweeps
can vmap over it.

Three step modes (``STEP_MODES``), numerically identical
(property-tested against each other and against the numpy oracle in
``core/continuum.py``):

* ``"gather"`` (default) — dynamic-slice the selected pool out of the
  stack, step it, scatter it back: O(slots) work per event regardless of
  cluster size, and only the pool step's branch the event takes runs
  (the eviction sort only on a miss that evicts).
* ``"vmap"`` — ``jax.vmap(pool_step)`` steps *all* pools against the
  event and a select mask keeps only the routed pool's new state: the
  fully batched formulation, O(N * slots) per event with every branch
  of the step computed (a batched ``cond`` is a select), useful as a
  cross-check and on accelerators where the batched sort amortizes.
* ``"fused"`` — the same all-pools formulation, but the miss-path
  evict-and-place decision runs through the step-backend seam
  (``core.pool_jax.pool_step_batch`` + ``register_step_backend``) as ONE
  fused Pallas kernel (``repro.kernels.pool_step``): rank-by-counting
  instead of argsort, prefix-sum eviction, and slot placement in a
  single pass over the stacked ``[pools, slots]`` axes.  Compiled by
  Mosaic on TPU (run and checked against ``"gather"`` on a TPU v5e by
  ``chip_smoke.py``), interpreted (bit-identically) on CPU.

Two program shapes scan one event body (:func:`_make_event`), each
single-lane (unbatched, so ``lax.cond`` skips the branches an event does
not take) and lane-stacked for sweeps (vmapped, optionally sharded):

* the **chunk program** (:func:`_chunk_impl`) scans a chunk of events
  with the carry donated, and the host loop (:func:`_chunk_loop`)
  threads that carry from chunk to chunk; a whole-trace call is one
  chunk of ``len(trace)`` events.  Failure schedules
  (``Scenario(..., failures=Failures(...))``) compile host-side into
  per-event ``up``/``recover`` bool[T, N] masks that ride in as data
  (shared verbatim with the oracle): routing sees ``RouteCtx.node_up``, a
  request routed to a down node drops to the cloud without touching any
  pool, and a recovering node's pools are cleared first
  (``_invalidate_nodes``) so the re-warm cost is observable.
* the **epoch grid** (:func:`_epoch_impl`) runs autoscaled scenarios
  (``Scenario(..., autoscale=Autoscale(...))``): an outer scan over
  fixed-length epochs, the event body inside each, and at the end of
  every full epoch each KiSS node re-splits its small/large pools from
  the per-class pressure observed on that node (``pool_resize`` vmapped
  over the stacked pool axis) and — when node scaling is enabled — one
  node spawns or retires from the cluster-wide drop fraction (the
  membership mask rides in the carry).  The trace is padded to a whole
  number of epochs with guaranteed-drop no-op events that are masked out
  of the pressure signal and sliced off the outputs.

Optional mechanisms (failures, telemetry, chains) are named fields of
the carry (:class:`Carry`) and of the per-event inputs (:class:`EventXs`)
that are ``None`` when off, so they vanish from the pytree and a run
without them compiles none of their work.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, PartitionSpec

from ..core.compat import deprecated
from ..core.continuum import (Autoscale, ChainPlan, ClusterConfig, Failures,
                              cloud_cold_draws, cluster_outcomes_ref,
                              route_hashes)
from ..core.ieee import ieee_div
from ..core.pool_jax import (Event, PoolState, get_step_backend, init_pool,
                             pool_resize, pool_step, pool_step_batch, put)
from ..core.registry import ROUTING, RouteCtx, observed_usage
from ..core.types import DROP, HIT, MISS, PoolConfig, Trace
from .metrics import ClusterResult, build_result

#: The scan-step formulations, in documentation order.  The single source
#: every mode list derives from: the validator below, its error message,
#: and the ``repro.sim`` docstrings (``api.py`` splices this tuple in) —
#: adding a mode here is the whole registration.
STEP_MODES = ("gather", "vmap", "fused")


def check_step_mode(mode: str) -> None:
    """Validate a scan step mode — the one place the rule lives (used by
    the cluster entrypoints and the ``repro.sim`` front door alike)."""
    if mode not in STEP_MODES:
        raise ValueError(
            f"mode must be one of {STEP_MODES}, got {mode!r}")


def check_chunk_events(chunk_events) -> int | None:
    """Validate (and normalize) a ``chunk_events`` argument — shared by
    the cluster entrypoints and the ``repro.sim`` front door."""
    if chunk_events is None:
        return None
    try:
        ok = int(chunk_events) == chunk_events and chunk_events >= 1
    except (TypeError, ValueError):
        ok = False
    if not ok:
        raise ValueError("chunk_events must be a positive integer or None, "
                         f"got {chunk_events!r}")
    return int(chunk_events)


def check_devices(devices) -> int | None:
    """Validate (and resolve) a sweep ``devices`` argument — shared by the
    cluster sweep entrypoints and the ``repro.sim`` front door.  ``None``
    keeps the single-device programs (byte-identical to the pre-sharding
    ones), ``"all"`` means every ``jax.devices()`` entry, a positive int
    means the first that many.  Raises ``ValueError`` *before* any mesh is
    built, so a bad count fails with a clear message instead of a
    shard_map mesh-shape error deep inside jit."""
    if devices is None:
        return None
    avail = jax.device_count()
    if isinstance(devices, str):
        if devices != "all":
            raise ValueError("devices must be a positive int, 'all' or "
                             f"None, got {devices!r}")
        return avail
    try:
        ok = (not isinstance(devices, bool) and int(devices) == devices
              and devices >= 1)
    except (TypeError, ValueError):
        ok = False
    if not ok:
        raise ValueError("devices must be a positive int, 'all' or None, "
                         f"got {devices!r}")
    n = int(devices)
    if n > avail:
        raise ValueError(
            f"devices={n} exceeds the {avail} available JAX device(s) — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n} before the first jax import to turn CPU cores into a "
            "host-device mesh, or pass a smaller count")
    return n


class ClusterEvent(NamedTuple):
    """One invocation + its precomputed node hashes.

    ``used`` is the deterministic observed memory usage the vertical-
    scaling (resize) path records on a cold start — precomputed host-side
    by ``observed_usage`` and ``None`` (vanishing from the pytree, so
    resize-off programs are byte-identical to pre-resize ones) whenever
    the scenario has no resize policy."""

    t: jax.Array
    func_id: jax.Array
    size: jax.Array
    cls: jax.Array
    warm: jax.Array
    cold: jax.Array
    h1: jax.Array     # sticky hash: func_id % n_nodes
    h2: jax.Array     # second (Knuth multiplicative) hash
    used: jax.Array | None = None   # f32 observed usage (resize only)


def _host_events(trace: Trace, n_nodes: int, *,
                 resize: bool = False) -> ClusterEvent:
    """The trace's events as host (numpy) columns, with their node
    hashes; the chunk loop uploads one slice at a time."""
    h1, h2 = route_hashes(trace.func_id, n_nodes)
    fid = np.asarray(trace.func_id, np.int32)
    size = np.asarray(trace.size_mb, np.float32)
    return ClusterEvent(
        t=np.asarray(trace.t, np.float32),
        func_id=fid,
        size=size,
        cls=np.asarray(trace.cls, np.int32),
        warm=np.asarray(trace.warm_dur, np.float32),
        cold=np.asarray(trace.cold_dur, np.float32),
        h1=h1, h2=h2,
        used=observed_usage(np, fid, size) if resize else None)


def cluster_events(trace: Trace, n_nodes: int, *,
                   resize: bool = False) -> ClusterEvent:
    """:func:`_host_events` as device arrays."""
    return jax.tree_util.tree_map(
        jnp.asarray, _host_events(trace, n_nodes, resize=resize))


def init_cluster(cfg: ClusterConfig) -> PoolState:
    """Stack all 2N pools of the cluster on a leading axis."""
    caps = cfg.pool_caps()
    states = [init_pool(PoolConfig(caps[n, k], cfg.policy, cfg.max_slots,
                                   resize_policy=cfg.resize_policy,
                                   resize_min_mb=cfg.resize_min_mb))
              for n in range(cfg.n_nodes) for k in range(2)]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


@jax.named_scope("step.route")
def _route(routing: jax.Array, ev: ClusterEvent, free_t: jax.Array,
           cap_t: jax.Array, cloud: jax.Array, node_up: jax.Array,
           chain_slack: jax.Array, chain_stage: jax.Array) -> jax.Array:
    """The in-scan routing decision: a ``lax.switch`` over every policy in
    the routing registry (same pure functions the numpy oracle dispatches),
    indexed by the ``routing`` code carried as data."""
    ctx = RouteCtx(h1=ev.h1, h2=ev.h2, size=ev.size, cls=ev.cls,
                   warm=ev.warm, cold=ev.cold, free=free_t, cap=cap_t,
                   cloud_rtt_s=cloud[0], cloud_cold_prob=cloud[1],
                   node_up=node_up, chain_slack=chain_slack,
                   chain_stage=chain_stage)
    branches = [
        (lambda _, fn=spec.fn: jnp.asarray(fn(jnp, ctx)).astype(jnp.int32))
        for spec in ROUTING.specs()
    ]
    return jax.lax.switch(routing, branches, None)


def _invalidate_nodes(pools: PoolState, mask_n: jax.Array, n_nodes: int):
    """Kill every resident of the masked nodes (failure recovery / node
    retirement): pools restart empty at their current capacity with a
    reset GreedyDual clock — ``WarmPool.invalidate`` is the sequential
    twin.  Returns ``(count i32[N] residents killed, cleared pools)``."""
    cnt2 = jnp.sum(pools.valid, axis=-1).astype(jnp.int32)       # i32[2N]
    cnt = jnp.where(mask_n, cnt2.reshape(n_nodes, 2).sum(axis=1), 0)
    m2 = jnp.repeat(mask_n, 2)                                   # bool[2N]
    extra = {}
    if pools.alloc is not None:
        # the residents' limits/usage die with them; the run-total
        # accumulators (acc_used/acc_alloc/bneck) persist, like the
        # oracle's ``WarmPool.invalidate``
        extra = dict(
            alloc=jnp.where(m2[:, None], jnp.float32(0.0), pools.alloc),
            used=jnp.where(m2[:, None], jnp.float32(0.0), pools.used))
    pools = pools._replace(
        valid=jnp.where(m2[:, None], False, pools.valid),
        func_id=jnp.where(m2[:, None], jnp.int32(-1), pools.func_id),
        free=jnp.where(m2, pools.capacity, pools.free),
        clock=jnp.where(m2, jnp.float32(0.0), pools.clock), **extra)
    return cnt, pools


# --------------------------------------------------------------------------
# in-scan telemetry: windowed counters riding the scan carry
# --------------------------------------------------------------------------
# ``repro.sim.telemetry`` documents the user-facing contract; the engine
# pieces here keep the accumulator a fixed-shape pytree so it rides the
# carry of either program shape and vmaps across sweep lanes.  Window
# indices are *global* event indices computed host-side
# (``i // window_events``) and carried into the scan as data, so a run in
# chunks scatters into the same windows as a whole-trace one for ANY
# chunk size, dividing the window or not.  Row ``n_windows`` is a junk
# row that absorbs pad events (epoch / chunk padding) and is sliced off
# host-side by ``_tel_np``.

class TelAcc(NamedTuple):
    """The in-carry windowed accumulator (one junk row past the end)."""

    counts: jax.Array   # i32[W+1, 2, 3] invocations per (cls, outcome)
    free: jax.Array     # f32[W+1, N] free MB per node at window end
    occ: jax.Array      # i32[W+1, N] resident containers at window end
    inval: jax.Array    # i32[W+1] residents invalidated in the window
    up: jax.Array       # i32[W+1] failure-up node count at window end
    active: jax.Array   # i32[W+1] autoscale-active count at window end
    cmiss: jax.Array    # i32[W+1] chain deadline misses in the window


def _n_windows(n_events: int, window: int) -> int:
    return -(-n_events // window)


def _tel_init(n_windows: int, n_nodes: int, lanes: tuple = ()) -> TelAcc:
    """A zeroed accumulator; ``lanes=(L,)`` stacks one per sweep lane."""
    w = (*lanes, n_windows + 1)
    return TelAcc(counts=jnp.zeros((*w, 2, 3), jnp.int32),
                  free=jnp.zeros((*w, n_nodes), jnp.float32),
                  occ=jnp.zeros((*w, n_nodes), jnp.int32),
                  inval=jnp.zeros(w, jnp.int32),
                  up=jnp.zeros(w, jnp.int32),
                  active=jnp.zeros(w, jnp.int32),
                  cmiss=jnp.zeros(w, jnp.int32))


@jax.named_scope("step.acc")
def _tel_event(tel: TelAcc, wi: jax.Array, ev: ClusterEvent,
               outcome: jax.Array, pools: PoolState, n_nodes: int,
               up_cnt: jax.Array, act_cnt: jax.Array,
               inval_cnt: jax.Array, miss_cnt: jax.Array) -> TelAcc:
    """Fold one stepped event into its window: counter columns scatter-
    add, snapshot columns last-write-win (each window reports the state
    after its final event) — mirrored step for step, through f32 for
    ``free``, by the oracle in ``core/continuum.py``.  ``miss_cnt`` is
    the event's chain deadline-miss flag (0/1; always 0 off-chains)."""
    free_n = pools.free.reshape(n_nodes, 2).sum(axis=1)
    occ_n = (jnp.sum(pools.valid, axis=-1).astype(jnp.int32)
             .reshape(n_nodes, 2).sum(axis=1))
    return TelAcc(
        counts=tel.counts.at[wi, ev.cls, outcome].add(1),
        free=tel.free.at[wi].set(free_n),
        occ=tel.occ.at[wi].set(occ_n),
        inval=tel.inval.at[wi].add(inval_cnt),
        up=tel.up.at[wi].set(up_cnt),
        active=tel.active.at[wi].set(act_cnt),
        cmiss=tel.cmiss.at[wi].add(miss_cnt))


def _tel_np(tel: TelAcc, n_windows: int) -> dict:
    """Host-side view: junk row sliced off, counters widened to i64."""
    return {
        "counts": np.asarray(tel.counts, np.int64)[:n_windows],
        "free_mb": np.asarray(tel.free)[:n_windows],
        "occupancy": np.asarray(tel.occ, np.int64)[:n_windows],
        "invalidated": np.asarray(tel.inval, np.int64)[:n_windows],
        "nodes_up": np.asarray(tel.up, np.int64)[:n_windows],
        "nodes_active": np.asarray(tel.active, np.int64)[:n_windows],
        "chain_miss": np.asarray(tel.cmiss, np.int64)[:n_windows]}


# --------------------------------------------------------------------------
# in-scan chain accounting: per-chain end-to-end state riding the carry
# --------------------------------------------------------------------------
# ``core.continuum.compile_chains`` turns a chained trace into a
# ``ChainPlan`` host-side; the engine carries one f32 latency row per
# chain (+ the junk row ``n_chains`` that absorbs pad events, exactly
# like the telemetry junk window) through either program shape, and the
# oracle mirrors each update through float32 in the same event order, so
# the two engines' chain latencies and deadline-miss flags are
# bit-identical by construction.  The plan's per-event arrays ride as
# ``xs`` data shared across sweep lanes; the per-chain deadline vector
# and the cloud cold draws are per-lane data (lanes differ in Chains
# config / cloud_cold_prob).

class ChainXs(NamedTuple):
    """Per-event chain scan data (host-compiled, shared across lanes)."""

    cid: jax.Array    # i32[T] dense chain row (junk row for pad events)
    stage: jax.Array  # i32[T] 0-based stage (-1 pad)
    last: jax.Array   # bool[T] event is its chain's final stage


class ChainAcc(NamedTuple):
    """The in-carry per-chain accumulator (one junk row past the end)."""

    lat: jax.Array      # f32[C+1] accumulated end-to-end latency
    dropped: jax.Array  # bool[C+1] any stage dropped so far
    done: jax.Array     # bool[C+1] final stage observed
    missed: jax.Array   # bool[C+1] deadline missed (judged at last stage)


def _chain_init(n_chains: int, lanes: tuple = ()) -> ChainAcc:
    """A zeroed accumulator; ``lanes=(L,)`` stacks one per sweep lane."""
    c = (*lanes, n_chains + 1)
    return ChainAcc(lat=jnp.zeros(c, jnp.float32),
                    dropped=jnp.zeros(c, bool),
                    done=jnp.zeros(c, bool),
                    missed=jnp.zeros(c, bool))


def _chain_pre(chain: ChainAcc, cdl: jax.Array, cx: ChainXs):
    """Pre-step chain view for routing: (remaining slack f32, stage i32).
    A no-deadline chain has ``cdl = +inf`` so its slack is ``+inf``."""
    return cdl[cx.cid] - chain.lat[cx.cid], cx.stage


@jax.named_scope("step.acc")
def _chain_event(chain: ChainAcc, cx: ChainXs, ccold: jax.Array,
                 cdl: jax.Array, ev: ClusterEvent, outcome: jax.Array,
                 cloud: jax.Array):
    """Fold one stepped event into its chain row: price the stage like
    ``continuum_latencies`` (hit -> warm, miss -> cold, drop -> RTT +
    cloud with the pre-drawn ``ccold`` flip), accumulate in f32, and at
    the chain's final stage judge the deadline — a dropped stage misses
    regardless of time.  Returns ``(chain, miss i32)`` so telemetry can
    window the miss.  Pad events land in the junk row with
    ``last=False`` and can never flag a miss."""
    stage_lat = jnp.where(
        outcome == HIT, ev.warm,
        jnp.where(outcome == MISS, ev.cold,
                  cloud[0] + jnp.where(ccold, ev.cold, ev.warm)))
    final = chain.lat[cx.cid] + stage_lat
    new_dropped = chain.dropped[cx.cid] | (outcome == DROP)
    miss = cx.last & (new_dropped | (final > cdl[cx.cid]))
    # the bool rows are updated by select (``pool_jax.put``), not scatter
    return ChainAcc(
        lat=chain.lat.at[cx.cid].set(final),
        dropped=put(chain.dropped, cx.cid, new_dropped),
        done=put(chain.done, cx.cid, chain.done[cx.cid] | cx.last),
        missed=put(chain.missed, cx.cid, chain.missed[cx.cid] | miss)
    ), miss.astype(jnp.int32)


def _chain_np(chain: ChainAcc, n_chains: int) -> dict:
    """Host-side view: junk row sliced off (the oracle's ``chain_np``
    twin — bit-identical arrays)."""
    return {"latency": np.asarray(chain.lat)[:n_chains],
            "dropped": np.asarray(chain.dropped)[:n_chains],
            "done": np.asarray(chain.done)[:n_chains],
            "missed": np.asarray(chain.missed)[:n_chains]}


def _chain_xs(plan: ChainPlan) -> ChainXs:
    """The plan's per-event arrays, host-side."""
    return ChainXs(cid=np.asarray(plan.cid, np.int32),
                   stage=np.asarray(plan.stage, np.int32),
                   last=np.asarray(plan.last, bool))


def _make_step(routing: jax.Array, unified: jax.Array, cloud: jax.Array,
               n_nodes: int, mode: str):
    """Build the per-event step (route, then step the routed pool) that
    the event body of both program shapes calls.  ``up_n`` (bool[N],
    optional) is the live-node mask: routing policies read it via
    ``RouteCtx.node_up`` and a request still routed to a down node drops
    to the cloud without touching any pool (down pools are frozen).
    ``cslack``/``cstage`` (optional f32/i32 scalars) are the event's chain
    slack and stage for ``RouteCtx`` — constants ``+inf``/``-1`` when
    chains are off, so slack-aware policies degrade to their slack-rich
    branch."""
    n = n_nodes
    tree = jax.tree_util.tree_map
    all_up = jnp.ones((n,), bool)
    no_slack, no_stage = jnp.float32(jnp.inf), jnp.int32(-1)
    # any mode beyond the two built-in formulations is a step backend
    # (resolved once, at step-build time — unknown names fail fast here)
    backend = (get_step_backend(mode)
               if mode not in ("gather", "vmap") else None)

    def step(pools, ev, up_n=None, cslack=None, cstage=None):
        free2 = pools.free.reshape(n, 2)
        cap2 = pools.capacity.reshape(n, 2)
        tgt = jnp.where(unified, 0, ev.cls)          # i32[N] pool per node
        lanes = jnp.arange(n)
        node = _route(routing, ev, free2[lanes, tgt], cap2[lanes, tgt],
                      cloud, all_up if up_n is None else up_n,
                      no_slack if cslack is None else cslack,
                      no_stage if cstage is None else cstage)
        ok = jnp.bool_(True) if up_n is None else up_n[node]
        p = node * 2 + tgt[node]
        core_ev = Event(ev.t, ev.func_id, ev.size, ev.cls, ev.warm, ev.cold,
                        ev.used)
        with jax.named_scope("pool.step"):
            if mode == "gather":
                stepped, outcome = pool_step(tree(lambda a: a[p], pools),
                                             core_ev)
            else:
                # step every pool, keep only the routed one: "vmap"
                # batches the per-pool step, any other mode is a
                # registered step backend driving the batched
                # pool_step_batch (the "fused" Pallas kernel being the
                # first)
                if mode == "vmap":
                    stepped, outs = jax.vmap(pool_step, in_axes=(0, None))(
                        pools, core_ev)
                else:
                    stepped, outs = pool_step_batch(pools, core_ev, backend)
                outcome = outs[p]
        # write the routed pool back by select, not scatter (see
        # ``pool_jax.put``); a request to a down node changes nothing
        with jax.named_scope("step.writeback"):
            sel = (jnp.arange(2 * n) == p) & ok
            pools = tree(
                lambda a, b: jnp.where(
                    sel.reshape((-1,) + (1,) * (a.ndim - 1)), b, a),
                pools, stepped)
        outcome = jnp.where(ok, outcome, DROP)
        return pools, (node, outcome)

    return step


# --------------------------------------------------------------------------
# the event body and the two program shapes that scan it
# --------------------------------------------------------------------------

class Carry(NamedTuple):
    """The scan carry of both program shapes.  A field whose mechanism is
    off is ``None`` and vanishes from the pytree, so a run without
    failures, telemetry or chains carries the pool state alone."""

    pools: PoolState
    inval: jax.Array | None = None   # i32[N] residents invalidated
    tel: TelAcc | None = None
    chain: ChainAcc | None = None


class EventXs(NamedTuple):
    """One event's scan inputs (stacked on a leading time axis in the
    programs); a field whose mechanism is off is ``None``."""

    ev: ClusterEvent
    up: jax.Array | None = None      # bool[N] nodes up (failures)
    rec: jax.Array | None = None     # bool[N] nodes recovering here
    widx: jax.Array | None = None    # i32 global telemetry window
    cxs: ChainXs | None = None       # chain row, stage, last flag
    ccold: jax.Array | None = None   # bool cloud cold flip (chains)


#: vmap in_axes of :class:`EventXs` in the lane-stacked programs: the
#: trace (events, window indices, chain structure) is shared by the lanes,
#: while failure masks and cloud cold draws are each lane's own.  A field
#: that is ``None`` in the data has no leaves, so its entry is inert.
_XS_LANES = EventXs(ev=None, up=0, rec=0, widx=None, cxs=None, ccold=0)


def _make_event(routing: jax.Array, unified: jax.Array, cloud: jax.Array,
                cdl: jax.Array | None, n_nodes: int, mode: str):
    """Build the one event body both program shapes scan.  In order it
    clears the nodes recovering at the event (failure masks only), routes
    and steps (:func:`_make_step`), then folds the outcome into the chain
    accumulator and the telemetry window, each only when on.  ``up_n`` is
    the live-node mask routing sees (``None`` = all up, which compiles no
    mask and no select) and ``n_active`` the membership count telemetry
    records; the epoch grid passes ``up & active`` and its live count."""
    step = _make_step(routing, unified, cloud, n_nodes, mode)

    def event(c: Carry, x: EventXs, up_n, n_active):
        pools, inval, cnt = c.pools, c.inval, None
        if x.rec is not None:
            cnt, pools = _invalidate_nodes(pools, x.rec, n_nodes)
            inval = inval + cnt
        chain, slack, stage, miss = c.chain, None, None, jnp.int32(0)
        if chain is not None:
            slack, stage = _chain_pre(chain, cdl, x.cxs)
        pools, (node, outcome) = step(pools, x.ev, up_n, slack, stage)
        if chain is not None:
            chain, miss = _chain_event(chain, x.cxs, x.ccold, cdl, x.ev,
                                       outcome, cloud)
        tel = c.tel
        if tel is not None:
            tel = _tel_event(
                tel, x.widx, x.ev, outcome, pools, n_nodes,
                (jnp.int32(n_nodes) if x.up is None
                 else jnp.sum(x.up).astype(jnp.int32)), n_active,
                jnp.int32(0) if cnt is None else jnp.sum(cnt), miss)
        return Carry(pools, inval, tel, chain), (node, outcome)

    return event


def _chunk_impl(carry: Carry, xs: EventXs, routing: jax.Array,
                unified: jax.Array, cloud: jax.Array, cdl=None, *,
                n_nodes: int, mode: str):
    """The chunk program: the event body scanned over one chunk of
    events.  Returns ``(carry, node i32[chunk], outcome i32[chunk])``;
    the next chunk picks the carry up, and global window indices and chain
    rows make events land where a whole-trace run would put them.
    ``cdl`` is the chain deadline vector (chains only)."""
    event = _make_event(routing, unified, cloud, cdl, n_nodes, mode)
    n_up = jnp.int32(n_nodes)
    carry, (nodes, outcomes) = jax.lax.scan(
        lambda c, x: event(c, x, x.up, n_up), carry, xs)
    return carry, nodes, outcomes


def _epoch_impl(carry: Carry, xs: EventXs, valid: jax.Array,
                routing: jax.Array, unified: jax.Array, cloud: jax.Array,
                frac: jax.Array, node_mb: jax.Array, asc: jax.Array,
                active0: jax.Array, cdl=None, *, n_nodes: int, mode: str):
    """The epoch grid: an outer scan over epochs, the event body inside
    each epoch, and a per-node re-split plus a node spawn/retire decision
    between epochs.

    ``xs`` leaves are shaped ``[E, epoch_events, ...]`` (trace padded
    with guaranteed-drop no-ops); ``valid`` is f32[E, e] marking real
    events.  Pad events never touch pool state (a drop is a no-op
    transition) and are masked out of the pressure signal here — the
    padding bias that skewed the legacy ``core.adaptive`` split decision
    cannot arise.  Without failure masks (``xs.up`` None) the program
    holds no per-event invalidation work: routing sees the membership
    alone.  ``carry.inval`` counts recovery and retirement invalidations.
    ``frac`` is the starting f32[N] small-pool fraction, ``asc`` packs
    (min_frac, max_frac, gain, spawn_drop_frac, retire_drop_frac) as data
    so sweeps can vmap over them (+/-inf thresholds = node scaling off),
    and ``active0`` (bool[N]) is the starting membership.  Returns
    ``(carry, node i32[E, e], outcome i32[E, e], fracs f32[E, N],
    actives bool[E, N])``.  Retirement invalidations land in the epoch's
    last real telemetry window, recovery invalidations in the window of
    the event that observed them; pad events land in the chain junk row.
    """
    event = _make_event(routing, unified, cloud, cdl, n_nodes, mode)
    tree = jax.tree_util.tree_map
    n = n_nodes
    mn, mx, gain, spawn_th, retire_th = (asc[0], asc[1], asc[2], asc[3],
                                         asc[4])
    pool_unified = jnp.repeat(unified, 2)            # bool[2N]

    def epoch(state, inp):
        c, frac, active = state
        x, val = inp
        n_active = jnp.sum(active.astype(jnp.int32))

        def inner(ic, iv):
            c, press, dropw = ic
            x, v = iv
            c, (node, outcome) = event(
                c, x, active if x.up is None else x.up & active, n_active)
            # pressure = misses + 2x drops, per (routed node, size class);
            # pad events carry v == 0 and contribute nothing
            w = v * jnp.where(outcome == MISS, 1.0,
                              jnp.where(outcome == DROP, 2.0, 0.0))
            press = press.at[node, x.ev.cls].add(w)
            dropw = dropw + v * jnp.where(outcome == DROP, 1.0, 0.0)
            return (c, press, dropw), (node, outcome)

        (c, press, dropw), (nodes, outcomes) = jax.lax.scan(
            inner, (c, jnp.zeros((n, 2), jnp.float32), jnp.float32(0.0)),
            (x, val))
        pools = c.pools
        press_s, press_l = press[:, 0], press[:, 1]
        tot = press_s + press_l
        delta = jnp.where(tot > 0,
                          ieee_div(gain * (press_s - press_l),
                                   jnp.where(tot > 0, tot, jnp.float32(1.0))),
                          jnp.float32(0.0))
        # a trailing partial epoch (pad suffix ⇒ last event invalid) never
        # completes: no re-split, the frac row just repeats
        is_full = val[-1] > 0
        cand = jnp.minimum(mx, jnp.maximum(frac + delta, mn))
        new_frac = jnp.where(is_full & ~unified, cand, frac)
        now = jnp.max(jnp.where(val > 0, x.ev.t, -jnp.inf))
        caps = jnp.stack([node_mb * new_frac,
                          node_mb * (jnp.float32(1.0) - new_frac)],
                         axis=1).reshape(-1)
        resized = jax.vmap(pool_resize, in_axes=(0, None, 0))(
            pools, now, caps)
        keep = is_full & ~pool_unified                # bool[2N]
        pools = tree(
            lambda r, o: jnp.where(
                keep.reshape((-1,) + (1,) * (r.ndim - 1)), r, o),
            resized, pools)
        # node add/remove from the cluster-wide drop fraction (post-resize
        # residency decides "emptiest"; at most one node moves per epoch)
        drop_frac = ieee_div(dropw,
                             jnp.maximum(jnp.sum(val), jnp.float32(1.0)))
        can_spawn = is_full & (drop_frac > spawn_th) & (n_active < n)
        can_retire = (is_full & ~can_spawn & (drop_frac < retire_th)
                      & (n_active > 1))
        used_n = (pools.capacity - pools.free).reshape(n, 2).sum(axis=1)
        cand_spawn = jnp.argmax(~active)
        cand_retire = jnp.argmin(
            jnp.where(active, used_n, jnp.float32(jnp.inf)))
        new_active = jnp.where(
            can_spawn, put(active, cand_spawn, True),
            jnp.where(can_retire, put(active, cand_retire, False), active))
        retire_mask = (jnp.arange(n) == cand_retire) & can_retire
        cnt, pools = _invalidate_nodes(pools, retire_mask, n)
        tel = c.tel
        if tel is not None:
            # retirement invalidations belong to the epoch's last real
            # window (retirement only fires on full epochs, so w_end is
            # always a real index there)
            w_end = jnp.max(jnp.where(val > 0, x.widx, -1))
            tel = tel._replace(inval=tel.inval.at[w_end].add(jnp.sum(cnt)))
        c = c._replace(pools=pools, inval=c.inval + cnt, tel=tel)
        return (c, new_frac, new_active), (nodes, outcomes, new_frac,
                                           new_active)

    (carry, _, _), (nodes, outcomes, fracs, actives) = jax.lax.scan(
        epoch, (carry, frac, active0), (xs, valid))
    return carry, nodes, outcomes, fracs, actives


#: vmap in_axes of the lane-stacked programs, argument by argument: each
#: lane has its own carry and config (routing, unified, cloud, autoscale
#: data, deadlines); the epoch validity mask is shared.
_CHUNK_LANES = (0, _XS_LANES, 0, 0, 0, 0)
_EPOCH_LANES = (0, _XS_LANES, None, 0, 0, 0, 0, 0, 0, 0, 0)


def _jit(fn, axes=None, devices: int | None = None):
    """``fn`` jitted with its carry (argument 0) donated, so a run's pool
    buffers are reused in place; with lane ``axes``, vmapped over a
    leading lane axis first and, with ``devices``, sharded across them."""
    if axes is not None:
        fn = _shard_lanes(jax.vmap(fn, in_axes=axes), axes, devices)
    return jax.jit(fn, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _chunk_runner(n_nodes: int, mode: str):
    """The single-lane chunk program.  It stays unbatched: a batched
    ``lax.cond`` computes both branches, an unbatched one only the taken
    branch."""
    return _jit(functools.partial(_chunk_impl, n_nodes=n_nodes, mode=mode))


@functools.lru_cache(maxsize=None)
def _sweep_chunk_runner(n_nodes: int, mode: str, devices: int | None):
    """The lane-stacked chunk program (``devices`` shards the lanes; the
    donated carry then lives sharded and is reused shard in place)."""
    return _jit(functools.partial(_chunk_impl, n_nodes=n_nodes, mode=mode),
                _CHUNK_LANES, devices)


@functools.lru_cache(maxsize=None)
def _epoch_runner(n_nodes: int, mode: str):
    """The single-lane epoch grid."""
    return _jit(functools.partial(_epoch_impl, n_nodes=n_nodes, mode=mode))


@functools.lru_cache(maxsize=None)
def _sweep_epoch_runner(n_nodes: int, mode: str, devices: int | None):
    """The lane-stacked epoch grid."""
    return _jit(functools.partial(_epoch_impl, n_nodes=n_nodes, mode=mode),
                _EPOCH_LANES, devices)


# --------------------------------------------------------------------------
# device-mesh sharded sweeps: lanes split across jax.devices()
# --------------------------------------------------------------------------
# ``sweep(..., devices=k)`` splits the stacked lane axis of each shape
# bucket across a 1-D device mesh with shard_map: every device runs the
# SAME vmapped scan on its shard of lanes, so per-lane arithmetic — and
# hence every per-lane output — is bit-identical to the unsharded run (no
# cross-lane reductions exist anywhere in the sweep path).  The in_specs
# mirror the program's vmap in_axes one-for-one (lane-stacked args split,
# shared args replicate; both use the same pytree-prefix rule), and a
# non-dividing lane count is padded with duplicates of lane 0 — the lane
# analogue of the guaranteed-drop no-op pad events of the epoch grid:
# the pad lanes run real (discarded) work and are sliced off before
# ``Result`` assembly.  ``devices=None`` skips shard_map entirely, so the
# single-device programs stay byte-identical to the pre-sharding ones.

def _lane_mesh(devices: int) -> Mesh:
    """A 1-D mesh over the first ``devices`` JAX devices; on CPU,
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before
    the first jax import) turns cores into mesh devices."""
    return Mesh(np.asarray(jax.devices()[:devices]), ("lanes",))


def _lane_specs(axes):
    """shard_map in_specs mirroring a vmap in_axes tree: lane-stacked
    args (axis 0) split across the mesh, shared args replicate."""
    return jax.tree_util.tree_map(
        lambda a: PartitionSpec("lanes") if a == 0 else PartitionSpec(),
        axes, is_leaf=lambda a: a is None)


def _shard_lanes(fn, axes, devices: int | None):
    """Wrap a vmapped program in shard_map over the lane axis (every
    output of either program is lane-stacked, hence the blanket
    out_specs).  A no-op when ``devices`` is None.  ``check_vma=False``
    because pallas_call (``mode="fused"``) has no replication rule —
    harmless here since no output is replicated."""
    if devices is None:
        return fn
    return jax.shard_map(fn, mesh=_lane_mesh(devices),
                         in_specs=_lane_specs(axes),
                         out_specs=PartitionSpec("lanes"),
                         check_vma=False)


def _lanes_np(x: jax.Array, placement: dict | None) -> np.ndarray:
    """Host copy of a lane-stacked output.  With a ``placement`` dict
    (sharded sweeps), first record how many lane rows, pad lanes
    included, each device holds — read from the output's shards, so it
    says where the lanes really ran."""
    if placement is not None:
        placement.update({str(sh.device): int(sh.data.shape[0])
                          for sh in x.addressable_shards})
    return np.asarray(x)


def _lane_pad(lanes: int, devices: int | None) -> int:
    """Pad lanes needed to make ``lanes`` divisible by the mesh size."""
    return 0 if devices is None else (-lanes) % devices


def _pad_tree(tree, pad: int):
    """Append ``pad`` copies of lane 0 along the leading axis of every
    leaf, host or device (zeros stay zeros for accumulators; real configs
    just duplicate — their outputs are never read)."""
    if not pad:
        return tree

    def dup(a):
        xp = np if isinstance(a, np.ndarray) else jnp
        return xp.concatenate([a, xp.repeat(a[:1], pad, axis=0)])
    return jax.tree_util.tree_map(dup, tree)


# --------------------------------------------------------------------------
# host side: inputs, padding, the chunk loop, results
# --------------------------------------------------------------------------

def _host_xs(trace: Trace, n_nodes: int, drop_size: float, *,
             resize: bool = False, masks=None, telemetry: int | None = None,
             chains: ChainPlan | None = None, ccold=None):
    """The whole trace's per-event inputs on the host, for the chunk loop
    to slice or the epoch grid to reshape: the events, and only for the
    mechanisms that are on the failure ``masks`` (up, recover), the
    global telemetry window indices, and the chain structure with its
    cloud cold draws ``ccold`` (``masks`` and ``ccold`` carry a leading
    lane axis in sweeps).  Returns them with what each pads with past the
    end of the trace: a guaranteed-drop no-op event (an impossible
    function id and a size no pool can host, ``drop_size``, so no pool
    state changes), every node up and none recovering, the junk telemetry
    window and chain row, and no cold flip."""
    ev = _host_events(trace, n_nodes, resize=resize)
    xs = EventXs(
        ev=ev,
        up=None if masks is None else masks[0],
        rec=None if masks is None else masks[1],
        widx=(None if telemetry is None
              else np.arange(len(trace), dtype=np.int32) // telemetry),
        cxs=None if chains is None else _chain_xs(chains),
        ccold=None if chains is None else ccold)
    fills = EventXs(
        ev=ClusterEvent(t=ev.t[-1] if len(ev.t) else 0.0, func_id=-2,
                        size=drop_size, cls=0, warm=0.0, cold=0.0, h1=0,
                        h2=0, used=None if ev.used is None else 0.0),
        up=True, rec=False,
        widx=None if telemetry is None else _n_windows(len(trace),
                                                       telemetry),
        cxs=ChainXs(cid=None if chains is None else chains.n_chains,
                    stage=-1, last=False), ccold=False)
    return xs, EventXs(*(None if a is None else f
                         for a, f in zip(xs, fills)))


def _pad_xs(xs: EventXs, pad: int) -> EventXs:
    """A sweep's inputs with ``pad`` more lanes in the per-lane fields."""
    return EventXs(*(a if ax is None else _pad_tree(a, pad)
                     for a, ax in zip(xs, _XS_LANES)))


def _per_field(fn, xs: EventXs, lanes: bool, *rest) -> EventXs:
    """``fn(array, *rest_arrays, axis=t)`` over every array of ``xs``, ``t``
    being its time axis: 1 for a sweep's lane-stacked fields, else 0."""
    return EventXs(*(
        None if a is None else jax.tree_util.tree_map(
            functools.partial(fn, axis=int(lanes and ax == 0)), a, *r)
        for a, ax, *r in zip(xs, _XS_LANES, *rest)))


def _xs_slice(xs: EventXs, fills: EventXs, s: int, e: int, length: int,
              lanes: bool = False) -> EventXs:
    """Events ``[s, e)`` of the host inputs, padded with ``fills`` to
    ``length`` events."""
    def cut(a, fill, axis):
        sl = a[(slice(None),) * axis + (slice(s, e),)]
        pad = length - (e - s)
        if not pad:
            return sl
        shape = list(sl.shape)
        shape[axis] = pad
        return np.concatenate([sl, np.full(shape, fill, a.dtype)],
                              axis=axis)
    return _per_field(cut, xs, lanes, fills)


def _xs_grid(xs: EventXs, fills: EventXs, n_events: int,
             epoch_events: int, lanes: bool = False):
    """The host inputs padded to whole epochs and reshaped to [E, e] on
    the device, with the f32[E, e] mask of real events (which doubles as
    the pressure weight)."""
    e = epoch_events
    n_ep = -(-n_events // e)

    def split(a, axis):
        return jnp.asarray(
            a.reshape(a.shape[:axis] + (n_ep, e) + a.shape[axis + 1:]))
    grid = _per_field(split, _xs_slice(xs, fills, 0, n_events, n_ep * e,
                                       lanes), lanes)
    valid = np.arange(n_ep * e) < n_events
    return grid, jnp.asarray(valid.reshape(n_ep, e), jnp.float32)


def _carry0(pools: PoolState, n_nodes: int, inval: bool,
            n_windows: int | None, plan: ChainPlan | None,
            lanes: tuple = ()) -> Carry:
    """The carry a run starts from: ``pools`` and zeroed accumulators for
    the mechanisms that are on (``lanes=(L,)`` stacks one per lane)."""
    return Carry(
        pools,
        inval=jnp.zeros((*lanes, n_nodes), jnp.int32) if inval else None,
        tel=None if n_windows is None else _tel_init(n_windows, n_nodes,
                                                     lanes),
        chain=None if plan is None else _chain_init(plan.n_chains, lanes))


def _chunk_loop(run, carry: Carry, xs: EventXs, fills: EventXs,
                data: tuple, t_len: int, chunk: int,
                lanes: int | None = None, placement: dict | None = None):
    """Run the host inputs ``xs`` through the chunk program ``run``
    ``chunk`` events at a time, threading the donated carry, under the
    host-loop spans: per chunk a ``sim.chunk`` holding ``sim.slice``,
    ``sim.dispatch``, ``sim.wait`` and ``sim.fetch``.  ``data`` is the
    per-lane config the program takes after the inputs; ``lanes`` is the
    real lane count of a lane-stacked run (None = single lane).  Returns
    the final carry and the node and outcome arrays, [T] or [lanes, T]."""
    shape = (t_len,) if lanes is None else (lanes, t_len)
    nodes_out = np.empty(shape, np.int32)
    outcomes_out = np.empty(shape, np.int32)
    for i, s in enumerate(range(0, t_len, chunk)):
        e = min(s + chunk, t_len)
        with TraceAnnotation("sim.chunk", index=i, events=e - s,
                             pad=chunk - (e - s)):
            with TraceAnnotation("sim.slice"):
                x = _xs_slice(xs, fills, s, e, chunk, lanes is not None)
            with TraceAnnotation("sim.dispatch",
                                 h2d_bytes=_host_nbytes(x)):
                carry, nodes, outcomes = run(carry, x, *data)
            with TraceAnnotation("sim.wait"):
                jax.block_until_ready((nodes, outcomes))
            if lanes is None:
                with TraceAnnotation("sim.fetch",
                                     d2h_bytes=2 * nodes_out[s:e].nbytes):
                    nodes_out[s:e] = np.asarray(nodes[:e - s])
                    outcomes_out[s:e] = np.asarray(outcomes[:e - s])
            else:
                with TraceAnnotation(
                        "sim.fetch", d2h_bytes=nodes.nbytes + outcomes.nbytes):
                    nodes_out[:, s:e] = _lanes_np(nodes, placement)[
                        :lanes, :e - s]
                    outcomes_out[:, s:e] = np.asarray(outcomes)[:lanes,
                                                                :e - s]
    return carry, nodes_out, outcomes_out


def _carry_np(c: Carry) -> tuple:
    """Host copy of what a run reports from its final carry: the pools'
    vertical-scaling run totals (resize only), the invalidation counts,
    and the telemetry and chain accumulators — ``None`` where off."""
    vert = (None if c.pools.alloc is None
            else (c.pools.acc_used, c.pools.acc_alloc, c.pools.bneck))
    return jax.device_get((vert, c.inval, c.tel, c.chain))


def _extras(host: tuple, n_windows: int | None,
            plan: ChainPlan | None) -> dict:
    """One lane's extras from its :func:`_carry_np` fields."""
    vert, inval, tel, chain = host
    extras = {}
    if tel is not None:
        extras["telemetry"] = _tel_np(tel, n_windows)
    if chain is not None:
        extras["chains"] = _chain_np(chain, plan.n_chains)
    if vert is not None:
        # the stacked node-major [2N] layout — the JAX twin of the
        # oracle's ``_vertical()`` extras
        acc_used, acc_alloc, bneck = vert
        extras["vertical"] = {
            "acc_used_mb": np.asarray(acc_used, np.float32),
            "acc_alloc_mb": np.asarray(acc_alloc, np.float32),
            "bottlenecks": np.asarray(bneck, np.int64)}
    if inval is not None:
        extras["invalidated"] = np.asarray(inval, np.int64)
    return extras


def _lane(tree, g: int):
    """Lane ``g`` of a lane-stacked host tree."""
    return jax.tree_util.tree_map(lambda a: a[g], tree)


def _autoscale_inputs(cfg: ClusterConfig, asc: Autoscale):
    """The per-config data the epoch grid consumes beyond the chunk
    program's (routing, unified, cloud): initial fracs, node capacities,
    the (min_frac, max_frac, gain, spawn, retire) vector (+/-inf
    thresholds encode "node scaling off" — the decision arithmetic runs
    identically and never fires), and the initial membership — all
    vmappable data."""
    n = cfg.n_nodes
    spawn = asc.spawn_drop_frac if asc.node_scaled else np.inf
    retire = asc.retire_drop_frac if asc.node_scaled else -np.inf
    k = asc.init_active if asc.init_active is not None else n
    return (jnp.asarray(cfg.small_frac, jnp.float32),
            jnp.asarray(cfg.node_mb, jnp.float32),
            jnp.asarray([asc.min_frac, asc.max_frac, asc.gain,
                         spawn, retire], jnp.float32),
            jnp.asarray(np.arange(n) < k, bool))


def _failure_masks(failures: Failures | None, trace: Trace, n_nodes: int):
    """Per-event up/recover bool[T, N] masks — all-up/none when the
    scenario has no failure schedule (the masked scan is arithmetic-
    identical to the unmasked one on an all-up mask)."""
    if failures is None:
        t = len(trace)
        return (np.ones((t, n_nodes), bool),
                np.zeros((t, n_nodes), bool))
    return failures.masks(np.asarray(trace.t), n_nodes)


def _lane_masks(failures, trace: Trace, n_nodes: int, lanes: int,
                what: str):
    """A sweep's lane-stacked (up, recover) bool[L, T, N] masks from one
    ``Failures`` (or None) per config; ``None`` when ``failures`` is."""
    if failures is None:
        return None
    failures = list(failures)
    if len(failures) != lanes:
        raise ValueError(f"{what}: need one Failures (or None) per config")
    masks = [_failure_masks(f, trace, n_nodes) for f in failures]
    return (np.stack([m[0] for m in masks]), np.stack([m[1] for m in masks]))


def _lane_plan(chains, lanes: int):
    """The chain structure a sweep's lanes share and their stacked
    deadlines, from one ``ChainPlan`` per config; ``(None, None)``
    without chains."""
    if chains is None:
        return None, None
    chains = list(chains)
    if len(chains) != lanes or any(p is None for p in chains):
        raise ValueError("chain sweep: need one ChainPlan per config")
    plan = chains[0]
    if any(p.n_chains != plan.n_chains for p in chains):
        raise ValueError("chain sweep: lanes must share the trace's "
                         "chain structure")
    return plan, jnp.asarray(np.stack([p.deadline for p in chains]))


def _result_counts(cfg: ClusterConfig, trace: Trace, node: np.ndarray,
                   outcome: np.ndarray) -> dict:
    """``sim.result``'s counters, counted only while a profiler records:
    ``hits``, ``misses`` and ``drops``, the invocations of each outcome
    code (the pool step's branches: a miss is the only one that may run
    the eviction sort); ``resteered``, the invocations routed off their
    sticky home node ``func_id % n_nodes``; and ``unhostable``, those
    larger than every node's target pool (capacity alone, as
    ``size_aware`` judges it), which no routing can place."""
    if not TraceAnnotation.is_enabled():
        return {}
    hits, misses, drops = np.bincount(outcome, minlength=3)[:3].tolist()
    caps = np.float32(cfg.pool_caps())
    tgt = np.where(np.asarray(cfg.unified)[:, None], 0, [[0, 1]])
    best = np.take_along_axis(caps, tgt, axis=1).max(axis=0)
    size = np.asarray(trace.size_mb, np.float32)
    fits = np.take(best, trace.cls) >= size - np.float32(1e-9)
    home = np.asarray(trace.func_id) % cfg.n_nodes
    return {"hits": hits, "misses": misses, "drops": drops,
            "resteered": int(np.count_nonzero(node != home)),
            "unhostable": int(size.size - np.count_nonzero(fits))}


def _host_nbytes(tree) -> int:
    """Bytes of the host (numpy) arrays in ``tree``: what a call that
    takes it uploads to the device."""
    return sum(a.nbytes for a in jax.tree_util.tree_leaves(tree)
               if isinstance(a, np.ndarray))


def _cloud_vec(cfg: ClusterConfig) -> jnp.ndarray:
    return jnp.asarray([cfg.cloud_rtt_s, cfg.cloud_cold_prob], jnp.float32)


def _drop_size(cfg: ClusterConfig) -> float:
    """A pad-event size no pool of this cluster can ever host, even after
    the autoscaler grows it to the whole node."""
    return float(max(cfg.node_mb)) * 10.0


def _lane_data(cfg: ClusterConfig, plan: ChainPlan | None) -> tuple:
    """One config's program inputs after the events: routing code,
    unified flags, cloud pricing, and the chain deadlines (None without
    chains)."""
    return (jnp.int32(int(cfg.routing)), jnp.asarray(cfg.unified, bool),
            _cloud_vec(cfg),
            None if plan is None else jnp.asarray(plan.deadline))


def _stack_configs(configs, what: str):
    """Validate the shared stacked shapes (``n_nodes``/``max_slots``) and
    stack the per-config scan inputs — the one place both sweep
    drivers (chunk program and epoch grid) build their vmapped data from."""
    configs = list(configs)
    if not configs:
        raise ValueError(f"{what}: configs must be non-empty")
    n, slots = configs[0].n_nodes, configs[0].max_slots
    if any(c.n_nodes != n or c.max_slots != slots for c in configs):
        raise ValueError(f"{what}: configs must share n_nodes and "
                         f"max_slots")
    rz = configs[0].resize_policy is not None
    if any((c.resize_policy is not None) != rz for c in configs):
        # which policy runs is data (the code vmaps per lane); whether the
        # resize fields exist at all changes the compiled pytree shapes
        raise ValueError(f"{what}: configs must agree on vertical scaling "
                         "on/off (repro.sim.sweep buckets mixed groups "
                         "for you)")
    pools = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[init_cluster(c) for c in configs])
    routing = jnp.asarray([int(c.routing) for c in configs], jnp.int32)
    unified = jnp.asarray([c.unified for c in configs], bool)
    cloud = jnp.stack([_cloud_vec(c) for c in configs])
    return configs, n, pools, routing, unified, cloud


# --------------------------------------------------------------------------
# drivers: each returns (ClusterResult, extras) per config — the epoch
# grid's with the split trajectory between them
# --------------------------------------------------------------------------
# The implementations below are shared by the deprecated public names and
# the ``repro.sim`` front door (which must not trip its own deprecation
# warnings).

def _simulate_cluster_jax(
        cfg: ClusterConfig, trace: Trace, rng_seed: int = 0,
        mode: str = "gather", chunk_events: int | None = None,
        failures: Failures | None = None, telemetry: int | None = None,
        chains: ChainPlan | None = None) -> tuple[ClusterResult, dict]:
    """One cluster over ``trace`` in the chunk program, ``chunk_events``
    at a time (None = the whole trace as one chunk): peak device memory
    is bounded by one chunk, and since ``lax.scan`` is sequential the
    outcomes do not depend on the chunk size.  Returns ``(result,
    extras)``: ``"telemetry"`` window arrays and ``"chains"`` per-chain
    arrays when requested, ``"vertical"`` run totals with resize, and
    with ``failures`` the per-node ``"invalidated"`` resident counts and
    the compiled ``"node_up"`` mask."""
    check_step_mode(mode)
    n, t_len = cfg.n_nodes, len(trace)
    chunk = check_chunk_events(chunk_events) or max(t_len, 1)
    with TraceAnnotation("sim.prep", nodes=n):
        n_w = None if telemetry is None else _n_windows(t_len, telemetry)
        cloud_cold = cloud_cold_draws(t_len, cfg.cloud_cold_prob, rng_seed)
        masks = (None if failures is None
                 else _failure_masks(failures, trace, n))
        xs, fills = _host_xs(
            trace, n, _drop_size(cfg), resize=cfg.resize_policy is not None,
            masks=masks, telemetry=telemetry, chains=chains, ccold=cloud_cold)
        carry = _carry0(init_cluster(cfg), n, masks is not None, n_w, chains)
        data = _lane_data(cfg, chains)
    carry, node, outcome = _chunk_loop(_chunk_runner(n, mode), carry, xs,
                                       fills, data, t_len, chunk)
    with TraceAnnotation("sim.result",
                         **_result_counts(cfg, trace, node, outcome)):
        result = build_result(cfg, trace, node, outcome, cloud_cold)
        extras = _extras(_carry_np(carry), n_w, chains)
        if masks is not None:
            extras["node_up"] = masks[0]
        return result, extras


def _simulate_cluster_ref(
        cfg: ClusterConfig, trace: Trace, rng_seed: int = 0,
        failures: Failures | None = None, telemetry: int | None = None,
        chains: ChainPlan | None = None) -> tuple[ClusterResult, dict]:
    """The numpy oracle's twin of :func:`_simulate_cluster_jax`."""
    cloud_cold = cloud_cold_draws(len(trace), cfg.cloud_cold_prob, rng_seed)
    node, outcome, *extras = cluster_outcomes_ref(
        cfg, trace, failures=failures, telemetry=telemetry, chains=chains,
        chain_cold=cloud_cold if chains is not None else None)
    return (build_result(cfg, trace, node, outcome, cloud_cold),
            extras[0] if extras else {})


def lower_chunk_program(cfg: ClusterConfig, trace: Trace,
                        mode: str = "gather", chunk_events: int = 65536):
    """The first-chunk program of a run in chunks with no failures,
    telemetry or chains (what ``simulate(..., chunk_events=...)``
    executes), lowered but not run, to inspect what the compiler is given
    — e.g. whether ``mode="fused"`` holds the Pallas kernel as a
    ``tpu_custom_call``."""
    check_step_mode(mode)
    chunk = check_chunk_events(chunk_events)
    xs, fills = _host_xs(trace, cfg.n_nodes, _drop_size(cfg))
    x = _xs_slice(xs, fills, 0, min(chunk, len(trace)), chunk)
    return _chunk_runner(cfg.n_nodes, mode).lower(
        _carry0(init_cluster(cfg), cfg.n_nodes, False, None, None), x,
        *_lane_data(cfg, None))


def _sweep_cluster(trace: Trace, configs, rng_seed: int = 0,
                   mode: str = "gather", chunk_events: int | None = None,
                   failures=None, telemetry: int | None = None,
                   chains=None, devices: int | None = None,
                   placement: dict | None = None
                   ) -> list[tuple[ClusterResult, dict]]:
    """Many configs (sharing ``n_nodes``/``max_slots``) over one trace in
    the lane-stacked chunk program: one stacked donated carry, threaded
    ``chunk_events`` at a time (None = the whole trace as one chunk).
    ``failures`` is one ``Failures`` (or None) per config, or None;
    ``chains`` one ``ChainPlan`` per config.  ``devices`` shards the lane
    axis (pad lanes ride in the carry and are never read back);
    ``placement`` receives the lane rows per device (see
    :func:`_lanes_np`).  Returns one ``(result, extras)`` per config, as
    :func:`_simulate_cluster_jax` does."""
    check_step_mode(mode)
    devices = check_devices(devices)
    t_len = len(trace)
    chunk = check_chunk_events(chunk_events) or max(t_len, 1)
    with TraceAnnotation("sim.prep"):
        configs, n, pools, routing, unified, cloud = _stack_configs(
            configs, "sweep")
        lanes = len(configs)
        pad = _lane_pad(lanes, devices)
        n_w = None if telemetry is None else _n_windows(t_len, telemetry)
        plan, cdl = _lane_plan(chains, lanes)
        clouds = np.stack([cloud_cold_draws(t_len, c.cloud_cold_prob,
                                            rng_seed) for c in configs])
        masks = _lane_masks(failures, trace, n, lanes, "sweep")
        xs, fills = _host_xs(
            trace, n, max(_drop_size(c) for c in configs),
            resize=configs[0].resize_policy is not None, masks=masks,
            telemetry=telemetry, chains=plan, ccold=clouds)
        xs = _pad_xs(xs, pad)
        carry = _carry0(_pad_tree(pools, pad), n, masks is not None, n_w,
                        plan, (lanes + pad,))
        data = _pad_tree((routing, unified, cloud, cdl), pad)
    carry, nodes, outcomes = _chunk_loop(
        _sweep_chunk_runner(n, mode, devices), carry, xs, fills, data,
        t_len, chunk, lanes, placement)
    with TraceAnnotation("sim.result"):
        host = _carry_np(carry)
        out = []
        for g, c in enumerate(configs):
            extras = _extras(_lane(host, g), n_w, plan)
            if masks is not None:
                extras["node_up"] = masks[0][g]
            out.append((build_result(c, trace, nodes[g], outcomes[g],
                                     clouds[g]), extras))
        return out


def _simulate_cluster_autoscale_jax(
        cfg: ClusterConfig, asc: Autoscale, trace: Trace, rng_seed: int = 0,
        mode: str = "gather", failures: Failures | None = None,
        telemetry: int | None = None, chains: ChainPlan | None = None
        ) -> tuple[ClusterResult, np.ndarray, dict]:
    """One autoscaled cluster over ``trace`` in the epoch grid: returns
    (ClusterResult, fracs f32[E, N], extras) — extras as
    :func:`_simulate_cluster_jax`'s, plus the membership trajectory
    (``active`` bool[E, N]), with ``invalidated`` always (retirements
    count) and ``node_up`` None without a failure schedule."""
    check_step_mode(mode)
    n, t_len = cfg.n_nodes, len(trace)
    n_w = None if telemetry is None else _n_windows(t_len, telemetry)
    cloud_cold = cloud_cold_draws(t_len, cfg.cloud_cold_prob, rng_seed)
    masks = None if failures is None else _failure_masks(failures, trace, n)
    xs, fills = _host_xs(
        trace, n, _drop_size(cfg), resize=cfg.resize_policy is not None,
        masks=masks, telemetry=telemetry, chains=chains, ccold=cloud_cold)
    grid, valid = _xs_grid(xs, fills, t_len, asc.epoch_events)
    routing, unified, cloud, cdl = _lane_data(cfg, chains)
    carry, node, outcome, fracs, actives = _epoch_runner(n, mode)(
        _carry0(init_cluster(cfg), n, True, n_w, chains), grid, valid,
        routing, unified, cloud, *_autoscale_inputs(cfg, asc), cdl)
    extras = _extras(_carry_np(carry), n_w, chains)
    extras.update(node_up=None if masks is None else masks[0],
                  active=np.asarray(actives, bool))
    node = np.asarray(node).reshape(-1)[:t_len]
    outcome = np.asarray(outcome).reshape(-1)[:t_len]
    return (build_result(cfg, trace, node, outcome, cloud_cold),
            np.asarray(fracs), extras)


def _simulate_cluster_autoscale_ref(
        cfg: ClusterConfig, asc: Autoscale, trace: Trace,
        rng_seed: int = 0, failures: Failures | None = None,
        telemetry: int | None = None, chains: ChainPlan | None = None
        ) -> tuple[ClusterResult, np.ndarray, dict]:
    cloud_cold = cloud_cold_draws(len(trace), cfg.cloud_cold_prob, rng_seed)
    node, outcome, fracs, extras = cluster_outcomes_ref(
        cfg, trace, autoscale=asc, failures=failures, telemetry=telemetry,
        chains=chains,
        chain_cold=(cloud_cold if chains is not None else None))
    return build_result(cfg, trace, node, outcome, cloud_cold), fracs, extras


def _sweep_cluster_autoscale(
        trace: Trace, configs, autoscales, failures=None, rng_seed: int = 0,
        mode: str = "gather", telemetry: int | None = None, chains=None,
        devices: int | None = None, placement: dict | None = None
        ) -> list[tuple[ClusterResult, np.ndarray, dict]]:
    """Many autoscaled configs over one trace in the lane-stacked epoch
    grid.  All configs must share ``n_nodes``/``max_slots`` AND all
    autoscales ``epoch_events`` (the stacked shapes); min/max/gain,
    node-scaling thresholds, initial membership, fracs, capacities, and
    per-lane failure masks vary as data.  Any lane with a failure
    schedule gives the group masks (lanes without one ride along on
    all-up masks — the same arithmetic); ``repro.sim.sweep`` buckets
    failure-free lanes separately."""
    check_step_mode(mode)
    devices = check_devices(devices)
    autoscales = list(autoscales)
    configs, n, pools, routing, unified, cloud = _stack_configs(
        configs, "autoscale sweep")
    lanes, t_len = len(configs), len(trace)
    if len(autoscales) != lanes:
        raise ValueError("autoscale sweep: need one Autoscale per config")
    e = autoscales[0].epoch_events
    if any(a.epoch_events != e for a in autoscales):
        raise ValueError("autoscale sweep: configs must share epoch_events"
                         " (sweep() buckets mixed epoch shapes for you)")
    failures = [None] * lanes if failures is None else list(failures)
    masks = _lane_masks(failures, trace, n, lanes, "autoscale sweep")
    if all(f is None for f in failures):
        masks = None    # the unmasked grid: no per-event invalidation
    pad = _lane_pad(lanes, devices)
    n_w = None if telemetry is None else _n_windows(t_len, telemetry)
    plan, cdl = _lane_plan(chains, lanes)
    clouds = np.stack([cloud_cold_draws(t_len, c.cloud_cold_prob, rng_seed)
                       for c in configs])
    xs, fills = _host_xs(
        trace, n, max(_drop_size(c) for c in configs),
        resize=configs[0].resize_policy is not None, masks=masks,
        telemetry=telemetry, chains=plan, ccold=clouds)
    grid, valid = _xs_grid(_pad_xs(xs, pad), fills, t_len, e, lanes=True)
    per_cfg = [_autoscale_inputs(c, a) for c, a in zip(configs, autoscales)]
    asc_data = tuple(jnp.stack([p[i] for p in per_cfg]) for i in range(4))
    carry, nodes, outcomes, fracs, actives = _sweep_epoch_runner(
        n, mode, devices)(
        _carry0(_pad_tree(pools, pad), n, True, n_w, plan, (lanes + pad,)),
        grid, valid,
        *_pad_tree((routing, unified, cloud, *asc_data, cdl), pad))
    # pad lanes (if any) are dropped here: only real lane rows are read
    nodes = (_lanes_np(nodes, placement)[:lanes]
             .reshape(lanes, -1)[:, :t_len])
    outcomes = np.asarray(outcomes)[:lanes].reshape(lanes, -1)[:, :t_len]
    fracs, actives = np.asarray(fracs), np.asarray(actives, bool)
    host = _carry_np(carry)
    out = []
    for g, c in enumerate(configs):
        extras = _extras(_lane(host, g), n_w, plan)
        extras.update(node_up=None if failures[g] is None else masks[0][g],
                      active=actives[g])
        out.append((build_result(c, trace, nodes[g], outcomes[g],
                                 clouds[g]), fracs[g], extras))
    return out


@deprecated("repro.sim.simulate(Scenario.cluster(...))")
def simulate_cluster_jax(cfg: ClusterConfig, trace: Trace,
                         rng_seed: int = 0,
                         mode: str = "gather") -> ClusterResult:
    """Simulate the cluster on ``trace``; one jitted scan end to end."""
    return _simulate_cluster_jax(cfg, trace, rng_seed, mode)[0]


@deprecated("repro.sim.simulate(Scenario.cluster(...), engine='ref')")
def simulate_cluster_ref(cfg: ClusterConfig, trace: Trace,
                         rng_seed: int = 0) -> ClusterResult:
    """Numpy-oracle twin of :func:`simulate_cluster_jax` (same result
    type, sequential engine from ``core/continuum.py``)."""
    return _simulate_cluster_ref(cfg, trace, rng_seed)[0]


@deprecated("repro.sim.sweep(trace, scenarios)")
def sweep_cluster(trace: Trace, configs, rng_seed: int = 0,
                  mode: str = "gather") -> list[ClusterResult]:
    """Evaluate many cluster configurations (capacities x splits x routing)
    in ONE vmapped jit.

    All configs must share ``n_nodes`` and ``max_slots`` (the stacked
    shapes); everything else — per-node capacities, splits, unified flags,
    routing policy, cloud pricing — may vary per config.  Cloud cold flips
    use common random numbers across configs.  (``repro.sim.sweep``
    additionally buckets mixed shapes into multiple vmapped runs.)
    """
    return [res for res, _ in _sweep_cluster(trace, configs, rng_seed,
                                             mode)]
