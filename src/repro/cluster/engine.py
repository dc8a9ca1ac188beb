"""Batched JAX cluster engine: N heterogeneous nodes, one ``lax.scan``.

Every node owns two warm pools (a unified node uses pool 0 with the whole
node memory and a zero-capacity pool 1), and all ``2N`` pools of the
cluster are stacked on one leading axis of a single ``PoolState``.  The
whole trace then runs as ONE ``lax.scan`` program:

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

Autoscaled scenarios (``Scenario(..., autoscale=Autoscale(...))``) run the
same per-event step inside an outer scan over fixed-length epochs
(``_run_autoscale_impl``): each full epoch ends with every KiSS node
re-splitting its small/large pools from the per-class pressure observed on
that node (``pool_resize`` vmapped over the stacked pool axis), and — when
node scaling is enabled — one node spawning or retiring from the
cluster-wide drop fraction (the membership mask rides in the carry).  The
trace is padded to a whole number of epochs with guaranteed-drop no-op
events that are masked out of the pressure signal and sliced off the
outputs.

Failure schedules (``Scenario(..., failures=Failures(...))``) compile
host-side into per-event ``up``/``recover`` bool[T, N] masks that ride
into the scan as data (``_run_failures_impl``; shared verbatim with the
oracle): routing sees ``RouteCtx.node_up``, a request routed to a down
node drops to the cloud without touching any pool, and a recovering
node's pools are cleared first (``_invalidate_nodes``) so the re-warm
cost is observable.
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


def cluster_events(trace: Trace, n_nodes: int, *,
                   resize: bool = False) -> ClusterEvent:
    h1, h2 = route_hashes(trace.func_id, n_nodes)
    return ClusterEvent(
        t=jnp.asarray(trace.t, jnp.float32),
        func_id=jnp.asarray(trace.func_id, jnp.int32),
        size=jnp.asarray(trace.size_mb, jnp.float32),
        cls=jnp.asarray(trace.cls, jnp.int32),
        warm=jnp.asarray(trace.warm_dur, jnp.float32),
        cold=jnp.asarray(trace.cold_dur, jnp.float32),
        h1=jnp.asarray(h1, jnp.int32),
        h2=jnp.asarray(h2, jnp.int32),
        used=(jnp.asarray(observed_usage(
            np, np.asarray(trace.func_id, np.int32),
            np.asarray(trace.size_mb, np.float32)))
            if resize else None),
    )


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
# pieces here keep the accumulator a fixed-shape pytree so it rides any
# scan carry (monolithic, failure-injected, epoch, or chunked) and vmaps
# across sweep lanes.  Window indices are *global* event indices computed
# host-side (``i // window_events``) and carried into the scan as data,
# so a chunked run scatters into the same windows as a monolithic one —
# chunked == monolithic holds for ANY chunk size, dividing the window or
# not.  Row ``n_windows`` is a junk row that absorbs pad events (epoch /
# chunk padding) and is sliced off host-side by ``_tel_np``.

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


def _tel_init(n_windows: int, n_nodes: int) -> TelAcc:
    w = n_windows + 1
    return TelAcc(counts=jnp.zeros((w, 2, 3), jnp.int32),
                  free=jnp.zeros((w, n_nodes), jnp.float32),
                  occ=jnp.zeros((w, n_nodes), jnp.int32),
                  inval=jnp.zeros((w,), jnp.int32),
                  up=jnp.zeros((w,), jnp.int32),
                  active=jnp.zeros((w,), jnp.int32),
                  cmiss=jnp.zeros((w,), jnp.int32))


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


def _widx(n_events: int, window: int) -> jnp.ndarray:
    """Global window index per event — scan data, computed host-side."""
    return jnp.asarray(np.arange(n_events, dtype=np.int32) // window)


def _widx_grid(n_events: int, epoch_events: int,
               window: int) -> jnp.ndarray:
    """Epoch-shaped [E, e] window indices (pad events index the junk
    row) — the telemetry analogue of :func:`_epoch_grid`."""
    e = epoch_events
    n_epochs = -(-n_events // e)
    pad = n_epochs * e - n_events
    idx = np.arange(n_events, dtype=np.int32) // window
    if pad:
        idx = np.concatenate(
            [idx, np.full(pad, _n_windows(n_events, window), np.int32)])
    return jnp.asarray(idx.reshape(n_epochs, e))


def _chunk_widx(s: int, e: int, chunk: int, window: int,
                n_windows: int) -> jnp.ndarray:
    """Chunk-slice of the global window indices, padded with the junk
    index — the telemetry analogue of :func:`_chunk_slice`."""
    idx = np.arange(s, e, dtype=np.int32) // window
    pad = chunk - (e - s)
    if pad:
        idx = np.concatenate([idx, np.full(pad, n_windows, np.int32)])
    return jnp.asarray(idx)


# --------------------------------------------------------------------------
# in-scan chain accounting: per-chain end-to-end state riding the carry
# --------------------------------------------------------------------------
# ``core.continuum.compile_chains`` turns a chained trace into a
# ``ChainPlan`` host-side; the engine carries one f32 latency row per
# chain (+ the junk row ``n_chains`` that absorbs pad events, exactly
# like the telemetry junk window) through every scan shape — monolithic,
# failure-injected, epoch, chunked — and the oracle mirrors each update
# through float32 in the same event order, so the two engines' chain
# latencies and deadline-miss flags are bit-identical by construction.
# The plan's per-event arrays ride as ``xs`` data shared across sweep
# lanes; the per-chain deadline vector and the cloud cold draws are
# per-lane data (lanes differ in Chains config / cloud_cold_prob).

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


def _chain_init(n_chains: int) -> ChainAcc:
    c = n_chains + 1
    return ChainAcc(lat=jnp.zeros((c,), jnp.float32),
                    dropped=jnp.zeros((c,), bool),
                    done=jnp.zeros((c,), bool),
                    missed=jnp.zeros((c,), bool))


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


def _stack_chain(n_chains: int, lanes: int) -> ChainAcc:
    """One zeroed chain accumulator per sweep lane (lanes in a group
    share the trace, hence the chain count — the stack is dense)."""
    return jax.tree_util.tree_map(
        lambda a: jnp.zeros((lanes,) + a.shape, a.dtype),
        _chain_init(n_chains))


def _chain_xs(plan: ChainPlan) -> ChainXs:
    """The plan's per-event arrays as scan data."""
    return ChainXs(cid=jnp.asarray(plan.cid, jnp.int32),
                   stage=jnp.asarray(plan.stage, jnp.int32),
                   last=jnp.asarray(plan.last, bool))


def _chain_xs_np(plan: ChainPlan) -> ChainXs:
    """Numpy twin of :func:`_chain_xs` for the chunked host loop."""
    return ChainXs(cid=np.asarray(plan.cid, np.int32),
                   stage=np.asarray(plan.stage, np.int32),
                   last=np.asarray(plan.last, bool))


def _chain_grid(plan: ChainPlan, n_events: int,
                epoch_events: int) -> ChainXs:
    """Epoch-shaped [E, e] chain xs (pad events index the junk row) —
    the chain analogue of :func:`_epoch_grid`."""
    e = epoch_events
    n_epochs = -(-n_events // e)
    pad = n_epochs * e - n_events
    xs = _chain_xs_np(plan)
    if pad:
        fills = ChainXs(cid=plan.n_chains, stage=-1, last=False)
        xs = jax.tree_util.tree_map(
            lambda a, f: np.concatenate([a, np.full(pad, f, a.dtype)]),
            xs, fills)
    return jax.tree_util.tree_map(
        lambda a: jnp.asarray(a.reshape(n_epochs, e)), xs)


def _chunk_chain(xs: ChainXs, n_chains: int, s: int, e: int,
                 chunk: int) -> ChainXs:
    """Chunk-slice of the per-event chain xs, padded with junk-row
    no-ops — the chain analogue of :func:`_chunk_slice`."""
    sl = jax.tree_util.tree_map(lambda a: a[s:e], xs)
    pad = chunk - (e - s)
    if pad:
        fills = ChainXs(cid=n_chains, stage=-1, last=False)
        sl = jax.tree_util.tree_map(
            lambda a, f: np.concatenate([a, np.full(pad, f, a.dtype)]),
            sl, fills)
    return jax.tree_util.tree_map(jnp.asarray, sl)


def _grid_pad(arr: np.ndarray, n_events: int, epoch_events: int,
              fill) -> jnp.ndarray:
    """Pad a per-event 1-D array to whole epochs and reshape [E, e]."""
    e = epoch_events
    n_epochs = -(-n_events // e)
    pad = n_epochs * e - n_events
    if pad:
        arr = np.concatenate([arr, np.full(pad, fill, arr.dtype)])
    return jnp.asarray(arr.reshape(n_epochs, e))


def _chunk_pad(arr: np.ndarray, s: int, e: int, chunk: int,
               fill) -> jnp.ndarray:
    """Chunk-slice a per-event 1-D array, padding to ``chunk``."""
    sl = arr[s:e]
    pad = chunk - (e - s)
    if pad:
        sl = np.concatenate([sl, np.full(pad, fill, arr.dtype)])
    return jnp.asarray(sl)


def _make_step(routing: jax.Array, unified: jax.Array, cloud: jax.Array,
               n_nodes: int, mode: str):
    """Build the per-event scan step (route, then step the routed pool) —
    shared by the static whole-trace scan, the failure-injected scan, and
    the autoscaled epoch scan.  ``up_n`` (bool[N], optional) is the
    live-node mask: routing policies read it via ``RouteCtx.node_up`` and
    a request still routed to a down node drops to the cloud without
    touching any pool (down pools are frozen).  ``cslack``/``cstage``
    (optional f32/i32 scalars) are the event's chain slack and stage for
    ``RouteCtx`` — constants ``+inf``/``-1`` when chains are off, so
    slack-aware policies degrade to their slack-rich branch."""
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


def _vert_of(pools: PoolState) -> tuple:
    """The vertical-scaling run totals of a final pool state, as a
    one-element tuple to splice onto a runner's outputs — empty when
    resize is off, so resize-off output shapes stay byte-identical.
    Always the LAST output element (after telemetry and chains)."""
    if pools.alloc is None:
        return ()
    return ((pools.acc_used, pools.acc_alloc, pools.bneck),)


def _vert_np(vert) -> dict:
    """Host-side view of a ``_vert_of`` element: per-pool run totals in
    the stacked node-major [2N] layout (or [L, 2N] sweep-lane slices) —
    the JAX twin of the oracle's ``_vertical()`` extras."""
    acc_used, acc_alloc, bneck = vert
    return {"acc_used_mb": np.asarray(acc_used, np.float32),
            "acc_alloc_mb": np.asarray(acc_alloc, np.float32),
            "bottlenecks": np.asarray(bneck, np.int64)}


def _run_cluster_impl(pools: PoolState, events: ClusterEvent,
                      routing: jax.Array, unified: jax.Array,
                      cloud: jax.Array, widx=None, tel=None, cxs=None,
                      ccold=None, cdl=None, chain=None, *,
                      n_nodes: int, mode: str):
    """The whole trace in one scan.  Returns (node i32[T], outcome
    i32[T]); with telemetry (``widx``/``tel`` set) the final
    :class:`TelAcc` rides along, and with chains (``cxs``/``ccold``/
    ``cdl``/``chain`` set) the final :class:`ChainAcc` comes last —
    ``tel is None and chain is None`` compiles the exact pre-telemetry,
    pre-chain program."""
    step = _make_step(routing, unified, cloud, n_nodes, mode)
    tel_on, ch_on = tel is not None, chain is not None
    if not tel_on and not ch_on:
        c_end, (nodes, outcomes) = jax.lax.scan(step, pools, events)
        return (nodes, outcomes) + _vert_of(c_end)
    n_up = jnp.int32(n_nodes)

    def s(carry, x):
        pools = carry[0]
        acc = carry[1] if tel_on else None
        chain = carry[-1] if ch_on else None
        ev = x[0]
        wi = x[1] if tel_on else None
        if ch_on:
            cx, cc = x[-2], x[-1]
            slack, stg = _chain_pre(chain, cdl, cx)
            pools, (node, outcome) = step(pools, ev, None, slack, stg)
            chain, miss = _chain_event(chain, cx, cc, cdl, ev, outcome,
                                       cloud)
        else:
            pools, (node, outcome) = step(pools, ev)
            miss = jnp.int32(0)
        if tel_on:
            acc = _tel_event(acc, wi, ev, outcome, pools, n_nodes,
                             n_up, n_up, jnp.int32(0), miss)
        carry = ((pools,) + ((acc,) if tel_on else ())
                 + ((chain,) if ch_on else ()))
        return carry, (node, outcome)

    c0 = ((pools,) + ((tel,) if tel_on else ())
          + ((chain,) if ch_on else ()))
    xs = ((events,) + ((widx,) if tel_on else ())
          + ((cxs, ccold) if ch_on else ()))
    c_end, (nodes, outcomes) = jax.lax.scan(s, c0, xs)
    out = (nodes, outcomes)
    if tel_on:
        out = out + (c_end[1],)
    if ch_on:
        out = out + (c_end[-1],)
    return out + _vert_of(c_end[0])


def _run_failures_impl(pools: PoolState, events: ClusterEvent,
                       up: jax.Array, recover: jax.Array,
                       routing: jax.Array, unified: jax.Array,
                       cloud: jax.Array, widx=None, tel=None, cxs=None,
                       ccold=None, cdl=None, chain=None, *,
                       n_nodes: int, mode: str):
    """The failure-injected trace in one scan: ``up``/``recover`` are the
    bool[T, N] masks compiled host-side from the ``Failures`` schedule
    (shared verbatim with the oracle).  Each event first clears the pools
    of any node recovering at it (counting the invalidated residents —
    the re-warm debt), then routes with ``RouteCtx.node_up = up[t]``.
    Returns (node i32[T], outcome i32[T], invalidated i32[N]); telemetry
    appends the final :class:`TelAcc` (recovery invalidations land in the
    window of the event that observed them) and chains append the final
    :class:`ChainAcc` last."""
    step = _make_step(routing, unified, cloud, n_nodes, mode)
    tel_on, ch_on = tel is not None, chain is not None

    def s(carry, x):
        pools, inval = carry[0], carry[1]
        acc = carry[2] if tel_on else None
        chain = carry[-1] if ch_on else None
        ev, u, r = x[0], x[1], x[2]
        wi = x[3] if tel_on else None
        cnt, pools = _invalidate_nodes(pools, r, n_nodes)
        if ch_on:
            cx, cc = x[-2], x[-1]
            slack, stg = _chain_pre(chain, cdl, cx)
            pools, (node, outcome) = step(pools, ev, u, slack, stg)
            chain, miss = _chain_event(chain, cx, cc, cdl, ev, outcome,
                                       cloud)
        else:
            pools, (node, outcome) = step(pools, ev, u)
            miss = jnp.int32(0)
        if tel_on:
            acc = _tel_event(acc, wi, ev, outcome, pools, n_nodes,
                             jnp.sum(u).astype(jnp.int32),
                             jnp.int32(n_nodes), jnp.sum(cnt), miss)
        carry = ((pools, inval + cnt) + ((acc,) if tel_on else ())
                 + ((chain,) if ch_on else ()))
        return carry, (node, outcome)

    inval0 = jnp.zeros((n_nodes,), jnp.int32)
    c0 = ((pools, inval0) + ((tel,) if tel_on else ())
          + ((chain,) if ch_on else ()))
    xs = ((events, up, recover) + ((widx,) if tel_on else ())
          + ((cxs, ccold) if ch_on else ()))
    c_end, (nodes, outcomes) = jax.lax.scan(s, c0, xs)
    out = (nodes, outcomes, c_end[1])
    if tel_on:
        out = out + (c_end[2],)
    if ch_on:
        out = out + (c_end[-1],)
    return out + _vert_of(c_end[0])


def _run_autoscale_impl(pools: PoolState, events: ClusterEvent,
                        valid: jax.Array, up: jax.Array, recover: jax.Array,
                        routing: jax.Array, unified: jax.Array,
                        cloud: jax.Array, frac: jax.Array,
                        node_mb: jax.Array, asc: jax.Array,
                        active0: jax.Array, widx=None, tel=None, cxs=None,
                        ccold=None, cdl=None, chain=None, *,
                        n_nodes: int, mode: str, masked: bool = True):
    """The autoscaled trace: an outer scan over epochs, the existing event
    scan inside each epoch, and a per-node re-split plus a node
    spawn/retire decision between epochs.

    ``events`` leaves are shaped ``[E, epoch_events, ...]`` (trace padded
    with guaranteed-drop no-ops); ``valid`` is f32[E, e] marking real
    events.  Pad events never touch pool state (a drop is a no-op
    transition) and are masked out of the pressure signal here — the
    padding bias that skewed the legacy ``core.adaptive`` split decision
    cannot arise.  ``up``/``recover`` are the epoch-shaped bool[E, e, N]
    failure masks; ``masked`` is static so a scenario *without* a failure
    schedule passes ``None`` masks and compiles a program with zero
    per-event invalidation work (node scaling alone only reads the
    membership carry — on all-up masks the masked program computes the
    identical results, just slower).  ``frac`` is the running f32[N]
    small-pool fraction, ``asc`` packs (min_frac, max_frac, gain,
    spawn_drop_frac, retire_drop_frac) as data so sweeps can vmap over
    them (+/-inf thresholds = node scaling off), and ``active0`` (bool[N])
    is the starting membership.  Returns (node i32[E, e], outcome
    i32[E, e], fracs f32[E, N], actives bool[E, N], invalidated i32[N]);
    telemetry (``widx`` f32[E, e] window indices + a :class:`TelAcc`)
    appends the final accumulator — retirement invalidations land in the
    epoch's last real window, recovery invalidations in the window of the
    event that observed them.  Chains (epoch-shaped ``cxs``/``ccold`` +
    the deadline vector and a :class:`ChainAcc`) append the final chain
    accumulator last — pad events land in its junk row.
    """
    step = _make_step(routing, unified, cloud, n_nodes, mode)
    tree = jax.tree_util.tree_map
    n = n_nodes
    tel_on = tel is not None
    ch_on = chain is not None
    mn, mx, gain, spawn_th, retire_th = (asc[0], asc[1], asc[2], asc[3],
                                         asc[4])
    pool_unified = jnp.repeat(unified, 2)            # bool[2N]

    def epoch(carry, inp):
        pools, frac, active, inval = (carry[0], carry[1], carry[2],
                                      carry[3])
        acc = carry[4] if tel_on else None
        chain = carry[-1] if ch_on else None
        evs, val = inp[0], inp[1]

        def inner(c, x):
            pools, press, dropw, inval = c[0], c[1], c[2], c[3]
            acc = c[4] if tel_on else None
            chain = c[-1] if ch_on else None
            ev, v = x[0], x[1]
            wi = x[2] if tel_on else None
            k = 3 if tel_on else 2
            if masked:
                u, r = x[k], x[k + 1]
                cnt, pools = _invalidate_nodes(pools, r, n)
                inval = inval + cnt
                eff = u & active
            else:
                eff = active
            if ch_on:
                cx, cc = x[-2], x[-1]
                slack, stg = _chain_pre(chain, cdl, cx)
                pools, (node, outcome) = step(pools, ev, eff, slack, stg)
                chain, miss = _chain_event(chain, cx, cc, cdl, ev,
                                           outcome, cloud)
            else:
                pools, (node, outcome) = step(pools, ev, eff)
                miss = jnp.int32(0)
            # pressure = misses + 2x drops, per (routed node, size class);
            # pad events carry v == 0 and contribute nothing
            w = v * jnp.where(outcome == MISS, 1.0,
                              jnp.where(outcome == DROP, 2.0, 0.0))
            press = press.at[node, ev.cls].add(w)
            dropw = dropw + v * jnp.where(outcome == DROP, 1.0, 0.0)
            if tel_on:
                acc = _tel_event(
                    acc, wi, ev, outcome, pools, n,
                    jnp.sum(u).astype(jnp.int32) if masked
                    else jnp.int32(n),
                    jnp.sum(active.astype(jnp.int32)),
                    jnp.sum(cnt) if masked else jnp.int32(0), miss)
            c = ((pools, press, dropw, inval)
                 + ((acc,) if tel_on else ()) + ((chain,) if ch_on else ()))
            return c, (node, outcome)

        c0 = ((pools, jnp.zeros((n, 2), jnp.float32), jnp.float32(0.0),
               inval) + ((acc,) if tel_on else ())
              + ((chain,) if ch_on else ()))
        c_end, (nodes, outcomes) = jax.lax.scan(inner, c0, inp)
        pools, press, dropw, inval = (c_end[0], c_end[1], c_end[2],
                                      c_end[3])
        if tel_on:
            acc = c_end[4]
        if ch_on:
            chain = c_end[-1]
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
        now = jnp.max(jnp.where(val > 0, evs.t, -jnp.inf))
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
        n_active = jnp.sum(active.astype(jnp.int32))
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
        if tel_on:
            # retirement invalidations belong to the epoch's last real
            # window (retirement only fires on full epochs, so w_end is
            # always a real index there)
            w_end = jnp.max(jnp.where(val > 0, inp[2], -1))
            acc = acc._replace(inval=acc.inval.at[w_end].add(jnp.sum(cnt)))
        carry = ((pools, new_frac, new_active, inval + cnt)
                 + ((acc,) if tel_on else ())
                 + ((chain,) if ch_on else ()))
        return carry, (nodes, outcomes, new_frac, new_active)

    xs = ((events, valid) + ((widx,) if tel_on else ())
          + ((up, recover) if masked else ())
          + ((cxs, ccold) if ch_on else ()))
    c0 = ((pools, frac, active0, jnp.zeros((n,), jnp.int32))
          + ((tel,) if tel_on else ()) + ((chain,) if ch_on else ()))
    c_end, (nodes, outcomes, fracs, actives) = jax.lax.scan(epoch, c0, xs)
    out = (nodes, outcomes, fracs, actives, c_end[3])
    if tel_on:
        out = out + (c_end[4],)
    if ch_on:
        out = out + (c_end[-1],)
    return out + _vert_of(c_end[0])


_run_cluster = jax.jit(_run_cluster_impl,
                       static_argnames=("n_nodes", "mode"))

_run_failures = jax.jit(_run_failures_impl,
                        static_argnames=("n_nodes", "mode"))

_run_autoscale = jax.jit(_run_autoscale_impl,
                         static_argnames=("n_nodes", "mode", "masked"))


def _chain_axes(tel: bool, chain: bool) -> tuple:
    """Trailing vmap in_axes for the optional telemetry + chain args
    ``(widx, tel, cxs, ccold, cdl, chain)``: window indices and chain
    event data are shared across lanes; accumulators, cold draws and
    deadlines are per-lane.  When only chains are on, the telemetry slots
    are ``None`` args (empty pytrees — any in_axes is harmless)."""
    axes = ()
    if tel or chain:
        axes += (None, 0)          # widx, TelAcc
    if chain:
        axes += (None, 0, 0, 0)    # cxs, ccold, cdl, ChainAcc
    return axes


# --------------------------------------------------------------------------
# device-mesh sharded sweeps: lanes split across jax.devices()
# --------------------------------------------------------------------------
# ``sweep(..., devices=k)`` splits the stacked lane axis of each shape
# bucket across a 1-D device mesh with shard_map: every device runs the
# SAME vmapped scan on its shard of lanes, so per-lane arithmetic — and
# hence every per-lane output — is bit-identical to the unsharded run (no
# cross-lane reductions exist anywhere in the sweep path).  The in_specs
# mirror the runner's vmap in_axes one-for-one (lane-stacked args split,
# shared args replicate; both use the same pytree-prefix rule), and a
# non-dividing lane count is padded with duplicates of lane 0 — the lane
# analogue of the guaranteed-drop no-op pad events in ``_epoch_grid``:
# the pad lanes run real (discarded) work and are sliced off before
# ``Result`` assembly.  ``devices=None`` skips shard_map entirely, so the
# single-device runners stay byte-identical to the pre-sharding programs.

def _lane_mesh(devices: int) -> Mesh:
    """A 1-D mesh over the first ``devices`` JAX devices; on CPU,
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before
    the first jax import) turns cores into mesh devices."""
    return Mesh(np.asarray(jax.devices()[:devices]), ("lanes",))


def _lane_specs(axes: tuple) -> tuple:
    """shard_map in_specs mirroring a vmap in_axes tuple: lane-stacked
    args (axis 0) split across the mesh, shared args replicate.  Entries
    are pytree prefixes, exactly like the in_axes they mirror."""
    return tuple(PartitionSpec("lanes") if a == 0 else PartitionSpec()
                 for a in axes)


def _shard_lanes(fn, axes: tuple, devices: int | None):
    """Wrap a vmapped sweep impl in shard_map over the lane axis (every
    output of every runner is lane-stacked, hence the blanket out_specs).
    A no-op when ``devices`` is None.  ``check_vma=False`` because
    pallas_call (``mode="fused"``) has no replication rule — harmless
    here since no output is replicated."""
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
    leaf (zeros stay zeros for accumulators; real configs just duplicate
    — their outputs are never read)."""
    if not pad:
        return tree
    return jax.tree_util.tree_map(
        lambda a: jnp.concatenate(
            [jnp.asarray(a), jnp.repeat(jnp.asarray(a)[:1], pad, axis=0)]),
        tree)


def _pad_lanes(args: tuple, axes: tuple, pad: int) -> tuple:
    """Pad every lane-stacked runner arg (vmap in_axes 0 — same
    pytree-prefix rule) with lane-0 duplicates; shared args and ``None``
    placeholders pass through untouched."""
    if not pad:
        return args
    return tuple(_pad_tree(arg, pad) if ax == 0 else arg
                 for arg, ax in zip(args, axes))


def _sweep_axes(tel: bool, chain: bool) -> tuple:
    return (0, None, 0, 0, 0) + _chain_axes(tel, chain)


def _sweep_failures_axes(tel: bool, chain: bool) -> tuple:
    return (0, None, 0, 0, 0, 0, 0) + _chain_axes(tel, chain)


def _sweep_autoscale_axes(masked: bool, tel: bool, chain: bool) -> tuple:
    return ((0, None, None, 0 if masked else None,
             0 if masked else None, 0, 0, 0, 0, 0, 0, 0)
            + _chain_axes(tel, chain))


@functools.lru_cache(maxsize=None)
def _sweep_runner(n_nodes: int, mode: str, tel: bool = False,
                  chain: bool = False, devices: int | None = None):
    """Cached jitted vmap of the scan, keyed on the static shape args, so
    repeated sweep calls hit the compile cache like ``_run_cluster``
    does.  ``tel`` lanes share the window-index data and stack their
    accumulators; ``chain`` lanes share the chain event data and stack
    their accumulators, cold draws and deadlines.  ``devices`` shards the
    lane axis across a device mesh (None = the exact single-device
    program)."""
    axes = _sweep_axes(tel, chain)
    return jax.jit(_shard_lanes(jax.vmap(
        functools.partial(_run_cluster_impl, n_nodes=n_nodes, mode=mode),
        in_axes=axes), axes, devices))


@functools.lru_cache(maxsize=None)
def _sweep_failures_runner(n_nodes: int, mode: str, tel: bool = False,
                           chain: bool = False,
                           devices: int | None = None):
    """Failure analogue of ``_sweep_runner``: every lane carries its own
    compiled up/recover masks as data (same [T, N] shape — lanes bucket by
    mask shape), so mixed failure schedules sweep in one program."""
    axes = _sweep_failures_axes(tel, chain)
    return jax.jit(_shard_lanes(jax.vmap(
        functools.partial(_run_failures_impl, n_nodes=n_nodes, mode=mode),
        in_axes=axes), axes, devices))


@functools.lru_cache(maxsize=None)
def _sweep_autoscale_runner(n_nodes: int, mode: str, masked: bool,
                            tel: bool = False, chain: bool = False,
                            devices: int | None = None):
    """Autoscale analogue of ``_sweep_runner``: configs (pools, masks,
    routing, unified, cloud, frac, node_mb, asc thresholds, active0) vmap
    as data; the epoch grid and validity mask are shared across lanes.
    ``masked`` lanes carry per-lane failure masks; unmasked lanes pass
    ``None`` masks and compile the cheap no-invalidation program."""
    axes = _sweep_autoscale_axes(masked, tel, chain)
    return jax.jit(_shard_lanes(jax.vmap(
        functools.partial(_run_autoscale_impl, n_nodes=n_nodes, mode=mode,
                          masked=masked),
        in_axes=axes), axes, devices))


def _epoch_grid(events: ClusterEvent, n_events: int, epoch_events: int,
                drop_size: float):
    """Pad the trace to a whole number of epochs and reshape to [E, e].

    Pad events are guaranteed-drop no-ops: an impossible function id and a
    size larger than any pool, so ``pool_step`` leaves every pool state
    untouched.  Returns (epoch-shaped events, valid f32[E, e]); the f32
    mask doubles as the pressure weight inside the scan.
    """
    e = epoch_events
    n_epochs = -(-n_events // e)
    pad = n_epochs * e - n_events
    if pad:
        last_t = events.t[-1] if n_events else jnp.float32(0.0)
        fills = ClusterEvent(
            t=last_t, func_id=-2, size=drop_size, cls=0, warm=0.0, cold=0.0,
            h1=0, h2=0,
            used=None if events.used is None else 0.0)
        events = jax.tree_util.tree_map(
            lambda a, f: jnp.concatenate(
                [a, jnp.full((pad,), f, a.dtype)]), events, fills)
    epochs = jax.tree_util.tree_map(
        lambda a: a.reshape(n_epochs, e), events)
    valid = jnp.concatenate(
        [jnp.ones(n_events, jnp.float32),
         jnp.zeros(pad, jnp.float32)]).reshape(n_epochs, e)
    return epochs, valid


def _autoscale_inputs(cfg: ClusterConfig, asc: Autoscale):
    """The per-config data the autoscaled scan consumes beyond the static
    scan's (routing, unified, cloud): initial fracs, node capacities, the
    (min_frac, max_frac, gain, spawn, retire) vector (+/-inf thresholds
    encode "node scaling off" — the decision arithmetic runs identically
    and never fires), and the initial membership — all vmappable data."""
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


def _mask_grid(mask: np.ndarray, n_events: int, epoch_events: int,
               fill: bool):
    """Pad a per-event [T, N] mask to whole epochs and reshape to
    [E, e, N] — the mask analogue of :func:`_epoch_grid` (pad rows are
    all-up / never-recovering so pad events stay no-ops)."""
    e = epoch_events
    n_epochs = -(-n_events // e)
    pad = n_epochs * e - n_events
    if pad:
        mask = np.concatenate(
            [mask, np.full((pad, mask.shape[1]), fill, bool)])
    return jnp.asarray(mask.reshape(n_epochs, e, mask.shape[1]))


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


# The implementations below are shared by the deprecated public names and
# the ``repro.sim`` front door (which must not trip its own deprecation
# warnings).

def _simulate_cluster_jax(cfg: ClusterConfig, trace: Trace,
                          rng_seed: int = 0, mode: str = "gather",
                          telemetry: int | None = None,
                          chains: ChainPlan | None = None):
    """Returns the ``ClusterResult`` — or, with ``telemetry`` (a window
    length in events) and/or ``chains`` (a compiled :class:`ChainPlan`),
    ``(result, extras)`` with ``"telemetry"`` window arrays /
    ``"chains"`` per-chain arrays."""
    check_step_mode(mode)
    rz_on = cfg.resize_policy is not None
    with TraceAnnotation("sim.prep", nodes=cfg.n_nodes):
        events = cluster_events(trace, cfg.n_nodes, resize=rz_on)
        cloud_cold = cloud_cold_draws(len(trace), cfg.cloud_cold_prob,
                                      rng_seed)
        args = (init_cluster(cfg), events, jnp.int32(int(cfg.routing)),
                jnp.asarray(cfg.unified, bool), _cloud_vec(cfg))
        n_w = (None if telemetry is None
               else _n_windows(len(trace), telemetry))
        if telemetry is not None or chains is not None:
            args = args + ((None, None) if telemetry is None else
                           (_widx(len(trace), telemetry),
                            _tel_init(n_w, cfg.n_nodes)))
        if chains is not None:
            args = args + (_chain_xs(chains), jnp.asarray(cloud_cold),
                           jnp.asarray(chains.deadline),
                           _chain_init(chains.n_chains))
    with TraceAnnotation("sim.dispatch", h2d_bytes=_host_nbytes(args)):
        outs = _run_cluster(*args, n_nodes=cfg.n_nodes, mode=mode)
    with TraceAnnotation("sim.wait"):
        jax.block_until_ready(outs[:2])
    with TraceAnnotation("sim.fetch",
                         d2h_bytes=outs[0].nbytes + outs[1].nbytes):
        node, outcome = np.asarray(outs[0]), np.asarray(outs[1])
    with TraceAnnotation("sim.result",
                         **_result_counts(cfg, trace, node, outcome)):
        result = build_result(cfg, trace, node, outcome, cloud_cold)
        if telemetry is None and chains is None and not rz_on:
            return result
        extras = {}
        if telemetry is not None:
            extras["telemetry"] = _tel_np(outs[2], n_w)
        if chains is not None:
            extras["chains"] = _chain_np(outs[-2] if rz_on else outs[-1],
                                         chains.n_chains)
        if rz_on:
            extras["vertical"] = _vert_np(outs[-1])
        return result, extras


def _simulate_cluster_ref(cfg: ClusterConfig, trace: Trace,
                          rng_seed: int = 0,
                          telemetry: int | None = None,
                          chains: ChainPlan | None = None):
    cloud_cold = cloud_cold_draws(len(trace), cfg.cloud_cold_prob, rng_seed)
    out = cluster_outcomes_ref(cfg, trace, telemetry=telemetry,
                               chains=chains,
                               chain_cold=(cloud_cold if chains is not None
                                           else None))
    if (telemetry is None and chains is None
            and cfg.resize_policy is None):
        node, outcome = out
        return build_result(cfg, trace, node, outcome, cloud_cold)
    node, outcome, extras = out
    return build_result(cfg, trace, node, outcome, cloud_cold), extras


def _stack_configs(configs, what: str):
    """Validate the shared stacked shapes (``n_nodes``/``max_slots``) and
    stack the per-config scan inputs — the one place both sweep
    entrypoints (static and autoscaled) build their vmapped data from."""
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


def _stack_tel(n_windows: int, n_nodes: int, lanes: int) -> TelAcc:
    """One zeroed accumulator per sweep lane, stacked on a leading axis
    (lanes in a group share the window count, so the stack is dense)."""
    return jax.tree_util.tree_map(
        lambda a: jnp.zeros((lanes,) + a.shape, a.dtype),
        _tel_init(n_windows, n_nodes))


def _sweep_chain_data(chains, configs, t_len: int, rng_seed: int):
    """Stacked per-lane chain inputs for a sweep bucket: one
    ``ChainPlan`` per config (same trace -> shared event structure),
    per-lane deadlines and per-lane common-random-number cloud cold
    draws.  Returns ``(plan, clouds, chain_args)``."""
    chains = list(chains)
    if len(chains) != len(configs) or any(p is None for p in chains):
        raise ValueError("chain sweep: need one ChainPlan per config")
    plan = chains[0]
    if any(p.n_chains != plan.n_chains for p in chains):
        raise ValueError("chain sweep: lanes must share the trace's "
                         "chain structure")
    clouds = [cloud_cold_draws(t_len, c.cloud_cold_prob, rng_seed)
              for c in configs]
    chain_args = (_chain_xs(plan), jnp.asarray(np.stack(clouds)),
                  jnp.asarray(np.stack([p.deadline for p in chains])),
                  _stack_chain(plan.n_chains, len(configs)))
    return plan, clouds, chain_args


def _sweep_cluster(trace: Trace, configs, rng_seed: int = 0,
                   mode: str = "gather", telemetry: int | None = None,
                   chains=None, devices: int | None = None,
                   placement: dict | None = None):
    """Returns one ``ClusterResult`` per config — or, with ``telemetry``
    and/or ``chains`` (one compiled ``ChainPlan`` per config), one
    ``(result, extras)`` pair per config.  ``devices`` shards the lane
    axis across a device mesh (results stay bit-identical; pad lanes are
    sliced off here by never reading their rows); ``placement`` receives
    the lane rows per device (see :func:`_lanes_np`)."""
    check_step_mode(mode)
    devices = check_devices(devices)
    configs, n, pools, routing, unified, cloud = _stack_configs(
        configs, "sweep_cluster")
    rz_on = configs[0].resize_policy is not None
    events = cluster_events(trace, n, resize=rz_on)
    tel_on, ch_on = telemetry is not None, chains is not None
    args = (pools, events, routing, unified, cloud)
    n_w = None if not tel_on else _n_windows(len(trace), telemetry)
    if tel_on or ch_on:
        args = args + ((None, None) if not tel_on else
                       (_widx(len(trace), telemetry),
                        _stack_tel(n_w, n, len(configs))))
    if ch_on:
        plan, clouds, chain_args = _sweep_chain_data(
            chains, configs, len(trace), rng_seed)
        args = args + chain_args
    args = _pad_lanes(args, _sweep_axes(tel_on, ch_on),
                      _lane_pad(len(configs), devices))
    outs = _sweep_runner(n, mode, tel=tel_on, chain=ch_on,
                         devices=devices)(*args)
    nodes, outcomes = _lanes_np(outs[0], placement), np.asarray(outs[1])
    out = []
    for g, c in enumerate(configs):
        cc = (clouds[g] if ch_on
              else cloud_cold_draws(len(trace), c.cloud_cold_prob,
                                    rng_seed))
        res = build_result(c, trace, nodes[g], outcomes[g], cc)
        extras = {}
        if tel_on:
            lane = jax.tree_util.tree_map(lambda a: a[g], outs[2])
            extras["telemetry"] = _tel_np(lane, n_w)
        if ch_on:
            lane = jax.tree_util.tree_map(
                lambda a: a[g], outs[-2] if rz_on else outs[-1])
            extras["chains"] = _chain_np(lane, plan.n_chains)
        if rz_on:
            extras["vertical"] = _vert_np(
                tuple(np.asarray(a)[g] for a in outs[-1]))
        out.append((res, extras) if extras else res)
    return out


def _drop_size(cfg: ClusterConfig) -> float:
    """A pad-event size no pool of this cluster can ever host, even after
    the autoscaler grows it to the whole node."""
    return float(max(cfg.node_mb)) * 10.0


def _simulate_cluster_failures_jax(
        cfg: ClusterConfig, failures: Failures, trace: Trace,
        rng_seed: int = 0, mode: str = "gather",
        telemetry: int | None = None,
        chains: ChainPlan | None = None) -> tuple[ClusterResult, dict]:
    """Failure-injected twin of :func:`_simulate_cluster_jax`: returns
    (ClusterResult, extras) with the compiled ``node_up`` mask and the
    per-node ``invalidated`` resident counts (plus ``"telemetry"`` window
    arrays / ``"chains"`` per-chain arrays when requested)."""
    check_step_mode(mode)
    rz_on = cfg.resize_policy is not None
    up, recover = _failure_masks(failures, trace, cfg.n_nodes)
    cloud_cold = cloud_cold_draws(len(trace), cfg.cloud_cold_prob, rng_seed)
    tel_on, ch_on = telemetry is not None, chains is not None
    args = (init_cluster(cfg),
            cluster_events(trace, cfg.n_nodes, resize=rz_on),
            jnp.asarray(up), jnp.asarray(recover),
            jnp.int32(int(cfg.routing)), jnp.asarray(cfg.unified, bool),
            _cloud_vec(cfg))
    n_w = None if not tel_on else _n_windows(len(trace), telemetry)
    if tel_on or ch_on:
        args = args + ((None, None) if not tel_on else
                       (_widx(len(trace), telemetry),
                        _tel_init(n_w, cfg.n_nodes)))
    if ch_on:
        args = args + (_chain_xs(chains), jnp.asarray(cloud_cold),
                       jnp.asarray(chains.deadline),
                       _chain_init(chains.n_chains))
    outs = _run_failures(*args, n_nodes=cfg.n_nodes, mode=mode)
    node, outcome, inval = outs[0], outs[1], outs[2]
    extras = {}
    if tel_on:
        extras["telemetry"] = _tel_np(outs[3], n_w)
    if ch_on:
        extras["chains"] = _chain_np(outs[-2] if rz_on else outs[-1],
                                     chains.n_chains)
    if rz_on:
        extras["vertical"] = _vert_np(outs[-1])
    extras.update(invalidated=np.asarray(inval, np.int64), node_up=up)
    return (build_result(cfg, trace, np.asarray(node), np.asarray(outcome),
                         cloud_cold), extras)


def _simulate_cluster_failures_ref(
        cfg: ClusterConfig, failures: Failures, trace: Trace,
        rng_seed: int = 0, telemetry: int | None = None,
        chains: ChainPlan | None = None) -> tuple[ClusterResult, dict]:
    cloud_cold = cloud_cold_draws(len(trace), cfg.cloud_cold_prob, rng_seed)
    node, outcome, extras = cluster_outcomes_ref(
        cfg, trace, failures=failures, telemetry=telemetry, chains=chains,
        chain_cold=(cloud_cold if chains is not None else None))
    return build_result(cfg, trace, node, outcome, cloud_cold), extras


def _sweep_cluster_failures(
        trace: Trace, configs, failures, rng_seed: int = 0,
        mode: str = "gather", telemetry: int | None = None,
        chains=None, devices: int | None = None,
        placement: dict | None = None) -> list[tuple[ClusterResult, dict]]:
    """Vmapped sweep over failure-injected configs: each lane's compiled
    up/recover masks ride as data (lanes bucket by mask shape, which the
    shared trace and ``n_nodes`` pin)."""
    check_step_mode(mode)
    devices = check_devices(devices)
    failures = list(failures)
    configs, n, pools, routing, unified, cloud = _stack_configs(
        configs, "failure sweep")
    if len(configs) != len(failures):
        raise ValueError("failure sweep: need one Failures per config")
    masks = [_failure_masks(f, trace, n) for f in failures]
    up = np.stack([m[0] for m in masks])
    recover = np.stack([m[1] for m in masks])
    tel_on, ch_on = telemetry is not None, chains is not None
    rz_on = configs[0].resize_policy is not None
    args = (pools, cluster_events(trace, n, resize=rz_on),
            jnp.asarray(up), jnp.asarray(recover), routing, unified, cloud)
    n_w = None if not tel_on else _n_windows(len(trace), telemetry)
    if tel_on or ch_on:
        args = args + ((None, None) if not tel_on else
                       (_widx(len(trace), telemetry),
                        _stack_tel(n_w, n, len(configs))))
    if ch_on:
        plan, clouds, chain_args = _sweep_chain_data(
            chains, configs, len(trace), rng_seed)
        args = args + chain_args
    args = _pad_lanes(args, _sweep_failures_axes(tel_on, ch_on),
                      _lane_pad(len(configs), devices))
    outs = _sweep_failures_runner(n, mode, tel=tel_on, chain=ch_on,
                                  devices=devices)(*args)
    nodes, outcomes = _lanes_np(outs[0], placement), np.asarray(outs[1])
    invals = np.asarray(outs[2], np.int64)
    out = []
    for g, c in enumerate(configs):
        extras = {"invalidated": invals[g], "node_up": up[g]}
        if tel_on:
            lane = jax.tree_util.tree_map(lambda a: a[g], outs[3])
            extras["telemetry"] = _tel_np(lane, n_w)
        if ch_on:
            lane = jax.tree_util.tree_map(
                lambda a: a[g], outs[-2] if rz_on else outs[-1])
            extras["chains"] = _chain_np(lane, plan.n_chains)
        if rz_on:
            extras["vertical"] = _vert_np(
                tuple(np.asarray(a)[g] for a in outs[-1]))
        cc = (clouds[g] if ch_on
              else cloud_cold_draws(len(trace), c.cloud_cold_prob,
                                    rng_seed))
        out.append((build_result(c, trace, nodes[g], outcomes[g], cc),
                    extras))
    return out


# --------------------------------------------------------------------------
# chunked-scan execution mode: million-invocation replays, bounded memory
# --------------------------------------------------------------------------
# ``simulate(..., chunk_events=...)`` splits the trace host-side into
# fixed-size chunks and runs each through the SAME per-event scan step,
# threading the pool state (and, with failures, the invalidation counters)
# between chunks as a donated carry.  ``lax.scan`` is sequential, so a
# chunked run is bit-identical to the monolithic scan by construction —
# regression-tested in tests/test_replay.py — while peak device memory is
# bounded by one chunk of events + outputs instead of the whole trace.
# The final partial chunk is padded with the same guaranteed-drop no-op
# events the autoscale epoch grid uses (they never touch pool state) so
# every chunk runs the one compiled program.

def _run_cluster_chunk_impl(carry, events: ClusterEvent,
                            routing: jax.Array, unified: jax.Array,
                            cloud: jax.Array, widx=None, cxs=None,
                            ccold=None, cdl=None, *,
                            n_nodes: int, mode: str):
    """One chunk of the static trace — ``_run_cluster_impl`` that also
    returns the final carry so the next chunk can pick it up.  The carry
    is the pool state, extended to ``(pools[, TelAcc][, ChainAcc])`` with
    telemetry (``widx`` set) and/or chains (``cxs`` set): global window
    indices and the threaded chain accumulator make events land in the
    same windows / chain rows a monolithic scan would."""
    step = _make_step(routing, unified, cloud, n_nodes, mode)
    tel_on, ch_on = widx is not None, cxs is not None
    if not tel_on and not ch_on:
        carry, (nodes, outcomes) = jax.lax.scan(step, carry, events)
        return carry, nodes, outcomes
    n_up = jnp.int32(n_nodes)

    def s(c, x):
        pools = c[0]
        acc = c[1] if tel_on else None
        chain = c[-1] if ch_on else None
        ev = x[0]
        if ch_on:
            cx, cc = x[-2], x[-1]
            slack, stg = _chain_pre(chain, cdl, cx)
            pools, (node, outcome) = step(pools, ev, None, slack, stg)
            chain, miss = _chain_event(chain, cx, cc, cdl, ev, outcome,
                                       cloud)
        else:
            pools, (node, outcome) = step(pools, ev)
            miss = jnp.int32(0)
        if tel_on:
            acc = _tel_event(acc, x[1], ev, outcome, pools, n_nodes,
                             n_up, n_up, jnp.int32(0), miss)
        nc = ((pools,) + ((acc,) if tel_on else ())
              + ((chain,) if ch_on else ()))
        return nc, (node, outcome)

    xs = ((events,) + ((widx,) if tel_on else ())
          + ((cxs, ccold) if ch_on else ()))
    carry, (nodes, outcomes) = jax.lax.scan(s, carry, xs)
    return carry, nodes, outcomes


def _run_failures_chunk_impl(carry, events: ClusterEvent, up: jax.Array,
                             recover: jax.Array, routing: jax.Array,
                             unified: jax.Array, cloud: jax.Array,
                             widx=None, cxs=None, ccold=None, cdl=None,
                             *, n_nodes: int, mode: str):
    """One chunk of the failure-injected trace; the carry is
    ``(pools, invalidated i32[N][, TelAcc][, ChainAcc])``."""
    step = _make_step(routing, unified, cloud, n_nodes, mode)
    tel_on, ch_on = widx is not None, cxs is not None

    def s(c, x):
        pools, inval = c[0], c[1]
        acc = c[2] if tel_on else None
        chain = c[-1] if ch_on else None
        ev, u, r = x[0], x[1], x[2]
        cnt, pools = _invalidate_nodes(pools, r, n_nodes)
        if ch_on:
            cx, cc = x[-2], x[-1]
            slack, stg = _chain_pre(chain, cdl, cx)
            pools, (node, outcome) = step(pools, ev, u, slack, stg)
            chain, miss = _chain_event(chain, cx, cc, cdl, ev, outcome,
                                       cloud)
        else:
            pools, (node, outcome) = step(pools, ev, u)
            miss = jnp.int32(0)
        if tel_on:
            acc = _tel_event(acc, x[3], ev, outcome, pools, n_nodes,
                             jnp.sum(u).astype(jnp.int32),
                             jnp.int32(n_nodes), jnp.sum(cnt), miss)
        nc = ((pools, inval + cnt) + ((acc,) if tel_on else ())
              + ((chain,) if ch_on else ()))
        return nc, (node, outcome)

    xs = ((events, up, recover) + ((widx,) if tel_on else ())
          + ((cxs, ccold) if ch_on else ()))
    carry, (nodes, outcomes) = jax.lax.scan(s, carry, xs)
    return carry, nodes, outcomes


@functools.lru_cache(maxsize=None)
def _chunk_runner(n_nodes: int, mode: str):
    """Jitted chunk step with the carry donated: the previous chunk's pool
    buffers are reused in place, so a replay's footprint stays flat no
    matter how many chunks it spans."""
    return jax.jit(functools.partial(_run_cluster_chunk_impl,
                                     n_nodes=n_nodes, mode=mode),
                   donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _failures_chunk_runner(n_nodes: int, mode: str):
    return jax.jit(functools.partial(_run_failures_chunk_impl,
                                     n_nodes=n_nodes, mode=mode),
                   donate_argnums=(0,))


def _chunk_chain_axes(tel: bool, chain: bool) -> tuple:
    """Trailing vmap in_axes for the optional chunk args
    ``(widx[, cxs, ccold, cdl])`` — the accumulators ride the stacked
    (axis-0) carry, so only the per-chunk data appears here: window
    indices and chain event data are shared, cold draws and deadlines are
    per-lane."""
    axes = ()
    if tel or chain:
        axes += (None,)            # widx (None arg when only chains on)
    if chain:
        axes += (None, 0, 0)       # cxs, ccold, cdl
    return axes


def _sweep_chunk_axes(tel: bool, chain: bool) -> tuple:
    return (0, None, 0, 0, 0) + _chunk_chain_axes(tel, chain)


def _sweep_failures_chunk_axes(tel: bool, chain: bool) -> tuple:
    return (0, None, 0, 0, 0, 0, 0) + _chunk_chain_axes(tel, chain)


@functools.lru_cache(maxsize=None)
def _sweep_chunk_runner(n_nodes: int, mode: str, tel: bool = False,
                        chain: bool = False, devices: int | None = None):
    """Vmapped chunk step for sweeps: lanes stack on the carry/config axes,
    the chunk's events are shared, and the stacked carry is donated.
    The leading ``0`` is a pytree prefix, so it maps every carry leaf —
    plain pools, ``(pools, TelAcc)`` or ``(pools[, TelAcc], ChainAcc)``
    alike.  ``devices`` shards the lane axis; the donated carry then
    lives sharded across the mesh and is reused shard-in-place chunk
    over chunk."""
    axes = _sweep_chunk_axes(tel, chain)
    return jax.jit(_shard_lanes(jax.vmap(
        functools.partial(_run_cluster_chunk_impl, n_nodes=n_nodes,
                          mode=mode),
        in_axes=axes), axes, devices),
        donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _sweep_failures_chunk_runner(n_nodes: int, mode: str,
                                 tel: bool = False, chain: bool = False,
                                 devices: int | None = None):
    axes = _sweep_failures_chunk_axes(tel, chain)
    return jax.jit(_shard_lanes(jax.vmap(
        functools.partial(_run_failures_chunk_impl, n_nodes=n_nodes,
                          mode=mode),
        in_axes=axes), axes, devices),
        donate_argnums=(0,))


def _host_events(trace: Trace, n_nodes: int, *,
                 resize: bool = False) -> ClusterEvent:
    """Numpy twin of :func:`cluster_events`: the whole trace stays host-
    side and chunked replay uploads one slice at a time."""
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


def _chunk_slice(ev: ClusterEvent, s: int, e: int, chunk: int,
                 drop_size: float) -> ClusterEvent:
    """Slice ``[s, e)`` out of host-side events, padding a final partial
    chunk to ``chunk`` with guaranteed-drop no-ops (same fill rule as
    :func:`_epoch_grid`)."""
    sl = jax.tree_util.tree_map(lambda a: a[s:e], ev)
    pad = chunk - (e - s)
    if pad:
        last_t = sl.t[-1] if e > s else np.float32(0.0)
        fills = ClusterEvent(t=last_t, func_id=-2, size=drop_size, cls=0,
                             warm=0.0, cold=0.0, h1=0, h2=0,
                             used=None if ev.used is None else 0.0)
        sl = jax.tree_util.tree_map(
            lambda a, f: np.concatenate([a, np.full(pad, f, a.dtype)]),
            sl, fills)
    return sl


def _chunk_mask(mask: np.ndarray, s: int, e: int, chunk: int, fill: bool,
                axis: int = 0) -> np.ndarray:
    """Chunk-slice a per-event mask along ``axis``, padding like
    :func:`_chunk_slice` (pad rows all-up / never-recovering)."""
    sl = np.take(mask, np.arange(s, e), axis=axis)
    pad = chunk - (e - s)
    if pad:
        shape = list(sl.shape)
        shape[axis] = pad
        sl = np.concatenate([sl, np.full(shape, fill, bool)], axis=axis)
    return sl


def _simulate_cluster_chunked_jax(
        cfg: ClusterConfig, trace: Trace, rng_seed: int = 0,
        mode: str = "gather", chunk_events: int = 65536,
        failures: Failures | None = None,
        telemetry: int | None = None,
        chains: ChainPlan | None = None):
    """Chunked twin of ``_simulate_cluster_jax`` /
    ``_simulate_cluster_failures_jax`` — same return shapes, bit-identical
    outcomes, peak memory bounded by one chunk.  Telemetry and chain
    accumulators thread through the donated carry (with *global* window
    indices / chain rows), so the windows and per-chain metrics match the
    monolithic scan for any chunk size."""
    check_step_mode(mode)
    chunk = check_chunk_events(chunk_events)
    n, t_len = cfg.n_nodes, len(trace)
    rz_on = cfg.resize_policy is not None
    tel_on, ch_on = telemetry is not None, chains is not None
    with TraceAnnotation("sim.prep", nodes=n):
        ev_np = _host_events(trace, n, resize=rz_on)
        routing = jnp.int32(int(cfg.routing))
        unified = jnp.asarray(cfg.unified, bool)
        cloud = _cloud_vec(cfg)
        drop = _drop_size(cfg)
        n_w = None if not tel_on else _n_windows(t_len, telemetry)
        cloud_cold = cloud_cold_draws(t_len, cfg.cloud_cold_prob, rng_seed)
        cxs_np = _chain_xs_np(chains) if ch_on else None
        cdl = jnp.asarray(chains.deadline) if ch_on else None
        nodes_out = np.empty(t_len, np.int32)
        outcomes_out = np.empty(t_len, np.int32)
        if failures is None:
            run = _chunk_runner(n, mode)
            carry = init_cluster(cfg)
            if tel_on or ch_on:
                carry = ((carry,) + ((_tel_init(n_w, n),) if tel_on else ())
                         + ((_chain_init(chains.n_chains),) if ch_on
                            else ()))
        else:
            run = _failures_chunk_runner(n, mode)
            up_full, rec_full = _failure_masks(failures, trace, n)
            carry = ((init_cluster(cfg), jnp.zeros((n,), jnp.int32))
                     + ((_tel_init(n_w, n),) if tel_on else ())
                     + ((_chain_init(chains.n_chains),) if ch_on else ()))
    for i, s in enumerate(range(0, t_len, chunk)):
        e = min(s + chunk, t_len)
        with TraceAnnotation("sim.chunk", index=i, events=e - s,
                             pad=chunk - (e - s)):
            with TraceAnnotation("sim.slice"):
                ev = _chunk_slice(ev_np, s, e, chunk, drop)
                fmask = () if failures is None else (
                    jnp.asarray(_chunk_mask(up_full, s, e, chunk, True)),
                    jnp.asarray(_chunk_mask(rec_full, s, e, chunk, False)))
                kw = ({} if not tel_on
                      else {"widx": _chunk_widx(s, e, chunk, telemetry,
                                                n_w)})
                if ch_on:
                    kw.update(cxs=_chunk_chain(cxs_np, chains.n_chains, s,
                                               e, chunk),
                              ccold=_chunk_pad(cloud_cold, s, e, chunk,
                                               False),
                              cdl=cdl)
            with TraceAnnotation("sim.dispatch",
                                 h2d_bytes=_host_nbytes((ev, kw))):
                carry, nodes, outcomes = run(carry, ev, *fmask, routing,
                                             unified, cloud, **kw)
            with TraceAnnotation("sim.wait"):
                jax.block_until_ready((nodes, outcomes))
            with TraceAnnotation("sim.fetch",
                                 d2h_bytes=2 * nodes_out[s:e].nbytes):
                nodes_out[s:e] = np.asarray(nodes[:e - s])
                outcomes_out[s:e] = np.asarray(outcomes[:e - s])
    with TraceAnnotation("sim.result",
                         **_result_counts(cfg, trace, nodes_out,
                                          outcomes_out)):
        result = build_result(cfg, trace, nodes_out, outcomes_out,
                              cloud_cold)
        extras = {}
        if tel_on:
            extras["telemetry"] = _tel_np(
                carry[1 if failures is None else 2], n_w)
        if ch_on:
            extras["chains"] = _chain_np(carry[-1], chains.n_chains)
        if rz_on:
            # the accumulators ride the threaded carry's pool state, so
            # the final chunk's pools already hold the whole-trace totals
            p_end = carry if isinstance(carry, PoolState) else carry[0]
            extras["vertical"] = _vert_np(_vert_of(p_end)[0])
        if failures is None:
            return result if not extras else (result, extras)
        extras.update(invalidated=np.asarray(carry[1], np.int64),
                      node_up=up_full)
        return result, extras


def lower_chunk_program(cfg: ClusterConfig, trace: Trace,
                        mode: str = "gather", chunk_events: int = 65536):
    """The first-chunk program of a static chunked run (what
    ``simulate(..., chunk_events=...)`` executes), lowered but not run,
    to inspect what the compiler is given — e.g. whether
    ``mode="fused"`` holds the Pallas kernel as a ``tpu_custom_call``."""
    check_step_mode(mode)
    chunk = check_chunk_events(chunk_events)
    ev = _chunk_slice(_host_events(trace, cfg.n_nodes), 0,
                      min(chunk, len(trace)), chunk, _drop_size(cfg))
    return _chunk_runner(cfg.n_nodes, mode).lower(
        init_cluster(cfg), ev, jnp.int32(int(cfg.routing)),
        jnp.asarray(cfg.unified, bool), _cloud_vec(cfg))


def _sweep_cluster_chunked(trace: Trace, configs, rng_seed: int = 0,
                           mode: str = "gather",
                           chunk_events: int = 65536,
                           failures=None, telemetry: int | None = None,
                           chains=None, devices: int | None = None,
                           placement: dict | None = None):
    """Chunked twin of ``_sweep_cluster`` / ``_sweep_cluster_failures``:
    the chunk loop threads one *stacked* donated carry across all lanes.
    With ``failures`` (one ``Failures``/None per config), ``telemetry``
    or ``chains`` returns ``(result, extras)`` pairs, else plain
    results.  ``devices`` shards the lane axis (pad lanes included in the
    donated carry, sliced off per chunk below)."""
    check_step_mode(mode)
    chunk = check_chunk_events(chunk_events)
    devices = check_devices(devices)
    with TraceAnnotation("sim.prep"):
        failing = failures is not None
        telw = telemetry
        tel_on, ch_on = telw is not None, chains is not None
        configs, n, pools, routing, unified, cloud = _stack_configs(
            configs, "chunked sweep")
        rz_on = configs[0].resize_policy is not None
        t_len, lanes = len(trace), len(configs)
        pad = _lane_pad(lanes, devices)
        lanes_p = lanes + pad
        pools = _pad_tree(pools, pad)
        routing, unified, cloud = (_pad_tree(a, pad)
                                   for a in (routing, unified, cloud))
        ev_np = _host_events(trace, n, resize=rz_on)
        drop = max(_drop_size(c) for c in configs)
        n_w = None if telw is None else _n_windows(t_len, telw)
        clouds = plan = cxs_np = cdl = None
        if ch_on:
            plan, clouds, _ = _sweep_chain_data(chains, configs, t_len,
                                                rng_seed)
            cxs_np = _chain_xs_np(plan)
            cdl = _pad_tree(jnp.asarray(
                np.stack([p.deadline for p in list(chains)])), pad)
            clouds_p = clouds + clouds[:1] * pad
        nodes_out = np.empty((lanes, t_len), np.int32)
        outcomes_out = np.empty((lanes, t_len), np.int32)
        if failing:
            failures = list(failures)
            if len(failures) != lanes:
                raise ValueError("chunked failure sweep: need one Failures "
                                 "(or None) per config")
            masks = [_failure_masks(f, trace, n) for f in failures]
            up_full = np.stack([m[0] for m in masks])       # [L, T, N]
            rec_full = np.stack([m[1] for m in masks])
            if pad:
                up_p = np.concatenate(
                    [up_full, np.repeat(up_full[:1], pad, axis=0)])
                rec_p = np.concatenate(
                    [rec_full, np.repeat(rec_full[:1], pad, axis=0)])
            else:
                up_p, rec_p = up_full, rec_full
            run = _sweep_failures_chunk_runner(n, mode, tel=tel_on,
                                               chain=ch_on, devices=devices)
            carry = (pools, jnp.zeros((lanes_p, n), jnp.int32))
            if tel_on:
                carry = carry + (_stack_tel(n_w, n, lanes_p),)
            if ch_on:
                carry = carry + (_stack_chain(plan.n_chains, lanes_p),)
        else:
            run = _sweep_chunk_runner(n, mode, tel=tel_on, chain=ch_on,
                                      devices=devices)
            if tel_on or ch_on:
                carry = ((pools,)
                         + ((_stack_tel(n_w, n, lanes_p),) if tel_on else ())
                         + ((_stack_chain(plan.n_chains, lanes_p),)
                            if ch_on else ()))
            else:
                carry = pools
    for i, s in enumerate(range(0, t_len, chunk)):
        e = min(s + chunk, t_len)
        with TraceAnnotation("sim.chunk", index=i, events=e - s,
                             pad=chunk - (e - s)):
            with TraceAnnotation("sim.slice"):
                ev = _chunk_slice(ev_np, s, e, chunk, drop)
                fmask = () if not failing else (
                    jnp.asarray(_chunk_mask(up_p, s, e, chunk, True,
                                            axis=1)),
                    jnp.asarray(_chunk_mask(rec_p, s, e, chunk, False,
                                            axis=1)))
                wx = ()
                if tel_on or ch_on:
                    wx += (None if telw is None
                           else _chunk_widx(s, e, chunk, telw, n_w),)
                if ch_on:
                    wx += (_chunk_chain(cxs_np, plan.n_chains, s, e, chunk),
                           jnp.stack([_chunk_pad(cc, s, e, chunk, False)
                                      for cc in clouds_p]), cdl)
            with TraceAnnotation("sim.dispatch",
                                 h2d_bytes=_host_nbytes((ev, wx))):
                carry, nodes, outcomes = run(carry, ev, *fmask, routing,
                                             unified, cloud, *wx)
            with TraceAnnotation("sim.wait"):
                jax.block_until_ready((nodes, outcomes))
            with TraceAnnotation("sim.fetch",
                                 d2h_bytes=nodes.nbytes + outcomes.nbytes):
                nodes_out[:, s:e] = _lanes_np(nodes, placement)[:lanes,
                                                                :e - s]
                outcomes_out[:, s:e] = np.asarray(outcomes)[:lanes, :e - s]
    with TraceAnnotation("sim.result"):
        out = []
        invals = (np.asarray(carry[1], np.int64) if failing else None)
        tels = None
        if tel_on:
            tels = carry[2] if failing else carry[1]
        chs = carry[-1] if ch_on else None
        p_end = carry if isinstance(carry, PoolState) else carry[0]
        for g, c in enumerate(configs):
            cc = (clouds[g] if ch_on
                  else cloud_cold_draws(t_len, c.cloud_cold_prob, rng_seed))
            res = build_result(c, trace, nodes_out[g], outcomes_out[g], cc)
            extras = {}
            if tel_on:
                lane = jax.tree_util.tree_map(lambda a: a[g], tels)
                extras["telemetry"] = _tel_np(lane, n_w)
            if ch_on:
                lane = jax.tree_util.tree_map(lambda a: a[g], chs)
                extras["chains"] = _chain_np(lane, plan.n_chains)
            if rz_on:
                extras["vertical"] = _vert_np(
                    tuple(np.asarray(a)[g] for a in _vert_of(p_end)[0]))
            if failing:
                extras.update(invalidated=invals[g], node_up=up_full[g])
            out.append((res, extras) if extras else res)
        return out


def _autoscale_extras(actives, inval, up, failures) -> dict:
    return {"invalidated": np.asarray(inval, np.int64),
            "node_up": up if failures is not None else None,
            "active": np.asarray(actives, bool)}


def _simulate_cluster_autoscale_jax(
        cfg: ClusterConfig, asc: Autoscale, trace: Trace, rng_seed: int = 0,
        mode: str = "gather", failures: Failures | None = None,
        telemetry: int | None = None, chains: ChainPlan | None = None
        ) -> tuple[ClusterResult, np.ndarray, dict]:
    """Autoscaled twin of :func:`_simulate_cluster_jax`: returns
    (ClusterResult, fracs f32[E, N], extras) — extras carries the
    membership trajectory (``active`` bool[E, N]), per-node
    ``invalidated`` resident counts, the ``node_up`` failure mask
    (None without a schedule), and the ``telemetry`` window arrays /
    ``chains`` per-chain arrays when requested."""
    check_step_mode(mode)
    n_events = len(trace)
    e = asc.epoch_events
    rz_on = cfg.resize_policy is not None
    epochs, valid = _epoch_grid(
        cluster_events(trace, cfg.n_nodes, resize=rz_on),
        n_events, e, _drop_size(cfg))
    masked = failures is not None
    tel_on, ch_on = telemetry is not None, chains is not None
    up = up_g = rec_g = None
    if masked:
        up, recover = _failure_masks(failures, trace, cfg.n_nodes)
        up_g = _mask_grid(up, n_events, e, True)
        rec_g = _mask_grid(recover, n_events, e, False)
    frac0, node_mb, asc_vec, active0 = _autoscale_inputs(cfg, asc)
    cloud_cold = cloud_cold_draws(n_events, cfg.cloud_cold_prob, rng_seed)
    args = (init_cluster(cfg), epochs, valid, up_g, rec_g,
            jnp.int32(int(cfg.routing)), jnp.asarray(cfg.unified, bool),
            _cloud_vec(cfg), frac0, node_mb, asc_vec, active0)
    n_w = None if not tel_on else _n_windows(n_events, telemetry)
    if tel_on or ch_on:
        args = args + ((None, None) if not tel_on else
                       (_widx_grid(n_events, e, telemetry),
                        _tel_init(n_w, cfg.n_nodes)))
    if ch_on:
        args = args + (_chain_grid(chains, n_events, e),
                       _grid_pad(cloud_cold, n_events, e, False),
                       jnp.asarray(chains.deadline),
                       _chain_init(chains.n_chains))
    outs = _run_autoscale(*args, n_nodes=cfg.n_nodes, mode=mode,
                          masked=masked)
    node, outcome, fracs, actives, inval = outs[:5]
    node = np.asarray(node).reshape(-1)[:n_events]
    outcome = np.asarray(outcome).reshape(-1)[:n_events]
    extras = _autoscale_extras(actives, inval, up, failures)
    if tel_on:
        extras["telemetry"] = _tel_np(outs[5], n_w)
    if ch_on:
        extras["chains"] = _chain_np(outs[-2] if rz_on else outs[-1],
                                     chains.n_chains)
    if rz_on:
        extras["vertical"] = _vert_np(outs[-1])
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
    """Vmapped sweep over autoscaled configs.  All configs must share
    ``n_nodes``/``max_slots`` AND all autoscales ``epoch_events`` (the
    stacked shapes); min/max/gain, node-scaling thresholds, initial
    membership, fracs, capacities, and per-lane failure masks vary as
    data."""
    check_step_mode(mode)
    devices = check_devices(devices)
    autoscales = list(autoscales)
    configs, n, pools, routing, unified, cloud = _stack_configs(
        configs, "autoscale sweep")
    if len(configs) != len(autoscales):
        raise ValueError("autoscale sweep: need one Autoscale per config")
    failures = (list(failures) if failures is not None
                else [None] * len(configs))
    if len(configs) != len(failures):
        raise ValueError("autoscale sweep: need one Failures (or None) "
                         "per config")
    e = autoscales[0].epoch_events
    if any(a.epoch_events != e for a in autoscales):
        raise ValueError("autoscale sweep: configs must share epoch_events"
                         " (sweep() buckets mixed epoch shapes for you)")
    per_cfg = [_autoscale_inputs(c, a) for c, a in zip(configs, autoscales)]
    frac0, node_mb, asc_vec, active0 = (jnp.stack([p[i] for p in per_cfg])
                                        for i in range(4))
    n_events = len(trace)
    rz_on = configs[0].resize_policy is not None
    drop_size = max(_drop_size(c) for c in configs)
    epochs, valid = _epoch_grid(cluster_events(trace, n, resize=rz_on),
                                n_events, e, drop_size)
    # any lane with a schedule forces the masked program for the group
    # (lanes without one ride along on all-up masks — same arithmetic);
    # repro.sim.sweep buckets failure-free lanes separately
    masked = any(f is not None for f in failures)
    up = [None] * len(configs)
    up_g = rec_g = None
    if masked:
        masks = [_failure_masks(f, trace, n) for f in failures]
        up = np.stack([m[0] for m in masks])
        up_g = jnp.stack([_mask_grid(m[0], n_events, e, True)
                          for m in masks])
        rec_g = jnp.stack([_mask_grid(m[1], n_events, e, False)
                           for m in masks])
    tel_on, ch_on = telemetry is not None, chains is not None
    args = (pools, epochs, valid, up_g, rec_g, routing, unified, cloud,
            frac0, node_mb, asc_vec, active0)
    n_w = None if not tel_on else _n_windows(n_events, telemetry)
    if tel_on or ch_on:
        args = args + ((None, None) if not tel_on else
                       (_widx_grid(n_events, e, telemetry),
                        _stack_tel(n_w, n, len(configs))))
    clouds = None
    if ch_on:
        chains = list(chains)
        if len(chains) != len(configs) or any(p is None for p in chains):
            raise ValueError("chain sweep: need one ChainPlan per config")
        plan = chains[0]
        clouds = [cloud_cold_draws(n_events, c.cloud_cold_prob, rng_seed)
                  for c in configs]
        args = args + (_chain_grid(plan, n_events, e),
                       jnp.stack([_grid_pad(cc, n_events, e, False)
                                  for cc in clouds]),
                       jnp.asarray(np.stack([p.deadline for p in chains])),
                       _stack_chain(plan.n_chains, len(configs)))
    args = _pad_lanes(args, _sweep_autoscale_axes(masked, tel_on, ch_on),
                      _lane_pad(len(configs), devices))
    outs = _sweep_autoscale_runner(n, mode, masked, tel=tel_on,
                                   chain=ch_on, devices=devices)(*args)
    nodes, outcomes, fracs, actives, invals = outs[:5]
    # pad lanes (if any) are dropped here: only real lane rows are read
    nodes = (_lanes_np(nodes, placement)[:len(configs)]
             .reshape(len(configs), -1)[:, :n_events])
    outcomes = (np.asarray(outcomes)[:len(configs)]
                .reshape(len(configs), -1)[:, :n_events])
    fracs = np.asarray(fracs)
    out = []
    for g, c in enumerate(configs):
        extras = _autoscale_extras(actives[g], invals[g], up[g],
                                   failures[g])
        if tel_on:
            lane = jax.tree_util.tree_map(lambda a: a[g], outs[5])
            extras["telemetry"] = _tel_np(lane, n_w)
        if ch_on:
            lane = jax.tree_util.tree_map(
                lambda a: a[g], outs[-2] if rz_on else outs[-1])
            extras["chains"] = _chain_np(lane, plan.n_chains)
        if rz_on:
            extras["vertical"] = _vert_np(
                tuple(np.asarray(a)[g] for a in outs[-1]))
        cc = (clouds[g] if ch_on
              else cloud_cold_draws(n_events, c.cloud_cold_prob, rng_seed))
        out.append((build_result(c, trace, nodes[g], outcomes[g], cc),
                    fracs[g], extras))
    return out


@deprecated("repro.sim.simulate(Scenario.cluster(...))")
def simulate_cluster_jax(cfg: ClusterConfig, trace: Trace,
                         rng_seed: int = 0,
                         mode: str = "gather") -> ClusterResult:
    """Simulate the cluster on ``trace``; one jitted scan end to end."""
    return _simulate_cluster_jax(cfg, trace, rng_seed, mode)


@deprecated("repro.sim.simulate(Scenario.cluster(...), engine='ref')")
def simulate_cluster_ref(cfg: ClusterConfig, trace: Trace,
                         rng_seed: int = 0) -> ClusterResult:
    """Numpy-oracle twin of :func:`simulate_cluster_jax` (same result
    type, sequential engine from ``core/continuum.py``)."""
    return _simulate_cluster_ref(cfg, trace, rng_seed)


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
    return _sweep_cluster(trace, configs, rng_seed, mode)
