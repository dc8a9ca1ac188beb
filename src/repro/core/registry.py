"""First-class policy registries: one definition, two engines.

Routing and replacement policies used to be closed enums whose semantics
were duplicated between the JAX engine (``lax.switch`` branches /
``jnp.where`` chains) and the numpy oracle (if/elif dispatch) — adding a
policy meant editing four files in lockstep.  Here each policy is ONE
registered pure function written against an array namespace ``xp`` (either
``numpy`` or ``jax.numpy``):

* the JAX engines *build* their ``lax.switch`` table / priority
  ``where``-chain from the registry at trace time, and
* the sequential oracle dispatches the very same function with ``numpy``
  scalars,

so a third-party policy is a decorator away and is automatically
bit-identical across engines (both sides run the same float32 arithmetic
on the same inputs)::

    from repro.sim import register_routing

    @register_routing("my_policy")
    def my_policy(xp, ctx):          # ctx: RouteCtx
        return xp.argmax(ctx.free)   # -> node index

Registered policies are identified by a stable integer *code* (assigned in
registration order) so they keep working as vmapped *data* in config
sweeps.  The four built-in routings and three built-in replacements are
registered here with codes matching the historical ``RoutingPolicy`` /
``Policy`` enums, which remain as aliases.

Registering a new policy invalidates the JIT caches of any engine that
baked the previous registry into a compiled program (see ``on_register``).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

from .ieee import div


class RouteCtx(NamedTuple):
    """Inputs available to a routing decision, one invocation at a time.

    Scalars are float32/int32 (numpy scalars in the oracle, traced scalars
    in the JAX scan); ``free``/``cap`` are f32[n_nodes] views of the pool
    each node would serve this request from.  ``free`` is only populated
    for policies registered with ``needs_free=True`` (the oracle skips the
    O(n_nodes) occupancy scan otherwise; the JAX engine always provides
    it).

    ``node_up`` is the live-node mask: False entries are nodes that are
    currently failed (``Scenario(..., failures=...)``) or not spawned by
    the node autoscaler.  Both engines always populate it (all-True when
    the cluster is fully static), so a policy that respects it re-steers
    around dead nodes with no engine edits.  A request routed to a down
    node is dropped to the cloud tier by the engine without touching any
    pool, so policies that ignore the mask stay correct — just lossier.

    ``chain_slack``/``chain_stage`` expose function-chain state when the
    scenario tracks chains (``Scenario(..., chains=...)``): the remaining
    slack ``deadline - elapsed_chain_latency`` (f32 seconds, ``+inf`` for
    chainless events or no-deadline chains) and the 0-based stage index
    (``-1`` for chainless events).  Both engines populate them identically
    (``+inf``/``-1`` when chains are off), so slack-aware policies like
    ``slack_aware`` run unmodified — and degrade to their slack-rich
    branch — on chainless traffic.
    """

    h1: object            # i32  sticky hash: func_id % n_nodes
    h2: object            # i32  second (Knuth multiplicative) hash
    size: object          # f32  container footprint (MB)
    cls: object           # i32  size class (0 small, 1 large)
    warm: object          # f32  warm execution time (s)
    cold: object          # f32  cold execution time (s)
    free: object          # f32[N] free MB of each node's target pool
    cap: object           # f32[N] capacity MB of each node's target pool
    cloud_rtt_s: object   # f32  edge->cloud round trip (s)
    cloud_cold_prob: object  # f32  cloud cold-start probability
    node_up: object = None   # bool[N] live-node mask (engines populate)
    chain_slack: object = None  # f32  remaining chain slack (s), +inf off
    chain_stage: object = None  # i32  stage within chain, -1 off


class ResizeCtx(NamedTuple):
    """Inputs available to a vertical-scaling (resize) decision.

    A resize policy sees one pool's per-slot state under memory pressure
    and proposes new per-resident memory limits; the engine then clamps
    the proposal (never below ``max(min_mb, used)``, never above the
    current ``alloc``, busy or empty slots untouched) and quantizes the
    shrink to whole MB so f32 byte accounting stays exact in any
    reduction order (the same quantized-trace contract the fused kernel
    relies on).

    Per-slot arrays are f32[slots] (the oracle passes f32 numpy arrays
    over its live containers; the JAX engine passes traced arrays, with
    an extra leading ``[pools]`` axis in the batched step).  The scalars
    ``min_mb``/``deficit``/``free``/``capacity`` broadcast against the
    slot axis in both layouts, so reductions inside a policy must use
    ``xp.sum(..., axis=-1, keepdims=True)``.
    """

    used: object      # f32[S]  observed usage per resident (MB)
    alloc: object     # f32[S]  current memory limit per resident (MB)
    size: object      # f32[S]  launch footprint per resident (MB)
    idle: object      # bool[S] resident and not busy (shrinkable)
    valid: object     # bool[S] slot holds a resident
    min_mb: object    # f32     configured floor for any limit
    deficit: object   # f32     bytes still needed after free (>= 0)
    free: object      # f32     pool free MB before shrinking
    capacity: object  # f32     pool capacity MB


class SlotStats(NamedTuple):
    """Per-container statistics a replacement policy may rank by.

    Lower priority = evicted first.  In the JAX pool these are f32[slots]
    arrays; in the sequential oracle they are python floats for one
    container.
    """

    last_use: object
    freq: object
    gd_pri: object        # GreedyDual priority maintained by the pool
    size: object          # container footprint (MB)
    busy_until: object


@dataclasses.dataclass(frozen=True)
class PolicySpec:
    name: str
    code: int
    fn: Callable
    needs_free: bool = True   # routing only: reads ctx.free?


class PolicyRegistry:
    """Ordered name -> code -> pure-function registry for one policy kind."""

    def __init__(self, kind: str):
        self.kind = kind
        self._specs: list[PolicySpec] = []
        self._by_name: dict[str, PolicySpec] = {}
        self._hooks: list[Callable[[], None]] = []

    # -- registration ------------------------------------------------------
    def register(self, name: str, *, needs_free: bool = True):
        """Decorator: register ``fn(xp, ctx_or_stats)`` under ``name``.

        Codes are assigned in registration order and never reused; a
        duplicate name is an error (policies are process-global).
        """
        if not isinstance(name, str) or not name:
            raise ValueError(f"{self.kind} policy name must be a non-empty "
                             f"string, got {name!r}")

        def deco(fn):
            if name in self._by_name:
                raise ValueError(
                    f"{self.kind} policy {name!r} is already registered")
            spec = PolicySpec(name=name, code=len(self._specs), fn=fn,
                              needs_free=needs_free)
            self._specs.append(spec)
            self._by_name[name] = spec
            for hook in self._hooks:
                hook()
            return fn

        return deco

    def on_register(self, hook: Callable[[], None]) -> None:
        """Run ``hook()`` after every new registration (engines use this to
        drop JIT caches that baked in the previous dispatch table)."""
        if hook not in self._hooks:
            self._hooks.append(hook)

    # -- lookup ------------------------------------------------------------
    def resolve(self, policy) -> int:
        """Name | code | IntEnum member -> registered integer code."""
        if isinstance(policy, str):
            try:
                return self._by_name[policy].code
            except KeyError:
                raise KeyError(
                    f"unknown {self.kind} policy {policy!r}; registered: "
                    f"{self.names()}") from None
        try:
            code = int(policy)
            if code != policy:   # 1.9 must not silently become policy 1
                raise ValueError
        except (TypeError, ValueError):
            raise KeyError(f"cannot resolve {self.kind} policy "
                           f"{policy!r} (want a name or an integer code)"
                           ) from None
        if not 0 <= code < len(self._specs):
            raise KeyError(f"unknown {self.kind} policy code {code}; "
                           f"registered: {self.names()}")
        return code

    def spec(self, policy) -> PolicySpec:
        return self._specs[self.resolve(policy)]

    def specs(self) -> tuple[PolicySpec, ...]:
        return tuple(self._specs)

    def names(self) -> list[str]:
        return [s.name for s in self._specs]

    def __len__(self) -> int:
        return len(self._specs)

    def __contains__(self, policy) -> bool:
        try:
            self.resolve(policy)
            return True
        except KeyError:
            return False


ROUTING = PolicyRegistry("routing")
REPLACEMENT = PolicyRegistry("replacement")
RESIZE = PolicyRegistry("resize")

register_routing = ROUTING.register
register_replacement = REPLACEMENT.register
register_resize_policy = RESIZE.register


def routing_policies() -> list[str]:
    """Names of all registered routing policies, in code order."""
    return ROUTING.names()


def replacement_policies() -> list[str]:
    """Names of all registered replacement policies, in code order."""
    return REPLACEMENT.names()


def resize_policies() -> list[str]:
    """Names of all registered resize (vertical-scaling) policies."""
    return RESIZE.names()


# --------------------------------------------------------------------------
# built-in routing policies (codes 0-3 == the historical RoutingPolicy enum)
# --------------------------------------------------------------------------
# All load comparisons are float32 so the numpy oracle and the JAX engine
# take bit-identical decisions on exact-f32 traces; policies divide with
# ``core.ieee.div``, whose JAX quotient rounds like numpy's on every
# backend (XLA:TPU's own f32 division does not always).  Every built-in
# respects ``ctx.node_up`` — on an all-up mask each reduces to its
# historical decision bit-for-bit (the masking selects the unmasked
# values exactly), so static scenarios are unchanged.

def _free_frac(xp, ctx: RouteCtx):
    return div(xp, ctx.free, xp.maximum(ctx.cap, xp.float32(1e-6)))


def _nth_masked(xp, mask, j):
    """Index of the ``j``-th True entry of ``mask`` (0-based); the shared
    re-steer helper: hash-over-survivors keeps assignments deterministic
    and as sticky as the mask allows."""
    return xp.argmax(xp.cumsum(mask.astype(xp.int32)) == j + 1)


@register_routing("sticky", needs_free=False)
def _sticky(xp, ctx: RouteCtx):
    """Per-function hash (``func_id % n_nodes``): maximum temporal
    locality — the property KiSS protects.  While the home node is down
    the hash re-steers over the up nodes only (and snaps back on
    recovery); with no node up it returns the home node, which the engine
    drops to the cloud."""
    up = ctx.node_up
    k = xp.sum(up.astype(xp.int32))
    j = xp.mod(ctx.h1, xp.maximum(k, 1))
    cand = _nth_masked(xp, up, j)
    return xp.where(up[ctx.h1], ctx.h1, xp.where(k == 0, ctx.h1, cand))


@register_routing("least_loaded")
def _least_loaded(xp, ctx: RouteCtx):
    """Highest instantaneous free fraction among the *up* nodes wins."""
    frac = xp.where(ctx.node_up, _free_frac(xp, ctx), xp.float32(-xp.inf))
    return xp.argmax(frac)


@register_routing("size_aware", needs_free=False)
def _size_aware(xp, ctx: RouteCtx):
    """Sticky-hash over the *up* nodes whose target pool can ever host
    this container (falls back to plain sticky when none can — the engine
    then drops to the cloud if that node is down or too small)."""
    can_host = ctx.cap >= ctx.size - xp.float32(1e-9)
    elig = (can_host & ctx.node_up).astype(xp.int32)
    k = xp.sum(elig)
    j = xp.mod(ctx.h1, xp.maximum(k, 1))
    cand = _nth_masked(xp, elig, j)
    return xp.where(k == 0, ctx.h1, cand)


@register_routing("power_of_two")
def _power_of_two(xp, ctx: RouteCtx):
    """Two hashes nominate two candidates; the less loaded *up* one wins
    (a down candidate scores -inf; both down falls back to ``h1`` and the
    engine drops to the cloud)."""
    frac = xp.where(ctx.node_up, _free_frac(xp, ctx), xp.float32(-xp.inf))
    return xp.where(frac[ctx.h1] >= frac[ctx.h2], ctx.h1, ctx.h2)


# --------------------------------------------------------------------------
# built-in replacement policies (codes 0-2 == the historical Policy enum)
# --------------------------------------------------------------------------

@register_replacement("lru")
def _lru(xp, s: SlotStats):
    return s.last_use


@register_replacement("greedy_dual")
def _greedy_dual(xp, s: SlotStats):
    """FaaSCache-style: priority = clock + freq * cold_cost / size, already
    maintained incrementally by the pool in ``gd_pri``."""
    return s.gd_pri


@register_replacement("freq")
def _freq(xp, s: SlotStats):
    return s.freq


def replacement_priority(xp, policy, stats: SlotStats):
    """Eviction priority for ``policy`` carried as *data* (vmappable).

    Builds a ``where``-chain over every registered replacement policy so a
    single jitted simulator sweeps policies as an int array.  The oracle,
    which holds a concrete code, dispatches directly via ``spec().fn``.

    Policy-as-data has an inherent cost: ``where`` (and ``lax.switch``
    under vmap) evaluates every registered branch per event.  Each branch
    is a few scalar f32 ops — noise next to the pool step's O(slots)
    sort — but registries are process-global, so keep policy functions
    cheap.
    """
    specs = REPLACEMENT.specs()
    out = specs[0].fn(xp, stats)
    for spec in specs[1:]:
        out = xp.where(policy == spec.code, spec.fn(xp, stats), out)
    return out


# --------------------------------------------------------------------------
# built-in resize (vertical-scaling) policies
# --------------------------------------------------------------------------
# A resize policy returns *proposed* per-slot limits; the engines clamp to
# [max(min_mb, used), alloc] and quantize the shrink to whole MB, so a
# policy never needs to enforce its own floors.

@register_resize_policy("static")
def _static(xp, ctx: ResizeCtx):
    """No-op: every resident keeps its current limit (the KiSS-static
    behaviour, but with utilization metrics recorded)."""
    return ctx.alloc


@register_resize_policy("fair_share")
def _fair_share(xp, ctx: ResizeCtx):
    """LaSS-style proportional reclamation: every idle resident gives up
    the same *fraction* of its reclaimable headroom ``alloc - max(min_mb,
    used)``, scaled so the total reclaimed just covers the deficit (or
    everything reclaimable, whichever is smaller)."""
    floor = xp.maximum(ctx.min_mb, ctx.used)
    headroom = xp.where(ctx.idle & ctx.valid,
                        xp.maximum(ctx.alloc - floor, xp.float32(0.0)),
                        xp.float32(0.0))
    total = xp.sum(headroom, axis=-1, keepdims=True)
    ratio = xp.minimum(div(xp, ctx.deficit,
                           xp.maximum(total, xp.float32(1e-6))),
                       xp.float32(1.0))
    return ctx.alloc - headroom * ratio


def resize_limits(xp, policy, ctx: ResizeCtx):
    """Proposed per-slot limits for ``policy`` carried as *data*.

    The vertical-scaling twin of :func:`replacement_priority`: a
    ``where``-chain over every registered resize policy, so resize
    policies vmap as an int array across sweep lanes.  The oracle holds a
    concrete code and dispatches the same functions directly.
    """
    specs = RESIZE.specs()
    out = specs[0].fn(xp, ctx)
    for spec in specs[1:]:
        out = xp.where(policy == spec.code, spec.fn(xp, ctx), out)
    return out


def shrink_amounts(xp, policy, ctx: ResizeCtx):
    """Per-slot shrink (MB) the engines actually apply for ``policy``.

    Runs the registered policy chain, then enforces the engine contract:
    a limit never drops below ``max(min_mb, used)``, never grows, only
    idle residents shrink, and the shrink is floored to whole MB so f32
    byte accounting stays exact in any reduction order.  Both engines
    call this one function, so a third-party resize policy is
    automatically bit-identical across them.
    """
    proposal = resize_limits(xp, policy, ctx)
    floor = xp.maximum(ctx.min_mb, ctx.used)
    headroom = xp.maximum(ctx.alloc - floor, xp.float32(0.0))
    shrink = xp.clip(ctx.alloc - proposal, xp.float32(0.0), headroom)
    return xp.where(ctx.idle & ctx.valid, xp.floor(shrink),
                    xp.float32(0.0))


def observed_usage(xp, func_id, size):
    """Deterministic per-function observed memory usage (MB).

    The simulator has no real memory telemetry, so both engines derive a
    resident's observed usage from the same pure function of its identity
    and footprint: a Knuth-hash fraction in [~0.55, ~0.95) of the launch
    footprint, floored to whole MB (keeping f32 sums of usage exact in
    any reduction order on quantized traces), and at least ``min(size,
    1)`` so a resident never observes zero.
    """
    import numpy as _np
    with _np.errstate(over="ignore"):   # uint32 hash wraps by design
        h = ((func_id.astype(xp.uint32) * xp.uint32(2654435761))
             >> xp.uint32(20))
    num = (h % xp.uint32(103)).astype(xp.float32) + xp.float32(140.0)
    u = xp.floor(size.astype(xp.float32) * num * xp.float32(1.0 / 256.0))
    return xp.maximum(u, xp.minimum(size.astype(xp.float32),
                                    xp.float32(1.0)))
