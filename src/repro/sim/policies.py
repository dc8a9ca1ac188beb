"""Policies that live *outside* the engines — proof the registry works.

``cost_model`` is the ROADMAP's learned/cost-model routing item: instead
of hashing or load-balancing, score every node by the end-to-end latency
this request would be *predicted* to pay there, and send it to the
cheapest.  It is registered through the same public decorator a
third-party package would use; neither ``repro.core`` nor
``repro.cluster`` knows it exists, yet it runs in the jitted ``lax.scan``
engine, the numpy oracle, and vmapped sweeps (bit-identically — the
prediction is pure float32 arithmetic over the routing context).

``slack_aware`` is the chain-SLO counterpart: the first policy to read
the per-event chain context (``ctx.chain_slack``) that ``Scenario(...,
chains=...)`` threads through both engines — sticky locality for every
chain that can still meet its deadline, clean cloud shedding for the
doomed ones.
"""
from __future__ import annotations

from ..core.ieee import div
from ..core.registry import ROUTING, RouteCtx, register_routing


@register_routing("cost_model")
def cost_model(xp, ctx: RouteCtx):
    """Predicted end-to-end latency per node; cheapest wins.

    * A node whose target pool can host the container is predicted to pay
      ``p_cold * cold_cost``, with the pool's occupancy (1 - free
      fraction) as the cold-start-probability estimate: an empty pool has
      room to keep containers warm, a full one will be evicting.
    * A node that can *never* host it — or that is currently down
      (``ctx.node_up``) — will drop to the cloud, which is predicted to
      pay the round trip plus the cloud's own cold-start probability
      times the cold cost.

    Ties (e.g. several idle nodes predicting zero) resolve to the lowest
    node index in both engines (``argmin`` takes the first minimum).
    """
    frac = div(xp, ctx.free, xp.maximum(ctx.cap, xp.float32(1e-6)))
    cold_cost = ctx.cold - ctx.warm
    p_cold = xp.float32(1.0) - frac
    edge_pred = p_cold * cold_cost
    cloud_pred = ctx.cloud_rtt_s + ctx.cloud_cold_prob * cold_cost
    feasible = (ctx.cap >= ctx.size - xp.float32(1e-9)) & ctx.node_up
    return xp.argmin(xp.where(feasible, edge_pred, cloud_pred))


@register_routing("slack_aware", needs_free=False)
def slack_aware(xp, ctx: RouteCtx):
    """Chain-SLO routing: shed *doomed* chains, protect the savable ones.

    A chain whose remaining slack (``deadline - elapsed``, threaded
    through ``RouteCtx.chain_slack`` by both engines) has gone
    non-positive will miss its deadline no matter what happens next —
    but its remaining stages still *cost* the edge: routed sticky, they
    evict warm containers that chains which can still make their
    deadlines depend on.  Warm locality is so valuable here that
    re-routing *savable* work is a net loss (a re-route is an almost
    certain cold start), so the only slack signal worth acting on is
    doom — and the right action is to get doomed work off the edge
    *without touching any pool*:

    * a **down node** (``~ctx.node_up``) is the perfect dump: the engine
      offloads the request to the cloud and no pool is disturbed — under
      an outage, sticky re-steers everything (doomed chains included)
      onto the survivors and storms their pools; this policy sheds
      exactly the doomed share of that storm;
    * otherwise a node whose target pool can **never host** the
      container (``cap < size``) drops it to the cloud just as cleanly;
    * with nowhere clean to dump (all nodes up and big enough), doomed
      work stays sticky — shedding onto a live pool would evict warm
      containers, the very thing being protected.

    Everything with slack left routes plain ``sticky`` (composed via
    ``ROUTING.spec("sticky").fn``, so the decision stays bit-identical
    in the scan, the oracle, and vmapped sweeps).  Chainless events —
    and whole runs without ``chains=`` — carry infinite slack and are
    never doomed, so the policy degrades to exact ``sticky`` there.
    """
    doomed = ctx.chain_slack <= xp.float32(0.0)
    down = (~ctx.node_up).astype(xp.int32)
    have_down = xp.sum(down) > 0
    cap_dump = xp.argmin(ctx.cap)
    never_fits = ctx.cap[cap_dump] < ctx.size - xp.float32(1e-9)
    dump = xp.where(have_down, xp.argmax(down), cap_dump)
    shed = doomed & (have_down | never_fits)
    home = ROUTING.spec("sticky").fn(xp, ctx)
    return xp.where(shed, dump, home)
