"""repro.cluster: JAX batched engine vs numpy oracle, conservation,
heterogeneous routing, step modes, and the vmapped config sweep.

These tests exercise the historical cluster entrypoints on purpose (they
are the reference implementations the ``repro.sim`` front door is
equivalence-tested against in ``test_sim_api.py``), so their deprecation
warnings are silenced module-wide.
"""
import numpy as np
import pytest

from repro.cluster import (ClusterConfig, RoutingPolicy,
                           simulate_cluster_jax, simulate_cluster_ref,
                           sweep_cluster)
from repro.core import Policy

from conftest import quantized_trace

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

ROUTINGS = list(RoutingPolicy)


def het4(routing=RoutingPolicy.STICKY, policy=Policy.LRU):
    """4 heterogeneous nodes incl. one unified-baseline node; the small
    nodes' large pools (204.8 MB) cannot ever host a 300+ MB container."""
    return ClusterConfig(node_mb=(1024.0, 1024.0, 2048.0, 4096.0),
                         small_frac=(0.8, 0.8, 0.8, 0.5),
                         unified=(False, True, False, False),
                         policy=policy, routing=routing, max_slots=64)


@pytest.mark.parametrize("routing", ROUTINGS)
def test_jax_matches_oracle_all_routings(routing):
    """Engine equivalence is exact per event: same routed node, same
    outcome, on a heterogeneous cluster with a unified node mixed in."""
    for seed in (0, 1, 2):
        rng = np.random.default_rng(seed)
        tr = quantized_trace(rng, 400)
        cfg = het4(routing)
        j = simulate_cluster_jax(cfg, tr)
        r = simulate_cluster_ref(cfg, tr)
        assert (j.node == r.node).all(), routing
        assert (j.outcome == r.outcome).all(), routing
        assert (j.per_node == r.per_node).all()
        assert np.allclose(j.latencies, r.latencies)


@pytest.mark.parametrize("policy",
                         [Policy.LRU, Policy.GREEDY_DUAL, Policy.FREQ])
def test_sixteen_node_sticky_equivalence(policy):
    """The acceptance-criterion scale: 16 heterogeneous nodes, sticky-hash
    routing, hits/misses/drops exact-match against the oracle."""
    rng = np.random.default_rng(7)
    tr = quantized_trace(rng, 1000)
    cfg = ClusterConfig(node_mb=tuple([1024.0] * 8 + [2048.0] * 4
                                      + [6144.0] * 4),
                        small_frac=(0.8,) * 16, unified=(False,) * 16,
                        policy=policy, max_slots=64)
    j = simulate_cluster_jax(cfg, tr)
    r = simulate_cluster_ref(cfg, tr)
    assert (j.node == r.node).all()
    assert (j.outcome == r.outcome).all()
    assert j.edge.__dict__ == r.edge.__dict__


@pytest.mark.parametrize("routing", ROUTINGS)
def test_metric_conservation(routing):
    """hits+misses+drops == trace length, in aggregate, per node, and per
    (node, class) against the routed-event counts."""
    rng = np.random.default_rng(3)
    tr = quantized_trace(rng, 500)
    res = simulate_cluster_jax(het4(routing), tr)
    counts = res.per_node[:, :, :3]
    assert counts.sum() == len(tr)
    assert res.edge.total_accesses == len(tr)
    cls = np.asarray(tr.cls)
    for n in range(res.cfg.n_nodes):
        routed = res.node == n
        assert counts[n].sum() == routed.sum()
        for c in (0, 1):
            assert counts[n, c].sum() == (routed & (cls == c)).sum()
    assert res.cloud_offloads == res.edge.drops
    assert len(res.latencies) == len(tr) and (res.latencies > 0).all()


def test_size_aware_places_large_on_big_nodes():
    """Size-aware routing must never send a large container to a node
    whose large pool cannot fit it — here only node 3 qualifies."""
    rng = np.random.default_rng(11)
    tr = quantized_trace(rng, 500)
    cfg = ClusterConfig(node_mb=(1024.0, 1024.0, 1024.0, 4096.0),
                        small_frac=(0.8, 0.8, 0.8, 0.5),
                        unified=(False,) * 4,
                        routing=RoutingPolicy.SIZE_AWARE, max_slots=64)
    res = simulate_cluster_jax(cfg, tr)
    cls = np.asarray(tr.cls)
    assert (res.node[cls == 1] == 3).all()
    # small containers keep sticky spread over all four eligible nodes
    assert len(np.unique(res.node[cls == 0])) == 4
    # and the steering pays: sticky drops what size-aware serves at edge
    sticky = simulate_cluster_jax(het4(RoutingPolicy.STICKY), tr)
    assert res.edge.drops < sticky.edge.drops


def test_step_modes_agree():
    """The gather (dynamic-slice) and vmap (step-all, select-one)
    formulations of the scan are numerically identical."""
    rng = np.random.default_rng(5)
    tr = quantized_trace(rng, 250)
    for routing in (RoutingPolicy.STICKY, RoutingPolicy.POWER_OF_TWO):
        cfg = het4(routing)
        g = simulate_cluster_jax(cfg, tr, mode="gather")
        v = simulate_cluster_jax(cfg, tr, mode="vmap")
        assert (g.node == v.node).all()
        assert (g.outcome == v.outcome).all()


def test_sweep_cluster_matches_pointwise():
    """One vmapped sweep over (routing x capacities) == per-config runs."""
    rng = np.random.default_rng(9)
    tr = quantized_trace(rng, 400)
    cfgs = [het4(RoutingPolicy.STICKY), het4(RoutingPolicy.SIZE_AWARE),
            ClusterConfig(node_mb=(2048.0,) * 4, small_frac=(0.8,) * 4,
                          unified=(False,) * 4,
                          routing=RoutingPolicy.LEAST_LOADED, max_slots=64)]
    swept = sweep_cluster(tr, cfgs)
    for cfg, got in zip(cfgs, swept):
        one = simulate_cluster_jax(cfg, tr)
        assert (got.node == one.node).all()
        assert (got.outcome == one.outcome).all()
        assert (got.per_node == one.per_node).all()


def test_sweep_cluster_rejects_mixed_shapes():
    rng = np.random.default_rng(0)
    tr = quantized_trace(rng, 50)
    with pytest.raises(ValueError):
        sweep_cluster(tr, [het4(), ClusterConfig.homogeneous(2, 1024.0)])


def test_nonsticky_beats_sticky_p95_on_heterogeneous_cluster():
    """The benchmark claim, pinned: with an expensive cloud, size-aware
    placement beats sticky-hash on p95 end-to-end latency."""
    rng = np.random.default_rng(2)
    tr = quantized_trace(rng, 1200)
    # the big node holds the whole large working set; offloading to the
    # cloud is priced realistically (WAN RTT + likely cloud cold start)
    base = dict(node_mb=(1024.0, 1024.0, 1024.0, 8192.0),
                small_frac=(0.8, 0.8, 0.8, 0.5), unified=(False,) * 4,
                cloud_rtt_s=1.0, cloud_cold_prob=0.6, max_slots=64)
    sticky, aware = sweep_cluster(tr, [
        ClusterConfig(routing=RoutingPolicy.STICKY, **base),
        ClusterConfig(routing=RoutingPolicy.SIZE_AWARE, **base)])
    assert aware.latency_stats()["p95_s"] < sticky.latency_stats()["p95_s"]
    assert aware.offload_pct < sticky.offload_pct


def test_slot_saturation_equivalence():
    """When a pool's resident count hits max_slots, both engines must
    drop identically (the JAX step needs an empty slot after memory-driven
    eviction; the oracle mirrors it).  Tiny slot count + ample memory +
    load-spreading routing forces the saturation path."""
    rng = np.random.default_rng(8)
    tr = quantized_trace(rng, 600)
    cfg = ClusterConfig.homogeneous(2, 16 * 1024.0, kiss=True,
                                    routing=RoutingPolicy.LEAST_LOADED,
                                    max_slots=8)
    j = simulate_cluster_jax(cfg, tr)
    r = simulate_cluster_ref(cfg, tr)
    assert j.edge.drops > 0          # the slot limit actually bound
    assert (j.node == r.node).all()
    assert (j.outcome == r.outcome).all()


def test_benchmark_het16_routing_claim_pinned():
    """Pin the exact benchmark configuration (paper trace + het16 cloud
    pricing): the claim continuum_bench prints — a non-sticky policy beats
    sticky-hash on p95 — must hold on the real trace, not just the
    synthetic 4-node fixture above.  The comparison now spans EVERY
    registered routing policy, so the externally registered cost_model
    must appear in it."""
    from benchmarks.continuum_bench import routing_comparison
    from benchmarks.common import paper_trace
    from repro.sim import routing_policies
    byr = routing_comparison(paper_trace(duration_s=1800.0))
    assert set(routing_policies()) <= set(byr)
    assert "cost_model" in byr
    p95 = {name: res.latency_stats()["p95_s"] for name, res in byr.items()}
    assert min(v for n, v in p95.items() if n != "sticky") < p95["sticky"]


def test_unified_node_serves_both_classes_in_pool_zero():
    """A unified node routes both size classes to its single pool; its
    zero-capacity second pool never sees an event."""
    rng = np.random.default_rng(4)
    tr = quantized_trace(rng, 300)
    cfg = ClusterConfig.homogeneous(2, 4096.0, kiss=False, max_slots=64)
    res = simulate_cluster_jax(cfg, tr)
    # both classes show up on unified nodes, and nothing is dropped for
    # want of the (empty) large pool at this ample capacity
    assert res.per_node[:, 0, :3].sum() == (np.asarray(tr.cls) == 0).sum()
    assert res.per_node[:, 1, :3].sum() == (np.asarray(tr.cls) == 1).sum()
    ref = simulate_cluster_ref(cfg, tr)
    assert (res.outcome == ref.outcome).all()


def test_continuum_wrapper_matches_cluster_oracle():
    """The historical simulate_continuum API now runs on the cluster
    oracle and must agree with an explicitly-built homogeneous config."""
    from repro.core.continuum import ContinuumConfig, simulate_continuum
    rng = np.random.default_rng(6)
    tr = quantized_trace(rng, 400)
    old = simulate_continuum(ContinuumConfig(n_nodes=4, node_mb=2048.0), tr)
    new = simulate_cluster_ref(
        ClusterConfig.homogeneous(4, 2048.0, kiss=True, small_frac=0.8), tr)
    assert old.edge.hits == new.edge.hits
    assert old.edge.drops == new.edge.drops
    assert np.allclose(old.latencies, new.latencies)


def _bool_scatters(jaxpr) -> list[str]:
    """Primitives of every scatter into a bool array in ``jaxpr`` and its
    sub-jaxprs (scan, cond and switch bodies)."""
    found = []
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name.startswith("scatter")
                and eqn.outvars[0].aval.dtype == np.bool_):
            found.append(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _bool_scatters(sub)
    return found


@pytest.mark.parametrize("mode", ["gather", "vmap", "fused"])
@pytest.mark.parametrize("masked", [False, True])
def test_vmapped_step_updates_bools_by_select(mode, masked):
    """Under a wide sweep ``vmap`` XLA:TPU lost updates of bool scatters
    (a v5e at 1,024 lanes left a newly placed slot's ``valid`` bit
    False), so the per-event step and the chain fold never scatter into
    a bool array."""
    import jax
    import jax.numpy as jnp

    from repro.cluster.engine import (ChainXs, _chain_event, _chain_init,
                                      _make_step, _stack_configs,
                                      cluster_events)
    tr = quantized_trace(np.random.default_rng(3), 8)
    _, n, pools, routing, unified, cloud = _stack_configs(
        [het4(), het4(RoutingPolicy.LEAST_LOADED)], "test")
    ev = jax.tree_util.tree_map(lambda a: a[0], cluster_events(tr, n))
    up = jnp.ones((n,), bool) if masked else None

    def step(p, r, u, c):
        return _make_step(r, u, c, n, mode)(p, ev, up)
    jaxpr = jax.make_jaxpr(jax.vmap(step))(pools, routing, unified, cloud)
    assert _bool_scatters(jaxpr.jaxpr) == []

    chain = jax.tree_util.tree_map(lambda a: jnp.stack([a, a]),
                                   _chain_init(5))
    cx = ChainXs(cid=jnp.int32(2), stage=jnp.int32(1), last=jnp.bool_(True))

    def fold(ch, outcome):
        return _chain_event(ch, cx, jnp.bool_(False), jnp.full((6,), 9.0),
                            ev, outcome, jnp.asarray([0.1, 0.5], jnp.float32))
    jaxpr = jax.make_jaxpr(jax.vmap(fold))(chain, jnp.asarray([0, 2]))
    assert _bool_scatters(jaxpr.jaxpr) == []


@pytest.mark.parametrize("shape,idx", [((6,), 4), ((3, 6), [5, 0, 2])])
@pytest.mark.parametrize("dtype", [np.bool_, np.int32, np.float32])
def test_put_matches_scatter(shape, idx, dtype):
    """``pool_jax.put`` (the step's select-based slot update) equals
    ``x.at[i].set(v)`` along the last axis, per row for 2-D ``x``."""
    import jax.numpy as jnp

    from repro.core.pool_jax import put
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 2, shape).astype(dtype))
    i = jnp.asarray(idx, jnp.int32)
    v = jnp.asarray(rng.integers(2, 5, shape[:-1]).astype(dtype))
    got = put(x, i, v)
    if len(shape) == 1:
        want = x.at[i].set(v)
    else:
        want = x.at[jnp.arange(shape[0]), i].set(v)
    assert got.dtype == x.dtype and bool(jnp.all(got == want))
