"""Device-mesh sharded sweeps: ``sweep(..., devices=)``.

The contract under test is *bitwise identity*: sharding the stacked lane
axis of a sweep bucket across a device mesh must not change a single
bit of any lane's result — summaries, per-event node/outcome arrays,
per-node tables, autoscale frac trajectories, telemetry windows and
chain metrics all compare exactly against the unsharded run, including
lane counts that don't divide the mesh (pad lanes) and sweeps whose
scenarios split into several shape buckets.

Multi-device cases skip unless the host exposes enough devices — CI
runs them under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(a fresh process; the flag must precede the first jax import)."""
import numpy as np
import pytest

import jax

from repro.core.types import Trace
from repro.sim import (Autoscale, Chains, Failures, Scenario, Telemetry,
                      simulate, sweep)
from repro.workloads import ChainConfig, chained_trace, edge_trace

from conftest import quantized_trace


def dev_param(d):
    return pytest.param(d, marks=pytest.mark.skipif(
        jax.device_count() < d, reason=f"needs {d} devices"))


DEVICES = [dev_param(1), dev_param(2), dev_param(8)]
MULTI = [dev_param(2), dev_param(8)]


@pytest.fixture(scope="module")
def trace():
    return quantized_trace(np.random.default_rng(0), 600)


@pytest.fixture(scope="module")
def chain_trace():
    return chained_trace(ChainConfig(duration_s=200.0, seed=3))


def static_lanes(n=5, **kw):
    # 5 lanes: divides neither 2 nor 8, so every mesh pads
    fracs = np.linspace(0.25, 0.75, n)
    return [Scenario(node_mb=(1024.0, 2048.0), small_frac=float(f),
                     max_slots=64, **kw) for f in fracs]


def assert_same(a, b):
    assert a.summary() == b.summary()
    assert np.array_equal(a.node, b.node)
    assert np.array_equal(a.outcome, b.outcome)
    assert np.array_equal(a.per_node, b.per_node)


@pytest.mark.parametrize("devices", DEVICES)
@pytest.mark.parametrize("mode", ["gather", "vmap", "fused"])
def test_static_sharded_bitwise(trace, mode, devices):
    scens = static_lanes()
    base = sweep(trace, scens, mode=mode)
    shard = sweep(trace, scens, mode=mode, devices=devices)
    for a, b in zip(base, shard):
        assert_same(a, b)
        assert b.run_info["devices"] == devices


@pytest.mark.parametrize("devices", MULTI)
def test_failures_sharded_bitwise(trace, devices):
    t0 = float(trace.t[len(trace) // 4])
    t1 = float(trace.t[len(trace) // 2])
    scens = static_lanes(failures=Failures(windows=((t0, t1, 0),)))
    base = sweep(trace, scens)
    for a, b in zip(base, sweep(trace, scens, devices=devices)):
        assert_same(a, b)
        assert np.array_equal(a.node_up, b.node_up)


@pytest.mark.parametrize("devices", MULTI)
def test_autoscale_sharded_bitwise(trace, devices):
    scens = [Scenario(node_mb=(1024.0, 2048.0), small_frac=float(f),
                      max_slots=64, autoscale=Autoscale(epoch_events=128))
             for f in np.linspace(0.55, 0.85, 5)]
    base = sweep(trace, scens)
    for a, b in zip(base, sweep(trace, scens, devices=devices)):
        assert_same(a, b)
        assert np.array_equal(a.fracs, b.fracs)


@pytest.mark.parametrize("devices", MULTI)
def test_telemetry_windows_sharded_bitwise(trace, devices):
    scens = static_lanes(telemetry=Telemetry(window_events=64))
    base = sweep(trace, scens)
    for a, b in zip(base, sweep(trace, scens, devices=devices)):
        assert_same(a, b)
        for field in ("counts", "free_mb", "occupancy", "invalidated"):
            assert np.array_equal(getattr(a.timeline(), field),
                                  getattr(b.timeline(), field))


@pytest.mark.parametrize("devices", MULTI)
def test_chains_sharded_bitwise(chain_trace, devices):
    scens = static_lanes(chains=Chains(deadline_s=1.0))
    base = sweep(chain_trace, scens)
    for a, b in zip(base, sweep(chain_trace, scens, devices=devices)):
        assert_same(a, b)
        assert np.array_equal(a.chain_latency, b.chain_latency)
        assert a.deadline_miss_pct == b.deadline_miss_pct


@pytest.mark.parametrize("devices", MULTI)
@pytest.mark.parametrize("failing", [False, True])
def test_chunked_sharded_bitwise(trace, failing, devices):
    fails = None
    if failing:
        t0 = float(trace.t[len(trace) // 4])
        fails = Failures(windows=((t0, t0 + 400.0, 1),))
    scens = static_lanes(failures=fails)
    base = sweep(trace, scens, chunk_events=256)
    shard = sweep(trace, scens, chunk_events=256, devices=devices)
    for a, b in zip(base, shard):
        assert_same(a, b)


@pytest.mark.parametrize("devices", MULTI)
def test_chunked_chains_sharded_bitwise(chain_trace, devices):
    scens = static_lanes(chains=Chains(deadline_s=1.0),
                         telemetry=Telemetry(window_events=64))
    base = sweep(chain_trace, scens, chunk_events=128)
    shard = sweep(chain_trace, scens, chunk_events=128, devices=devices)
    for a, b in zip(base, shard):
        assert_same(a, b)
        assert np.array_equal(a.chain_latency, b.chain_latency)
        assert np.array_equal(a.timeline().counts, b.timeline().counts)


@pytest.mark.parametrize("devices", MULTI)
@pytest.mark.parametrize("lanes", [1, 2, 3, 7])
def test_pad_lanes_every_remainder(trace, lanes, devices):
    """Non-dividing lane counts exercise the pad-lane path: results for
    the real lanes are untouched by the no-op duplicates."""
    scens = static_lanes(lanes)
    base = sweep(trace, scens)
    for a, b in zip(base, sweep(trace, scens, devices=devices)):
        assert_same(a, b)


@pytest.mark.parametrize("devices", MULTI)
def test_mixed_buckets_sharded(trace, devices):
    """Scenarios splitting into several shape/flavor buckets shard each
    bucket independently; order and bits are preserved."""
    t0 = float(trace.t[len(trace) // 4])
    scens = [Scenario(node_mb=(1024.0, 2048.0), small_frac=0.4,
                      max_slots=64),
             Scenario(node_mb=(1024.0, 2048.0, 4096.0), small_frac=0.5,
                      max_slots=64),
             Scenario(node_mb=(1024.0, 2048.0), small_frac=0.6,
                      max_slots=64,
                      autoscale=Autoscale(epoch_events=128)),
             Scenario(node_mb=(1024.0, 2048.0), small_frac=0.7,
                      max_slots=64,
                      failures=Failures(windows=((t0, t0 + 300.0, 0),))),
             Scenario(node_mb=(1024.0, 2048.0), small_frac=0.45,
                      max_slots=64)]
    base = sweep(trace, scens)
    for a, b in zip(base, sweep(trace, scens, devices=devices)):
        assert_same(a, b)


def test_devices_all_resolves(trace):
    scens = static_lanes(3)
    base = sweep(trace, scens)
    shard = sweep(trace, scens, devices="all")
    for a, b in zip(base, shard):
        assert_same(a, b)
        assert b.run_info["devices"] == jax.device_count()


def test_devices_validation(trace):
    scens = static_lanes(2)
    over = jax.device_count() + 1
    with pytest.raises(ValueError, match="exceeds"):
        sweep(trace, scens, devices=over)
    # the error should point at the CPU mesh escape hatch
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        sweep(trace, scens, devices=over)
    for bad in (0, -2, 1.5, True, "some", "ALL"):
        with pytest.raises(ValueError, match="devices"):
            sweep(trace, scens, devices=bad)


def test_devices_validation_ref_engine(trace):
    """The ref engine validates then ignores, like chunk_events."""
    scens = static_lanes(2)
    with pytest.raises(ValueError, match="exceeds"):
        sweep(trace, scens, engine="ref", devices=jax.device_count() + 1)
    with pytest.raises(ValueError, match="devices"):
        sweep(trace, scens, engine="ref", devices=0)
    base = sweep(trace, scens, engine="ref")
    ignored = sweep(trace, scens, engine="ref", devices=1)
    for a, b in zip(base, ignored):
        assert a.summary() == b.summary()


def test_run_info_devices_key(trace):
    scens = static_lanes(2)
    assert sweep(trace, scens)[0].run_info["devices"] is None
    assert sweep(trace, scens, devices=1)[0].run_info["devices"] == 1
    r = simulate(scens[0], trace)
    assert r.run_info["devices"] is None   # single runs never shard
    assert "devices" in r.manifest()["run"]


@pytest.mark.parametrize("devices", DEVICES)
@pytest.mark.parametrize("flavor", ["static", "chunked", "failures",
                                    "autoscale"])
def test_lane_devices_from_output_shards(trace, flavor, devices):
    """A sharded sweep records where its lanes ran, read from the shards
    of its outputs: each mesh device holds an equal share of the 5 lanes
    plus pads.  Unsharded sweeps record nothing."""
    kw, scens = {}, static_lanes()
    if flavor == "chunked":
        kw["chunk_events"] = 256
    elif flavor == "failures":
        t0 = float(trace.t[len(trace) // 4])
        scens = static_lanes(failures=Failures(windows=((t0, t0 + 300.0, 0),)))
    elif flavor == "autoscale":
        scens = [Scenario(node_mb=(1024.0, 2048.0), small_frac=float(f),
                          max_slots=64, autoscale=Autoscale(epoch_events=128))
                 for f in np.linspace(0.55, 0.85, 5)]
    assert "lane_devices" not in sweep(trace, scens, **kw)[0].run_info
    res = sweep(trace, scens, devices=devices, **kw)
    rows = -(-len(scens) // devices)
    want = {str(d): rows for d in jax.devices()[:devices]}
    assert all(r.run_info["lane_devices"] == want for r in res)
