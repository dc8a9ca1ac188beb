"""In-scan telemetry: windowed time-series, trace-event export, manifests.

The heart is the design contract from ``repro.sim.telemetry``: the
windows are bit-identical JAX vs oracle (every registered routing, every
scenario shape, both step modes), chunked == monolithic for dividing AND
non-dividing chunk sizes, and per-window counters sum exactly to the
end-of-run ``summary()`` totals.  On top: the trace-event JSON schema is
pinned, manifests carry the run identity, and ``telemetry=None`` keeps
yesterday's behavior bit for bit.
"""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.core.types import Trace
from repro.sim import (Autoscale, Failures, Scenario, Telemetry,
                       TelemetrySeries, simulate, sweep)
from repro.sim.telemetry import scenario_hash, trace_fingerprint

from conftest import quantized_trace

BUILTIN_ROUTINGS = ["sticky", "least_loaded", "size_aware", "power_of_two",
                    "cost_model"]
WINDOW = 64

TEL_FIELDS = ("counts", "free_mb", "occupancy", "invalidated", "nodes_up",
              "nodes_active", "t_start", "t_end", "event_start")


def het4(routing="sticky", failures=None, autoscale=None, telemetry=WINDOW):
    return Scenario.cluster((1024.0, 1024.0, 2048.0, 4096.0),
                            small_frac=(0.8, 0.8, 0.8, 0.5),
                            unified=(False, True, False, False),
                            routing=routing, max_slots=64,
                            failures=failures, autoscale=autoscale,
                            telemetry=telemetry)


def mid_windows(tr, nodes=(0, 2)):
    t0 = float(tr.t[int(len(tr) * 0.25)])
    t1 = float(tr.t[int(len(tr) * 0.6)])
    return Failures(windows=tuple((t0 + 3 * i, t1 + 11 * i, n)
                                  for i, n in enumerate(nodes)))


NODE_ASC = Autoscale(epoch_events=100, min_frac=0.4, max_frac=0.9,
                     gain=0.2, spawn_drop_frac=0.05, retire_drop_frac=0.01,
                     init_active=2)


def assert_tel_equal(a: TelemetrySeries, b: TelemetrySeries, tag=""):
    for f in TEL_FIELDS:
        fa, fb = getattr(a, f), getattr(b, f)
        assert fa.dtype == fb.dtype, (tag, f)
        assert np.array_equal(fa, fb), (tag, f)


# ---------------------------------------------------------------------------
# engine equivalence: bit-identical windows
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["gather", "vmap"])
@pytest.mark.parametrize("routing", BUILTIN_ROUTINGS)
def test_telemetry_jax_matches_oracle_static(routing, mode):
    tr = quantized_trace(np.random.default_rng(0), 450)
    sc = het4(routing)
    j = simulate(sc, tr, engine="jax", mode=mode)
    r = simulate(sc, tr, engine="ref")
    assert (j.outcome == r.outcome).all(), routing
    assert_tel_equal(j.timeline(), r.timeline(), routing)


@pytest.mark.parametrize("mode", ["gather", "vmap"])
@pytest.mark.parametrize("variant", ["failures", "autoscale", "both"])
def test_telemetry_jax_matches_oracle_dynamic(variant, mode):
    """Failure recovery and node retirement both invalidate residents:
    the per-window invalidation series (and the up/active counts) must
    agree bit for bit on every combination."""
    tr = quantized_trace(np.random.default_rng(1), 450)
    fails = mid_windows(tr) if variant in ("failures", "both") else None
    asc = NODE_ASC if variant in ("autoscale", "both") else None
    sc = het4("size_aware", failures=fails, autoscale=asc)
    j = simulate(sc, tr, engine="jax", mode=mode)
    r = simulate(sc, tr, engine="ref")
    assert (j.outcome == r.outcome).all(), variant
    assert_tel_equal(j.timeline(), r.timeline(), variant)
    if variant != "autoscale":
        assert j.timeline().invalidated.sum() > 0, "outage must invalidate"


def test_telemetry_every_registered_routing_dynamic():
    from repro.sim import routing_policies
    tr = quantized_trace(np.random.default_rng(2), 300)
    fails = mid_windows(tr)
    for name in routing_policies():
        sc = het4(name, failures=fails)
        j = simulate(sc, tr)
        r = simulate(sc, tr, engine="ref")
        assert_tel_equal(j.timeline(), r.timeline(), name)


# ---------------------------------------------------------------------------
# chunked == monolithic
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [128, 97])   # dividing and non-dividing
def test_chunked_matches_monolithic(chunk):
    """Window indices are global, so ANY chunk size — aligned to the
    window grid or not — must reproduce the monolithic windows."""
    tr = quantized_trace(np.random.default_rng(3), 450)
    for sc in (het4("least_loaded"),
               het4("least_loaded", failures=mid_windows(tr))):
        mono = simulate(sc, tr)
        ch = simulate(sc, tr, chunk_events=chunk)
        assert (mono.outcome == ch.outcome).all()
        assert_tel_equal(mono.timeline(), ch.timeline(), f"chunk={chunk}")


# ---------------------------------------------------------------------------
# exact totals and the window axis
# ---------------------------------------------------------------------------

def test_window_sums_match_summary_totals():
    tr = quantized_trace(np.random.default_rng(4), 450)
    sc = het4("size_aware", failures=mid_windows(tr), autoscale=NODE_ASC)
    res = simulate(sc, tr)
    tel, s = res.timeline(), res.summary()
    assert len(tel) == Telemetry(WINDOW).n_windows(len(tr)) == s["n_windows"]
    assert int(tel.counts.sum()) == s["total"] == len(tr)
    assert int(tel.hits.sum()) == res.per_class().overall.hits
    assert int(tel.misses.sum()) == res.per_class().overall.misses
    assert int(tel.drops.sum()) == res.per_class().overall.drops
    # per-class too: counts[:, c, :] sums to that class's metrics
    pc = res.per_class()
    for c, m in ((0, pc.small), (1, pc.large)):
        assert int(tel.counts[:, c, 0].sum()) == m.hits
        assert int(tel.counts[:, c, 1].sum()) == m.misses
        assert int(tel.counts[:, c, 2].sum()) == m.drops
    assert int(tel.invalidated.sum()) == res.n_invalidated > 0
    assert (tel.events[:-1] == WINDOW).all()
    assert tel.events.sum() == len(tr)
    assert (tel.event_start == np.arange(len(tel)) * WINDOW).all()
    assert (tel.t_start <= tel.t_end).all()
    assert (tel.t_start[1:] >= tel.t_end[:-1]).all()
    assert len(tel.table()) == len(tel)
    assert (tel.cold_start_pct() <= 100.0).all()


def test_snapshot_columns_reflect_run_end():
    """The last window's snapshot columns are the end-of-run state."""
    tr = quantized_trace(np.random.default_rng(5), 300)
    res = simulate(het4("sticky"), tr)
    tel = res.timeline()
    assert (tel.nodes_up == 4).all()       # no failure schedule
    assert (tel.nodes_active == 4).all()   # no node scaling
    assert (tel.occupancy >= 0).all()
    assert (tel.occupancy.max(axis=0) > 0).any()   # something got warm


# ---------------------------------------------------------------------------
# sweeps
# ---------------------------------------------------------------------------

def test_sweep_telemetry_matches_single_runs():
    """Telemetry lanes batch by window length; mixed telemetry-on and
    -off scenarios sweep together and each result matches its solo
    run — including chunked sweeps."""
    tr = quantized_trace(np.random.default_rng(6), 400)
    scns = [het4("sticky"), het4("least_loaded"),
            het4("sticky", telemetry=None),
            het4("power_of_two", failures=mid_windows(tr)),
            het4("size_aware", autoscale=NODE_ASC, telemetry=128)]
    for kw in ({}, {"chunk_events": 97}):
        if kw:          # autoscale does not compose with chunking
            lanes = scns[:4]
        else:
            lanes = scns
        rs = sweep(tr, lanes, **kw)
        for sc, r in zip(lanes, rs):
            solo = simulate(sc, tr, **kw)
            assert (r.outcome == solo.outcome).all(), sc.label
            if sc.telemetry is None:
                assert r.telemetry is None
            else:
                assert_tel_equal(r.timeline(), solo.timeline(), sc.label)


# ---------------------------------------------------------------------------
# the knob, the off-switch, and Trace.replace
# ---------------------------------------------------------------------------

def test_telemetry_knob_validation_and_sugar():
    assert Scenario.kiss(1024.0, telemetry=64).telemetry == Telemetry(64)
    assert (Scenario.kiss(1024.0, telemetry={"window_events": 32}).telemetry
            == Telemetry(32))
    assert Scenario.kiss(1024.0).telemetry is None
    with pytest.raises(ValueError):
        Telemetry(window_events=0)
    with pytest.raises(ValueError):
        Telemetry(window_events=-5)
    with pytest.raises(ValueError):
        Telemetry(window_events=2.5)
    with pytest.raises(ValueError):
        Scenario.kiss(1024.0, telemetry=True)    # bool is not a window
    assert Telemetry(64).n_windows(450) == 8
    assert Telemetry(64).n_windows(448) == 7
    assert hash(het4("sticky")) == hash(het4("sticky"))   # stays hashable


def test_no_telemetry_is_off():
    tr = quantized_trace(np.random.default_rng(7), 200)
    res = simulate(het4("sticky", telemetry=None), tr)
    assert res.telemetry is None
    assert res.summary()["n_windows"] == 0
    with pytest.raises(ValueError, match="telemetry"):
        res.timeline()
    # the outcomes are identical with and without the knob
    on = simulate(het4("sticky"), tr)
    assert (res.outcome == on.outcome).all()
    assert (res.node == on.node).all()


def test_trace_replace_is_safe_where_namedtuple_replace_is_not():
    """``Trace.__len__`` is the event count, which breaks namedtuple's
    ``_replace`` (its ``_make`` length check); ``Trace.replace`` is the
    supported spelling."""
    tr = quantized_trace(np.random.default_rng(8), 50)
    with pytest.raises(TypeError):
        tr._replace(t=tr.t)
    tr2 = tr.replace(t=tr.t + np.float32(1.0))
    assert np.array_equal(tr2.t, tr.t + np.float32(1.0))
    assert tr2.func_id is tr.func_id     # untouched fields pass through
    with pytest.raises(ValueError, match="no field"):
        tr.replace(bogus=tr.t)
    assert isinstance(tr.shifted(), Trace)
    assert float(tr.shifted().t[0]) == 0.0


# ---------------------------------------------------------------------------
# trace-event export: stable schema
# ---------------------------------------------------------------------------

def test_trace_events_schema(tmp_path):
    tr = quantized_trace(np.random.default_rng(9), 450)
    # a hair-trigger spawn threshold so the membership timeline actually
    # moves (at this scale the outage-induced drop fraction is small)
    asc = dataclasses.replace(NODE_ASC, spawn_drop_frac=0.005,
                              retire_drop_frac=0.001)
    sc = het4("size_aware", failures=mid_windows(tr), autoscale=asc)
    res = simulate(sc, tr)
    path = tmp_path / "run.trace.json"
    doc = res.to_trace_events(str(path))
    on_disk = json.loads(path.read_text())
    assert on_disk == doc
    assert set(doc) == {"traceEvents", "displayTimeUnit", "otherData"}
    assert doc["otherData"]["schema"] == "repro.sim/trace-events@1"
    assert doc["otherData"]["scenario"] == sc.label
    evs = doc["traceEvents"]
    phases = {e["ph"] for e in evs}
    assert phases == {"M", "C", "X", "i"}   # meta, counter, outage, instant
    for e in evs:
        assert {"ph", "pid", "tid", "name"} <= set(e)
    counters = {e["name"] for e in evs if e["ph"] == "C"}
    assert counters == {"outcomes", "cloud_offloads", "invalidated",
                        "nodes", "free_mb", "occupancy"}
    n_windows = len(res.timeline())
    assert sum(e["name"] == "outcomes" for e in evs) == n_windows
    outages = [e for e in evs if e["ph"] == "X"]
    assert len(outages) == len(sc.failures.windows)
    for e, (t0, t1, node) in zip(outages, sc.failures.windows):
        assert e["tid"] == node
        assert e["ts"] == pytest.approx(t0 * 1e6)
        assert e["dur"] == pytest.approx((t1 - t0) * 1e6)
    # NODE_ASC starts 2 of 4 nodes: the spawn instants must appear
    assert any(e["ph"] == "i" and e["name"].startswith("spawn")
               for e in evs)
    assert any(e["ph"] == "i" and e["name"].startswith("resplit")
               for e in evs)


def test_trace_events_without_telemetry_still_exports_timeline():
    tr = quantized_trace(np.random.default_rng(10), 200)
    sc = het4("sticky", failures=mid_windows(tr), telemetry=None)
    doc = simulate(sc, tr).to_trace_events()
    assert not any(e["ph"] == "C" for e in doc["traceEvents"])
    assert sum(e["ph"] == "X" for e in doc["traceEvents"]) == 2


# ---------------------------------------------------------------------------
# run manifests
# ---------------------------------------------------------------------------

def test_run_manifest_identity():
    tr = quantized_trace(np.random.default_rng(11), 300)
    sc = het4("least_loaded")
    res = simulate(sc, tr, chunk_events=128)
    man = res.manifest()
    assert man["schema"] == "repro.sim/run-manifest@1"
    assert man["scenario"]["hash"] == scenario_hash(sc)
    assert man["scenario"]["label"] == sc.label
    assert man["scenario"]["telemetry_window_events"] == WINDOW
    assert man["trace"]["fingerprint"] == trace_fingerprint(tr)
    assert man["trace"]["n_events"] == len(tr)
    assert man["run"] == {"engine": "jax", "mode": "gather",
                          "chunk_events": 128, "devices": None,
                          "rng_seed": 0}
    assert man["summary"] == res.summary()
    assert {"python", "jax", "numpy", "platform", "device_platform",
            "device_kind", "device_count"} <= set(man["versions"])
    assert man["versions"]["device_platform"] == jax.devices()[0].platform
    assert man["versions"]["device_count"] == jax.device_count()
    # the manifest is JSON-serializable as-is
    json.dumps(man, default=float)
    # same scenario, same trace -> same identity; different trace differs
    assert scenario_hash(het4("least_loaded")) == man["scenario"]["hash"]
    tr2 = tr.replace(t=tr.t + np.float32(1.0))
    assert trace_fingerprint(tr2) != man["trace"]["fingerprint"]


def test_manifest_ref_engine_run_info():
    tr = quantized_trace(np.random.default_rng(12), 150)
    man = simulate(het4("sticky"), tr, engine="ref").manifest()
    assert man["run"]["engine"] == "ref"
    assert man["run"]["mode"] is None
    assert man["run"]["chunk_events"] is None
