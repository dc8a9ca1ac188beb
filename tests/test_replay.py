"""Azure-2019 replay: schema ingest edge cases + chunked-scan
bit-equivalence (the two halves of the replay tentpole)."""
import dataclasses
import os

import numpy as np
import pytest

from repro.core.types import Trace
from repro.sim import (Autoscale, Chains, Failures, Resize, Scenario,
                       simulate, sweep)
from repro.workloads import (ReplayConfig, SchemaConfig, load_azure_trace,
                             read_azure_csvs, synthesize_azure_schema,
                             trace_from_tables, write_azure_csvs)
from repro.workloads.chains import ChainConfig, chained_trace
from repro.workloads.replay import (DURATION_PCT_LEVELS, MEMORY_PCT_LEVELS,
                                    AzureTables, _interp_pcts)

SMALL_SCHEMA = SchemaConfig(n_funcs=40, n_minutes=30, rpm_total=120.0,
                            seed=7)


@pytest.fixture(scope="module")
def tables():
    return synthesize_azure_schema(SMALL_SCHEMA)


@pytest.fixture(scope="module")
def trace(tables):
    return trace_from_tables(tables)


def _tiny_tables(counts, dur=None, mem=None):
    """Hand-built single-app tables: counts is i64[F, M]."""
    counts = np.asarray(counts, np.int64)
    f = counts.shape[0]
    if dur is None:
        dur = np.tile(np.array([10.0, 20.0, 100.0, 200.0, 400.0, 900.0,
                                1000.0]), (f, 1))
    if mem is None:
        mem = np.array([[30, 35, 40, 45, 50, 55, 58, 60]], np.float64)
    return AzureTables(
        owners=("o",) * f, apps=("a",) * f,
        funcs=tuple(f"f{i}" for i in range(f)),
        triggers=("http",) * f, counts=counts,
        dur_pcts=np.asarray(dur, np.float64),
        mem_apps=(("o", "a"),), mem_pcts=np.asarray(mem, np.float64))


# --------------------------------------------------------------------------
# ingest
# --------------------------------------------------------------------------

def test_trace_is_sorted_quantized_and_counts_match(tables, trace):
    t = np.asarray(trace.t)
    assert len(trace) == tables.n_invocations
    assert (np.diff(t) >= 0).all()
    assert np.allclose(t * 64, np.round(t * 64))              # 1/64 s grid
    assert np.allclose(trace.size_mb, np.round(trace.size_mb))  # whole MB
    assert np.asarray(trace.size_mb).min() >= 1.0
    for d in (trace.warm_dur, trace.cold_dur):
        d = np.asarray(d)
        assert np.allclose(d * 64, np.round(d * 64))
        assert d.min() >= 1 / 64
    assert (np.asarray(trace.cold_dur) > np.asarray(trace.warm_dur)).all()


def test_class_threshold_and_ratio(trace):
    sz = np.asarray(trace.size_mb)
    cls = np.asarray(trace.cls)
    assert ((sz >= 225.0) == (cls == 1)).all()
    small, large = np.bincount(cls, minlength=2)[:2]
    assert small > large                 # the paper's dominant-small mix


def test_empty_minute_buckets():
    # function 0 has interior empty minutes, function 1 is all-empty
    counts = np.array([[3, 0, 0, 2, 0], [0, 0, 0, 0, 0]])
    tr = trace_from_tables(_tiny_tables(counts))
    assert len(tr) == 5
    assert (np.asarray(tr.func_id) == 0).all()    # all-empty func dropped
    minutes = np.floor(np.asarray(tr.t) / 60.0).astype(int)
    assert np.bincount(minutes, minlength=5).tolist() == [3, 0, 0, 2, 0]


def test_empty_tables_give_empty_trace():
    tr = trace_from_tables(_tiny_tables(np.zeros((2, 4))))
    assert len(tr) == 0


def test_intra_minute_placement_deterministic_and_even(tables):
    tr1 = trace_from_tables(tables)
    tr2 = trace_from_tables(tables)
    for a, b in zip(tr1, tr2):
        np.testing.assert_array_equal(a, b)
    # k events in one minute are evenly spaced: gaps within +/- one
    # quantum of 60/k
    counts = np.array([[64]])
    tr = trace_from_tables(_tiny_tables(counts))
    gaps = np.diff(np.asarray(tr.t))
    assert np.abs(gaps - 60.0 / 64).max() <= 2 / 64 + 1e-9


def test_row_order_invariance(tables):
    """Shuffled table rows (ingest sees CSVs in any order) must replay to
    the bit-identical trace."""
    perm = np.random.default_rng(0).permutation(tables.n_functions)
    shuffled = AzureTables(
        owners=tuple(tables.owners[i] for i in perm),
        apps=tuple(tables.apps[i] for i in perm),
        funcs=tuple(tables.funcs[i] for i in perm),
        triggers=tuple(tables.triggers[i] for i in perm),
        counts=tables.counts[perm],
        dur_pcts=tables.dur_pcts[perm],
        mem_apps=tables.mem_apps, mem_pcts=tables.mem_pcts)
    a, b = trace_from_tables(tables), trace_from_tables(shuffled)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_seed_changes_trace(tables):
    a = trace_from_tables(tables, ReplayConfig(seed=0))
    b = trace_from_tables(tables, ReplayConfig(seed=1))
    assert len(a) == len(b)          # counts are schema data, not draws
    assert not np.array_equal(np.asarray(a.t), np.asarray(b.t))


def test_percentile_boundary_sampling_deterministic():
    levels = DURATION_PCT_LEVELS
    values = np.array([10.0, 20.0, 100.0, 200.0, 400.0, 900.0, 1000.0])
    # u exactly on a level returns that column, twice
    u = np.asarray(levels) / 100.0
    np.testing.assert_array_equal(_interp_pcts(u, levels, values), values)
    np.testing.assert_array_equal(_interp_pcts(u, levels, values), values)
    # non-monotone rows (they exist in the real dataset) are repaired
    broken = np.array([10.0, 20.0, 15.0, 200.0, 400.0, 900.0, 1000.0])
    out = _interp_pcts(u, levels, broken)
    assert (np.diff(out) >= 0).all()
    assert len(MEMORY_PCT_LEVELS) == 8


def test_csv_round_trip(tables, trace, tmp_path):
    paths = write_azure_csvs(tables, str(tmp_path))
    for p in paths:
        assert os.path.exists(p)
    tr2 = load_azure_trace(*paths)
    for a, b in zip(trace, tr2):
        np.testing.assert_array_equal(a, b)


def test_csv_rows_out_of_order(tables, trace, tmp_path):
    """Reversing the data rows of every CSV must not change the trace."""
    paths = write_azure_csvs(tables, str(tmp_path))
    for p in paths:
        with open(p) as f:
            header, *rows = f.read().splitlines()
        with open(p, "w") as f:
            f.write("\n".join([header] + rows[::-1]) + "\n")
    tr2 = load_azure_trace(*paths)
    for a, b in zip(trace, tr2):
        np.testing.assert_array_equal(a, b)


def test_missing_duration_and_memory_rows(tables, tmp_path):
    """Functions absent from the duration table / apps absent from the
    memory table fall back to median curves instead of crashing."""
    paths = write_azure_csvs(tables, str(tmp_path))
    for p in paths[1:]:
        with open(p) as f:
            header, *rows = f.read().splitlines()
        with open(p, "w") as f:          # drop half the rows
            f.write("\n".join([header] + rows[::2]) + "\n")
    tr = load_azure_trace(*paths)
    assert len(tr) == tables.n_invocations


def test_csv_schema_validation(tmp_path):
    bad = tmp_path / "bad.csv"
    bad.write_text("NotTheSchema\n1\n")
    with pytest.raises(ValueError, match="missing schema columns"):
        read_azure_csvs(str(bad), str(bad), str(bad))


# --------------------------------------------------------------------------
# Trace slicers
# --------------------------------------------------------------------------

def test_head_slicing(trace):
    h = trace.head(100)
    assert len(h) == 100
    for a, b in zip(h, trace):
        if b is None:            # optional chain fields on chainless traces
            assert a is None
            continue
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[:100])
    assert len(trace.head(10**9)) == len(trace)
    assert len(trace.head(0)) == 0
    with pytest.raises(ValueError):
        trace.head(-1)


def test_head_prefix_consistency(trace):
    """Simulating head(n) == the first n outcomes of the full run."""
    scn = Scenario.kiss(512.0, max_slots=32)
    full = simulate(scn, trace)
    pre = simulate(scn, trace.head(500))
    np.testing.assert_array_equal(pre.outcome, full.outcome[:500])


def test_window_and_shifted(trace):
    t = np.asarray(trace.t)
    w = trace.window(120.0, 300.0)
    assert len(w) == int(((t >= 120.0) & (t < 300.0)).sum())
    assert len(w) and np.asarray(w.t).min() >= 120.0
    assert np.asarray(w.t).max() < 300.0
    z = w.shifted()
    assert np.asarray(z.t)[0] == 0.0
    zt = np.asarray(z.t)
    assert np.allclose(zt * 64, np.round(zt * 64))   # still on the grid
    with pytest.raises(ValueError):
        trace.window(10.0, 5.0)


# --------------------------------------------------------------------------
# chunked scan == monolithic scan
# --------------------------------------------------------------------------

CLUSTER = (256.0, 512.0, 1024.0)


def _assert_same(a, b, fields=("node", "outcome", "latencies")):
    for f in fields:
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f),
                                      err_msg=f)


@pytest.fixture(scope="module")
def replay_trace(trace):
    return trace.head(2000)


@pytest.mark.parametrize("chunk", [64, 333, 2000, 4096])
def test_chunked_equals_monolithic(replay_trace, chunk):
    """Chunk sizes that do / don't divide the length, == the length, and
    > the length all reproduce the monolithic scan bit-for-bit."""
    scn = Scenario.cluster(CLUSTER, routing="size_aware", max_slots=32)
    _assert_same(simulate(scn, replay_trace),
                 simulate(scn, replay_trace, chunk_events=chunk))


@pytest.mark.parametrize("mode", ["gather", "vmap"])
def test_chunked_equals_oracle(replay_trace, mode):
    scn = Scenario.cluster(CLUSTER, routing="least_loaded", max_slots=32)
    jx = simulate(scn, replay_trace, chunk_events=256, mode=mode)
    ref = simulate(scn, replay_trace, engine="ref", chunk_events=256)
    _assert_same(jx, ref)


def test_chunked_failures(replay_trace):
    t_end = float(np.asarray(replay_trace.t)[-1])
    scn = Scenario.cluster(
        CLUSTER, routing="least_loaded", max_slots=32,
        failures=Failures(((0.2 * t_end, 0.5 * t_end, 0),
                           (0.4 * t_end, 0.8 * t_end, 2))))
    mono = simulate(scn, replay_trace)
    for chunk in (100, 777):
        ch = simulate(scn, replay_trace, chunk_events=chunk)
        _assert_same(mono, ch)
        np.testing.assert_array_equal(mono.invalidated, ch.invalidated)
        np.testing.assert_array_equal(mono.node_up, ch.node_up)
    ref = simulate(scn, replay_trace, engine="ref")
    _assert_same(mono, ref)


def test_chunked_sweep_matches_pointwise(replay_trace):
    t_end = float(np.asarray(replay_trace.t)[-1])
    scns = [
        Scenario.cluster(CLUSTER, routing="sticky", max_slots=32),
        Scenario.cluster(CLUSTER, routing="size_aware", max_slots=32),
        Scenario.cluster(CLUSTER, unified=True, max_slots=32),
        Scenario.cluster(CLUSTER, routing="least_loaded", max_slots=32,
                         failures=((0.3 * t_end, 0.6 * t_end, 1),)),
        Scenario.kiss(512.0, max_slots=32),      # different bucket shape
    ]
    swept = sweep(replay_trace, scns, chunk_events=300)
    for s, r in zip(scns, swept):
        _assert_same(simulate(s, replay_trace), r)
        if s.failures is not None:
            one = simulate(s, replay_trace, chunk_events=300)
            np.testing.assert_array_equal(one.invalidated, r.invalidated)


def test_chunk_events_validation(replay_trace):
    scn = Scenario.kiss(512.0, max_slots=32)
    for bad in (0, -5, 2.5, "x"):
        with pytest.raises(ValueError, match="chunk_events"):
            simulate(scn, replay_trace, chunk_events=bad)
    asc = Scenario.kiss(512.0, max_slots=32,
                        autoscale=Autoscale(epoch_events=256))
    with pytest.raises(ValueError, match="autoscale"):
        simulate(asc, replay_trace, chunk_events=256)
    with pytest.raises(ValueError, match="autoscale"):
        sweep(replay_trace, [scn, asc], chunk_events=256)


def test_chunked_accepts_tiny_trace():
    n = 5
    tr = Trace(t=np.arange(n, dtype=np.float32),
               func_id=np.zeros(n, np.int32),
               size_mb=np.full(n, 64, np.float32),
               cls=np.zeros(n, np.int32),
               warm_dur=np.ones(n, np.float32),
               cold_dur=np.full(n, 2, np.float32))
    scn = Scenario.kiss(256.0, max_slots=8)
    _assert_same(simulate(scn, tr), simulate(scn, tr, chunk_events=64))


# ---------------------------------------------------------------------------
# every optional mechanism through the one chunk program, against the oracle
# ---------------------------------------------------------------------------

MECHANISMS = {
    "failures": dict(failures=((20.0, 50.0, 0), (35.0, 70.0, 2))),
    "telemetry": dict(telemetry=64),
    "chains": dict(chains=Chains(slack=2.0)),
    "resize": dict(resize=Resize("fair_share", min_mb=32.0)),
}
COMBOS = {"none": ()} | {k: (k,) for k in MECHANISMS} | {
    "all": tuple(MECHANISMS)}


@pytest.fixture(scope="module")
def chained():
    """About 360 events in chains of four stages."""
    tr = chained_trace(ChainConfig(duration_s=90.0, seed=5))
    assert len(tr) <= 400
    return tr


def _mech_scenario(combo: str, routing: str = "least_loaded") -> Scenario:
    kw = {}
    for k in COMBOS[combo]:
        kw.update(MECHANISMS[k])
    return Scenario.cluster(CLUSTER, routing=routing, max_slots=32, **kw)


def _assert_result_equal(a, b):
    """Bit-equal outcomes and extras, field by field."""
    _assert_same(a.raw, b.raw)
    for f in ("node_up", "invalidated"):
        x, y = getattr(a, f), getattr(b, f)
        assert (x is None) == (y is None), f
        if x is not None:
            np.testing.assert_array_equal(x, y, err_msg=f)
    for f in ("telemetry", "chains"):
        x, y = getattr(a, f), getattr(b, f)
        assert (x is None) == (y is None), f
        if x is not None:
            for fld in dataclasses.fields(x):
                np.testing.assert_array_equal(
                    getattr(x, fld.name), getattr(y, fld.name),
                    err_msg=f"{f}.{fld.name}")
    assert (a.vertical is None) == (b.vertical is None)
    if a.vertical is not None:
        assert a.vertical.keys() == b.vertical.keys()
        for k in a.vertical:
            np.testing.assert_array_equal(a.vertical[k], b.vertical[k],
                                          err_msg=k)


@pytest.mark.parametrize("chunk", [None, 97], ids=["whole", "chunk97"])
@pytest.mark.parametrize("combo", list(COMBOS))
def test_mechanisms_match_oracle(chained, combo, chunk):
    """No mechanism, each of failures, telemetry, chains and resize alone,
    and all four: the chunk program, over the whole trace as one chunk
    and in chunks of 97, equals the numpy oracle bit for bit."""
    scn = _mech_scenario(combo)
    got = simulate(scn, chained, chunk_events=chunk)
    _assert_result_equal(got, simulate(scn, chained, engine="ref"))
    if "failures" in COMBOS[combo]:
        assert got.invalidated.sum() > 0


def test_two_lane_sweep_with_every_mechanism_matches_oracle(chained):
    lanes = [_mech_scenario("all"), _mech_scenario("all", "size_aware")]
    for scn, got in zip(lanes, sweep(chained, lanes, chunk_events=97)):
        _assert_result_equal(got, simulate(scn, chained, engine="ref"))
