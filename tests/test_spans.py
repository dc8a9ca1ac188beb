"""The simulator's own profiler spans and the named scopes of its scan step.

``simulate`` and ``sweep`` open ``sim.*`` host spans
(``jax.profiler.TraceAnnotation``) around their phases, with counters as
span arguments, and the scan step names its layers with
``jax.named_scope``.  With no profiler running a span costs about a
microsecond and a scope nothing.  These tests record a trace on the CPU
and read the spans back, and look for the scopes in the lowered chunk
program.
"""
import glob
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.cluster.engine import (Carry, _chunk_runner, _drop_size,
                                  _host_xs, _lane_data, _result_counts,
                                  _tel_init, _xs_slice, init_cluster,
                                  lower_chunk_program)
from repro.core.types import Trace
from repro.sim import Scenario, simulate, sweep

from conftest import quantized_trace

EVENTS, CHUNK = 300, 128          # three chunks, the last one partial
CHUNK_SPANS = ("sim.slice", "sim.dispatch", "sim.wait", "sim.fetch")
SCOPES = ("step.route", "pool.step", "pool.evict", "step.writeback")


@pytest.fixture(scope="module")
def trace():
    return quantized_trace(np.random.default_rng(13), EVENTS)


def _spans(tmp_path, fn):
    """``fn()`` under the profiler: its result and its ``sim.*`` spans as
    ``(start, end, name, args)`` in start order."""
    fn()                                   # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
             for p in ProfileData.from_file(path).planes for ln in p.lines
             for e in ln.events if e.name.startswith("sim.")]
    return out, sorted(spans, key=lambda s: (s[0], -s[1]))


def _inside(inner, outer) -> bool:
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def _named(spans, name):
    return [s for s in spans if s[2] == name]


def _check_chunks(spans, root, h2d_bytes, d2h_bytes):
    """Every chunk span sits in ``root`` with one of each chunk-phase span
    inside it, in order, and carries its index, real events and pad;
    ``d2h_bytes(events)`` is what a chunk of that many events fetches."""
    chunks = _named(spans, "sim.chunk")
    assert [c[3]["index"] for c in chunks] == [0, 1, 2]
    assert [c[3]["events"] for c in chunks] == [128, 128, 44]
    assert [c[3]["pad"] for c in chunks] == [0, 0, 84]
    for c in chunks:
        assert _inside(c, root)
        phases = [s for s in spans if s[2] in CHUNK_SPANS and _inside(s, c)]
        assert [s[2] for s in phases] == list(CHUNK_SPANS)
        dispatch, fetch = phases[1], phases[3]
        assert dispatch[3] == {"h2d_bytes": h2d_bytes}
        assert fetch[3] == {"d2h_bytes": d2h_bytes(c[3]["events"])}
    assert len(_named(spans, "sim.slice")) == len(chunks)


def test_chunked_simulate_span_tree_and_counters(tmp_path, trace):
    scn = Scenario.kiss(2048.0)
    res, spans = _spans(tmp_path, lambda: simulate(scn, trace,
                                                   chunk_events=CHUNK))
    (root,) = _named(spans, "sim.simulate")
    assert root[3] == {"events": EVENTS, "chunks": 3}
    (fp,) = _named(spans, "sim.fingerprint")
    assert fp[3] == {"bytes": sum(np.asarray(a).nbytes for a in trace
                                  if a is not None)}
    (prep,) = _named(spans, "sim.prep")
    (result,) = _named(spans, "sim.result")
    assert all(_inside(s, root) for s in (fp, prep, result))
    # front door, then the engine's prep, the chunks, the result
    chunks = _named(spans, "sim.chunk")
    first, last = chunks[0], chunks[-1]
    assert fp[1] <= prep[0] and prep[1] <= first[0] and last[1] <= result[0]
    # eight 4-byte event columns of the padded chunk go up; each real
    # event's node and outcome (int32) come back
    _check_chunks(spans, root, CHUNK * 8 * 4, lambda n: n * 2 * 4)
    assert len(spans) <= 4 + 5 * 3
    assert res.summary() == simulate(scn, trace).summary()


def test_chunked_sweep_span_tree_and_counters(tmp_path, trace):
    lanes = [Scenario.kiss(2048.0), Scenario.baseline(2048.0)]
    res, spans = _spans(tmp_path, lambda: sweep(trace, lanes,
                                                chunk_events=CHUNK))
    (root,) = _named(spans, "sim.sweep")
    assert root[3] == {"events": EVENTS, "lanes": 2, "chunks": 3}
    for name in ("sim.fingerprint", "sim.prep", "sim.result"):
        (s,) = _named(spans, name)
        assert _inside(s, root)
    # the lanes share the uploaded events; each lane brings back the
    # whole padded chunk of nodes and outcomes
    _check_chunks(spans, root, CHUNK * 8 * 4, lambda n: 2 * 2 * CHUNK * 4)
    assert len(spans) <= 4 + 5 * 3
    assert [r.summary() for r in res] == [r.summary()
                                          for r in sweep(trace, lanes)]


def test_monolithic_simulate_spans(tmp_path, trace):
    """A whole-trace call runs the chunk program once: one ``sim.chunk``
    of every event, with no pad."""
    scn = Scenario.kiss(2048.0)
    _, spans = _spans(tmp_path, lambda: simulate(scn, trace))
    (root,) = _named(spans, "sim.simulate")
    assert root[3] == {"events": EVENTS, "chunks": 1}
    names = [s[2] for s in spans if s is not root]
    assert names == ["sim.fingerprint", "sim.prep", "sim.chunk",
                     "sim.slice", "sim.dispatch", "sim.wait", "sim.fetch",
                     "sim.result"]
    assert all(_inside(s, root) for s in spans)
    (chunk,) = _named(spans, "sim.chunk")
    assert chunk[3] == {"index": 0, "events": EVENTS, "pad": 0}
    assert all(_inside(s, chunk) for s in spans if s[2] in CHUNK_SPANS)
    (dispatch,) = _named(spans, "sim.dispatch")
    assert dispatch[3] == {"h2d_bytes": EVENTS * 8 * 4}
    (fetch,) = _named(spans, "sim.fetch")
    assert fetch[3] == {"d2h_bytes": 2 * EVENTS * 4}


# four nodes of 1, 2, 0.5 and 4 GB split 80/20: small pools of 819.2,
# 1638.4, 409.6 and 3276.8 MB, large pools of 204.8, 409.6, 102.4 and
# 819.2 MB; size-aware routing over them, by hand (h = func mod 4):
ROUTED = [  # (func, size, class, node)
    (0, 300.0, 1, 1),   # large pools of 1 and 3 hold it: h 0 -> node 1
    (1, 50.0, 0, 1),    # every small pool holds it: stays home
    (3, 500.0, 1, 3),   # only node 3 holds it, its home
    (2, 1000.0, 1, 2),  # no pool holds it: home, dropped
    (5, 250.0, 1, 3),   # nodes 1 and 3: h 1 -> node 3
    (6, 900.0, 1, 2),   # no pool holds it: home, dropped
]
RESTEERED, UNHOSTABLE = 2, 2
# every placed event finds room: the two unhostable ones drop
HITS, MISSES, DROPS = 0, 4, 2


@pytest.mark.parametrize("chunk", [None, 4], ids=["monolithic", "chunked"])
def test_routing_counters_on_a_heterogeneous_site(tmp_path, chunk):
    f, size, cls, node = (np.array(c) for c in zip(*ROUTED))
    n = len(ROUTED)
    trace = Trace(t=np.arange(n, dtype=np.float32), func_id=f.astype(
        np.int32), size_mb=size.astype(np.float32), cls=cls.astype(np.int32),
        warm_dur=np.ones(n, np.float32), cold_dur=np.full(n, 2, np.float32))
    scn = Scenario(node_mb=(1024.0, 2048.0, 512.0, 4096.0), small_frac=0.8,
                   unified=False, routing="size_aware")
    res, spans = _spans(tmp_path, lambda: simulate(scn, trace,
                                                   chunk_events=chunk))
    assert res.node.tolist() == node.tolist()
    assert np.count_nonzero(res.node != f % 4) == RESTEERED
    (prep,) = _named(spans, "sim.prep")
    assert prep[3] == {"nodes": 4}
    (result,) = _named(spans, "sim.result")
    assert result[3] == {"hits": HITS, "misses": MISSES, "drops": DROPS,
                         "resteered": RESTEERED, "unhostable": UNHOSTABLE}


def test_routing_counters_of_a_unified_pool(tmp_path):
    """A unified node hosts either class in its whole memory: only a
    container larger than every node is unhostable."""
    trace = Trace(t=np.float32([0, 1, 2]), func_id=np.int32([0, 1, 2]),
                  size_mb=np.float32([300, 900, 1100]),
                  cls=np.int32([1, 1, 1]), warm_dur=np.ones(3, np.float32),
                  cold_dur=np.full(3, 2, np.float32))
    scn = Scenario(node_mb=(1024.0, 512.0), small_frac=0.8,
                   unified=(True, False), routing="sticky")
    _, spans = _spans(tmp_path, lambda: simulate(scn, trace))
    (result,) = _named(spans, "sim.result")
    # sticky: the 900 MB one goes home to node 1, whose large pool
    # (102.4 MB) cannot hold it
    assert result[3] == {"hits": 0, "misses": 1, "drops": 2,
                         "resteered": 0, "unhostable": 1}


def test_routing_counters_cost_nothing_without_a_profiler():
    """Outside a profile ``sim.result`` carries no counters, so the
    O(events) count is not paid on an unprofiled call."""
    trace = Trace(t=np.float32([0, 1]), func_id=np.int32([0, 1]),
                  size_mb=np.float32([300, 50]), cls=np.int32([1, 0]),
                  warm_dur=np.ones(2, np.float32),
                  cold_dur=np.full(2, 2, np.float32))
    cfg = Scenario(node_mb=(1024.0, 512.0),
                   routing="size_aware").to_cluster_config()
    assert _result_counts(cfg, trace, np.int32([0, 1]),
                          np.int32([1, 1])) == {}


@pytest.mark.parametrize("chunk", [None, CHUNK], ids=["monolithic",
                                                     "chunked"])
def test_outcome_counters_on_sim_result(tmp_path, trace, chunk):
    """Under a profiler ``sim.result`` counts the outcome codes: the
    branches the pool step took (hit, miss, drop)."""
    scn = Scenario.kiss(1024.0)
    res, spans = _spans(tmp_path, lambda: simulate(scn, trace,
                                                   chunk_events=chunk))
    (result,) = _named(spans, "sim.result")
    counts = np.bincount(res.outcome, minlength=3).tolist()
    assert [result[3][k] for k in ("hits", "misses", "drops")] == counts
    assert min(counts) > 0, counts
    assert sum(counts) == EVENTS


@pytest.mark.parametrize("mode", ["gather", "vmap", "fused"])
def test_scopes_in_the_lowered_chunk_program(trace, mode):
    cfg = Scenario.cluster((1024.0, 2048.0)).to_cluster_config()
    text = lower_chunk_program(cfg, trace, mode=mode,
                               chunk_events=CHUNK).as_text(debug_info=True)
    for scope in SCOPES:
        assert scope in text, scope
    # under vmap the inner scope reads "pool.step/vmap(pool.evict)"
    assert re.search(r"pool\.step/(vmap\()?pool\.evict", text)


def test_accumulator_scope_in_a_telemetry_chunk_program(trace):
    cfg = Scenario.kiss(2048.0).to_cluster_config()
    xs, fills = _host_xs(trace, cfg.n_nodes, _drop_size(cfg), telemetry=64)
    x = _xs_slice(xs, fills, 0, CHUNK, CHUNK)
    carry = Carry(init_cluster(cfg), tel=_tel_init(5, cfg.n_nodes))
    lowered = _chunk_runner(cfg.n_nodes, "gather").lower(
        carry, x, *_lane_data(cfg, None))
    assert "step.acc" in lowered.as_text(debug_info=True)
