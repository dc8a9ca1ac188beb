"""The plain reference on hand-made traces whose answers are known, and
beside a second witness: the program's own sequential oracle."""
import importlib.util
from pathlib import Path

import numpy as np
import pytest

from repro.core.types import Trace

_PATH = Path(__file__).resolve().parents[1] / "reference" / "kiss_sticky_lru.py"
_SPEC = importlib.util.spec_from_file_location("kiss_sticky_lru", _PATH)
ref = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(ref)

HIT, MISS, DROP = ref.HIT, ref.MISS, ref.DROP


def _cluster(node_mb=(125.0,), slots=1024, **kw):
    # 125 MB split 80/20: a 100 MB small pool and a 25 MB large one
    return {"node_mb": list(node_mb), "small_frac": 0.8, "unified": False,
            "routing": "sticky", "replacement": "lru", "max_slots": slots,
            "cloud_rtt_s": 0.5, "cloud_cold_prob": 0.05, **kw}


def _trace(rows):
    """``rows`` of (t, func, size, warm, cold); class 1 from 225 MB."""
    t, f, s, w, c = (np.array(x) for x in zip(*rows))
    return Trace(t=t.astype(np.float32), func_id=f.astype(np.int32),
                 size_mb=s.astype(np.float32),
                 cls=(s >= 225).astype(np.int32),
                 warm_dur=w.astype(np.float32),
                 cold_dur=c.astype(np.float32))


def _outcomes(rows, **kw):
    return ref.replay(_cluster(**kw), _trace(rows))["outcome"].tolist()


def test_an_idle_container_serves_and_a_busy_one_does_not():
    assert _outcomes([(0, 1, 40, 1, 2), (1, 1, 40, 1, 2),
                      (2, 1, 40, 1, 2), (3, 1, 40, 1, 2)]) == [
        MISS, MISS, HIT, HIT]


def test_the_least_recently_used_idle_container_goes_first():
    # 1 and 2 fill the pool; 2 is used again at 10, so 3 evicts 1
    assert _outcomes([(0, 1, 50, 1, 1), (1, 2, 50, 1, 1),
                      (10, 2, 50, 1, 1), (20, 3, 50, 1, 1),
                      (30, 2, 50, 1, 1), (40, 1, 50, 1, 1)]) == [
        MISS, MISS, HIT, MISS, HIT, MISS]


def test_drops_leave_the_pool_as_it_was():
    assert _outcomes([
        (0, 1, 60, 5, 5),     # placed, busy until 5
        (1, 2, 60, 1, 1),     # 40 MB free, 1 is busy: drop
        (2, 3, 101, 1, 1),    # larger than the pool: drop
        (6, 1, 60, 1, 1),     # 1 was not evicted by the drops: hit
    ]) == [MISS, DROP, DROP, HIT]


def test_every_slot_taken_drops():
    assert _outcomes([(0, 1, 10, 1, 1), (1, 2, 10, 1, 1),
                      (2, 3, 10, 1, 1)], slots=2) == [MISS, MISS, DROP]


def test_a_drop_costs_the_round_trip_and_a_cloud_start():
    got = ref.replay(_cluster(), _trace([(0, 1, 500, 1, 3)] * 64))
    coin = np.random.default_rng(0).random(64) < 0.05
    assert got["outcome"].tolist() == [DROP] * 64
    assert got["latency"].tolist() == np.where(coin, 3.5, 1.5).tolist()
    assert got["summary"]["offload_pct"] == 100.0


@pytest.mark.parametrize("seed", [0, 1])
def test_the_reference_agrees_with_the_programs_oracle(seed):
    """Two nodes, tight pools and few slots, so routing, eviction, drops
    and the slot limit all occur."""
    from repro.sim import Scenario, simulate
    from repro.workloads.azure import edge_trace
    trace = edge_trace(seed=seed, duration_s=600.0, scale=4.0)
    cluster = _cluster(node_mb=(1024.0, 512.0), slots=8)
    want = ref.replay(cluster, trace)
    got = simulate(Scenario(**{**cluster, "node_mb": (1024.0, 512.0)}),
                   trace, engine="ref")
    assert np.bincount(want["outcome"], minlength=3).min() > 0
    assert np.array_equal(got.node, want["node"])
    assert np.array_equal(got.outcome, want["outcome"])
    assert np.array_equal(got.latencies, want["latency"])
    assert got.summary() == want["summary"]
