"""The size-aware plain reference on hand-made traces whose answers are
known, beside the program on Azure-schema traffic, and the check's
verdict on the program run with the wrong routing."""
import dataclasses
import importlib.util
from pathlib import Path

import numpy as np
import pytest

import repro.sim as sim
from bench.gen import stream
from bench.harness import job_slice
from bench.tests.harness_util import run_cell
from repro.core.types import Trace

_PATH = Path(__file__).resolve().parents[1] / "reference" / \
    "kiss_size_aware_lru.py"
_SPEC = importlib.util.spec_from_file_location("kiss_size_aware_lru", _PATH)
ref = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(ref)

HIT, MISS, DROP = ref.HIT, ref.MISS, ref.DROP
# small pools 819.2, 1638.4, 409.6, 3276.8 MB; large pools 204.8, 409.6,
# 102.4, 819.2 MB
SITE = (1024.0, 2048.0, 512.0, 4096.0)


def _cluster(node_mb=SITE, slots=1024, **kw):
    return {"node_mb": list(node_mb), "small_frac": 0.8, "unified": False,
            "routing": "size_aware", "replacement": "lru",
            "max_slots": slots, "cloud_rtt_s": 0.5,
            "cloud_cold_prob": 0.05, **kw}


def _trace(rows):
    """``rows`` of (t, func, size, warm, cold); class 1 from 225 MB."""
    t, f, s, w, c = (np.array(x) for x in zip(*rows))
    return Trace(t=t.astype(np.float32), func_id=f.astype(np.int32),
                 size_mb=s.astype(np.float32),
                 cls=(s >= 225).astype(np.int32),
                 warm_dur=w.astype(np.float32),
                 cold_dur=c.astype(np.float32))


def _replay(rows, **kw):
    got = ref.replay(_cluster(**kw), _trace(rows))
    return got["node"].tolist(), got["outcome"].tolist()


def test_a_large_container_skips_nodes_whose_large_pool_is_too_small():
    # 300 MB fits the large pools of nodes 1 and 3 only; 500 MB node 3's
    node, outcome = _replay([(0, 0, 300, 1, 1), (1, 2, 300, 1, 1),
                             (2, 4, 500, 1, 1), (3, 5, 500, 1, 1)])
    assert node == [1, 1, 3, 3]
    assert outcome == [MISS, MISS, MISS, MISS]


@pytest.mark.parametrize("func", range(8))
def test_the_resteer_picks_the_h_mod_k_th_eligible_node(func):
    caps = ref.capacities(_cluster())
    eligible = [n for n in range(4) if caps[n][1] >= 300]
    assert eligible == [1, 3]
    assert ref.route(caps, func, 1, 300.0) == eligible[func % 4 % 2]
    # a small container fits every node and stays home
    assert ref.route(caps, func, 0, 50.0) == func % 4


def test_no_eligible_node_drops_at_the_home_node():
    node, outcome = _replay([(0, 6, 900, 1, 1), (1, 1, 1000, 1, 1)])
    assert node == [2, 1]
    assert outcome == [DROP, DROP]


@pytest.mark.parametrize("mb,cap", [(1024.0, 819.2), (2048.0, 1638.4)])
def test_fractional_float32_capacities_stay_exact(mb, cap):
    small = ref.capacities(_cluster(node_mb=(mb,)))[0][0]
    assert small == float(np.float32(cap)) != cap
    pool = ref.Pool(small, 1024)
    rng = np.random.default_rng(0)
    t = 0.0
    for i in range(2000):
        size = float(rng.integers(18, 120))
        pool.access(t, int(rng.integers(0, 60)), size, t + 1.0, t + 2.0)
        assert pool.free == float(np.float32(pool.free)) >= 0.0
        resident = sum(c[1] for c in pool.containers.values())
        assert pool.free == small - resident
        t += 0.25
    assert pool.launched - len(pool.containers) > 100   # evictions ran
    pool._release(t + 10.0)
    for k in list(pool.containers):
        pool._unidle(k)
        pool.free = ref.f32(pool.free + pool.containers.pop(k)[1])
    assert pool.free == small


@pytest.mark.parametrize("bad", [{"routing": "sticky"}, {"unified": True},
                                 {"replacement": "fifo"}, {"autoscale": 1}])
def test_a_configuration_it_does_not_implement_is_refused(bad):
    with pytest.raises(ValueError, match="size-aware"):
        ref.replay(_cluster(**bad), _trace([(0, 1, 40, 1, 2)]))


# six nodes with pools of 819.2 and 1,638.4 MB and a 512 MB node that no
# large container fits; 64 slots, so both eviction and drops occur
HET6 = _cluster(node_mb=(1024.0, 2048.0, 512.0, 3072.0, 1024.0, 2048.0),
                slots=64, cloud_rtt_s=0.25)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_reference_agrees_with_the_program(seed):
    trace = stream({"kind": "azure_day", "n_funcs": 300, "n_minutes": 20,
                    "rpm_total": 600.0}, seed)
    job = job_slice(trace, 0, 3000)
    want = ref.replay(HET6, job)
    assert np.bincount(want["outcome"], minlength=3).min() > 0
    assert np.count_nonzero(want["node"] != job.func_id % 6) > 100
    scn = sim.Scenario(**{**HET6, "node_mb": tuple(HET6["node_mb"])})
    for got in (sim.simulate(scn, job, chunk_events=1024),
                sim.simulate(scn, job, engine="ref")):
        assert np.array_equal(got.node, want["node"])
        assert np.array_equal(got.outcome, want["outcome"])
        assert np.array_equal(got.latencies, want["latency"])
        assert got.summary() == want["summary"]


def test_the_program_routed_sticky_is_not_correct(capsys, monkeypatch):
    """A planted routing fault: the timed path runs the site with sticky
    routing in place of size-aware."""
    simulate = sim.simulate

    def sticky(scn, trace, **kw):
        return simulate(dataclasses.replace(scn, routing="sticky"), trace,
                        **kw)

    monkeypatch.setattr(sim, "simulate", sticky)
    rc, line = run_cell(capsys, "day_replay")
    assert rc == 0 and line["correct"] is False
    assert line["checks"]["answers_differ"]["value"] > 0
