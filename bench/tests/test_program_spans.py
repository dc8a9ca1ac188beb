"""Readings that rest on the program's own ``sim.*`` spans, from the
reduction that every traced run already makes."""
import json
from pathlib import Path

import pytest

from bench import cells, devtrace
from bench.tests.harness_util import ROOT

FIXTURE = Path(__file__).resolve().parent / "fixtures" / \
    "trace_stress_replay.json"


def _readers():
    return {m.name: m.read for m in cells.load(ROOT, "stress_replay")
            .per_layer}


def _profile(idle_gaps, window_s=10.0, busy_s=4.0):
    return {"profile": {"window_s": window_s, "busy_s": [busy_s],
                        "device_ops": [], "idle_gaps": idle_gaps},
            "steps": 4, "compile_s": 1.0}


def test_host_gap_on_known_intervals():
    # 6 s idle: 5.9 s while the host waited on the job's programs, the
    # rest in its own phases and in work outside any program span
    ctx = _profile([["sim.wait", 5.9], ["sim.prep", 0.06],
                    ["DevicePut", 0.03], ["sim.result", 0.01]])
    assert _readers()["host_gap_pct.replay"](ctx) == pytest.approx(1.0)


def test_host_gap_counts_a_wait_that_left_the_top_spans_as_zero():
    ctx = _profile([["sim.prep", 0.5]], window_s=10.0, busy_s=9.5)
    assert _readers()["host_gap_pct.replay"](ctx) == pytest.approx(5.0)


def test_host_gap_needs_the_program_spans():
    """A program without ``sim.*`` spans (the one before them): the reader
    finds nothing; nor does a trace without device work."""
    read = _readers()["host_gap_pct.replay"]
    assert read(_profile([["np.asarray(jax.Array)", 5.9],
                          ["bench.call", 0.1]])) is None
    assert read({"profile": None}) is None


@pytest.mark.parametrize("name,want", [
    ("device_idle_pct.replay", 53.6049588217995),
    ("step_device_us.replay", 35.73807360839844),
    ("host_gap_pct.replay", 0.4654591884186774),
])
def test_readings_of_the_recorded_stress_trace(name, want):
    fx = json.loads(FIXTURE.read_text())
    ctx = {"profile": devtrace.reduce(fx["planes"], fx["chips"]),
           "steps": fx["steps"], "compile_s": 1.0}
    assert _readers()[name](ctx) == pytest.approx(want, rel=1e-12)


def test_the_recorded_stress_trace_ends_where_buffers_dropped():
    """The TPU stopped recording 4.72 s into the 10.1 s job: the device's
    busy time there covers the first chunk only, so the idle share of the
    traced job is mostly time with no device events at all."""
    fx = json.loads(FIXTURE.read_text())
    lo, hi, _ = devtrace.window(fx["planes"])
    (tpu,) = devtrace.device_planes(fx["planes"])
    (drop,) = [e for ln in tpu["lines"] for e in ln["events"]
               if e[2] == "Trace Buffers Dropped"]
    busy = devtrace.busy_intervals(tpu)
    assert (drop[0] - lo) / 1e9 == pytest.approx(4.72, abs=0.01)
    assert max(b for _, b in busy) <= drop[0] < hi
    assert devtrace.total(devtrace.gaps(devtrace.clip(busy, lo, hi),
                                        drop[0], hi)) / 1e9 > 5.3
