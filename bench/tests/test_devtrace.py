"""The reduction from a device trace to busy, idle and op time."""
import json
from pathlib import Path

import pytest

from bench import devtrace

FIXTURES = Path(__file__).resolve().parent / "fixtures"


def _plane(name, lines):
    return {"name": name, "lines": [{"name": k, "events": v}
                                    for k, v in lines.items()]}


def test_union_merges_overlaps_and_drops_empty():
    assert devtrace.union([(5, 7), (0, 2), (1, 3), (7, 9), (4, 4)]) == [
        [0, 3], [5, 9]]


def test_clip_and_gaps():
    busy = devtrace.clip([[0, 3], [5, 9], [12, 20]], 2, 14)
    assert busy == [[2, 3], [5, 9], [12, 14]]
    assert devtrace.gaps(busy, 1, 15) == [[1, 2], [3, 5], [9, 12], [14, 15]]
    assert devtrace.total(busy) == 7


def _synthetic(devices=2):
    host = _plane("/host:CPU", {"python3": [
        [100, 50, "bench.job_prep"], [150, 850, "bench.call"],
        [200, 100, "ExecuteHelper"], [600, 300, "ResultAssembly"]]})
    devs = []
    for d in range(devices):
        shift = 10 * d
        devs.append(_plane(f"/device:TPU:{d}", {
            "XLA Modules": [[300 + shift, 200, "jit_run"],
                            [350 + shift, 100, "jit_run"],
                            [900, 400, "jit_tail"]],
            "XLA Ops": [[300 + shift, 150, "fusion.1"],
                        [450 + shift, 50, "sort.2"],
                        [900, 100, "fusion.1"]]}))
    return [host] + devs


def test_reduce_on_synthetic_intervals():
    r = devtrace.reduce(_synthetic(), devices=2)
    # window [100, 1000]; busy [300, 500] + [900, 1000] on device 0,
    # [310, 510] + [900, 1000] on device 1
    assert r["window_s"] == pytest.approx(900e-9)
    assert r["busy_s"] == pytest.approx([300e-9, 300e-9])
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(250e-9)]
    idle = dict(r["idle_gaps"])
    # gaps [100,150) prep, [150,200)+[500,600) call, [200,300) execute,
    # [600,900) result assembly; device 1 idles 10 more before its
    # first program and 10 less after it, both inside bench.call
    assert idle["bench.job_prep"] == pytest.approx(50e-9)
    assert idle["ResultAssembly"] == pytest.approx(300e-9)
    assert sum(idle.values()) == pytest.approx(600e-9)


def test_reduce_uses_only_the_cells_devices():
    r = devtrace.reduce(_synthetic(devices=4), devices=1)
    assert r["busy_s"] == pytest.approx([300e-9])


def test_reduce_finds_nothing_without_a_device_or_a_window():
    host_only = [p for p in _synthetic() if p["name"].startswith("/host")]
    assert devtrace.reduce(host_only, devices=1) is None
    no_window = [p for p in _synthetic() if p["name"].startswith("/device")]
    assert devtrace.reduce(no_window, devices=1) is None


@pytest.mark.parametrize("path", sorted(FIXTURES.glob("trace_*.json")),
                         ids=lambda p: p.stem)
def test_reduce_on_a_recorded_chip_trace(path):
    fx = json.loads(path.read_text())
    r = devtrace.reduce(fx["planes"], fx["chips"])
    want = fx["reduced"]
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-12)
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-12)
    assert 0 < min(r["busy_s"]) <= r["window_s"]
    assert [k for k, _ in r["device_ops"]] == [k for k, _ in
                                               want["device_ops"]]
    assert [k for k, _ in r["idle_gaps"]] == [k for k, _ in
                                              want["idle_gaps"]]


def test_innermost_names_each_stretch_by_the_deepest_open_event():
    ev = [[0, 100, "call"], [10, 20, "a"], [12, 5, "a.1"], [50, 10, "b"],
          [120, 10, "later"]]
    assert devtrace.innermost(ev) == [
        [0, 10, "call"], [10, 12, "a"], [12, 17, "a.1"], [17, 30, "a"],
        [30, 50, "call"], [50, 60, "b"], [60, 100, "call"],
        [120, 130, "later"]]
    idle = devtrace.attribute([[5, 15], [95, 125]],
                              devtrace.innermost(ev))
    assert dict(idle) == pytest.approx({
        "call": 10e-9, "a": 2e-9, "a.1": 3e-9, "(no host span)": 20e-9,
        "later": 5e-9})
