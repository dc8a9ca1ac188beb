"""The check has to call wrong answers wrong: the bfloat16 control, and
the timed path broken underneath in each way a replay cell can break."""
import dataclasses
import json

import numpy as np
import pytest

import repro.sim as sim
from bench.tests.harness_util import BENCH, CELLS, ROOT, run_cell


@pytest.mark.parametrize("cell", CELLS)
def test_bf16_control_is_not_correct(capsys, cell):
    rc, line = run_cell(capsys, cell, "--control", "bf16")
    assert rc == 0 and line["correct"] is False
    assert line["checks"]["answers_differ"]["value"] > 0


def _raw(res, **fields):
    return dataclasses.replace(res, raw=dataclasses.replace(res.raw,
                                                            **fields))


def answer_altered(simulate):
    """One invocation's outcome changed where it is produced."""
    def sim_(scn, trace, **kw):
        res = simulate(scn, trace, **kw)
        out = np.array(res.outcome)
        out[len(out) // 2] = (out[len(out) // 2] + 1) % 3
        return _raw(res, outcome=out)
    return sim_


def state_frozen(simulate):
    """Every step returns the pool state unchanged: each invocation meets
    the empty pool it started from, so none is ever a hit."""
    def sim_(scn, trace, **kw):
        res = simulate(scn, trace, **kw)
        cap = np.array([scn.node_mb[0] * scn.small_frac[0],
                        scn.node_mb[0] * (1 - scn.small_frac[0])])
        fits = np.asarray(trace.size_mb) <= cap[np.asarray(trace.cls)]
        return _raw(res, outcome=np.where(fits, 1, 2).astype(np.int32))
    return sim_


def carry_dropped(simulate):
    """The pool state is not handed from one chunk to the next: the
    second half of the job runs from an empty cluster."""
    def sim_(scn, trace, chunk_events=None, **kw):
        res = simulate(scn, trace, chunk_events=chunk_events, **kw)
        half = len(trace) // 2
        tail = simulate(scn, trace.replace(**{
            f: getattr(trace, f)[half:] for f in
            ("t", "func_id", "size_mb", "cls", "warm_dur", "cold_dur")}),
            **kw)
        return _raw(res, node=np.concatenate([res.node[:half], tail.node]),
                    outcome=np.concatenate([res.outcome[:half],
                                            tail.outcome]))
    return sim_


def _faults(cell: dict) -> list:
    """The faults a cell can have, from what its traffic file says it
    runs."""
    traffic = json.loads((ROOT / "bench" / "traffic"
                          / f"{cell['traffic']}.json").read_text())
    return [answer_altered, state_frozen] + (
        [carry_dropped] if traffic.get("chunk_events") else [])


FAULTS = [(w["name"], f) for w in BENCH["workloads"] for f in _faults(w)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_a_broken_timed_path_is_not_correct(capsys, monkeypatch, cell,
                                            fault):
    monkeypatch.setattr(sim, "simulate", fault(sim.simulate))
    rc, line = run_cell(capsys, cell)
    assert rc == 0 and line["correct"] is False
    assert sum(c["value"] for c in line["checks"].values()) > 0
