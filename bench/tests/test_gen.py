"""The copied generator equals the program's, array for array, and is
pinned by digest so the yardstick cannot move with the program."""
import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

from bench.gen import stream

BENCH = Path(__file__).resolve().parents[1]
FIELDS = ("t", "func_id", "size_mb", "cls", "warm_dur", "cold_dur")


def _stream(mix):
    return json.loads((BENCH / "traffic" / f"{mix}.json")
                      .read_text())["stream"]


def _digest(trace) -> str:
    h = hashlib.sha256()
    for f in FIELDS:
        h.update(np.ascontiguousarray(getattr(trace, f)).tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_edge_copy_equals_program(seed):
    azure = pytest.importorskip("repro.workloads.azure")
    # the stress mix's parameters over its first 10 minutes
    spec = {**_stream("stress_2h_chunked"), "duration_s": 600.0}
    got = stream(spec, seed)
    want = azure.synthesize(azure.TraceConfig(seed=seed, **{
        k: v for k, v in spec.items() if k != "kind"}))
    for f in FIELDS:
        x, y = getattr(got, f), getattr(want, f)
        assert x.dtype == y.dtype and np.array_equal(x, y), f


# digests of the copies at seed 0, recorded when they equalled the program
PINNED = {"stress_2h_chunked": "486c763aab8df5fa"}


@pytest.mark.parametrize("mix", sorted(PINNED))
def test_copies_are_pinned(mix):
    assert _digest(stream(_stream(mix), 0)) == PINNED[mix]


def test_unknown_stream_parameter_is_refused():
    with pytest.raises(ValueError, match="unknown"):
        stream({"kind": "edge", "duration_s": 60.0, "rps": 3.0}, 0)
