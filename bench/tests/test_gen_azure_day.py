"""The copied Azure-schema day equals the program's schema synthesizer and
its expansion, array for array, and is pinned by digest so the yardstick
cannot move with the program."""
import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

from bench.gen import stream

BENCH = Path(__file__).resolve().parents[1]
FIELDS = ("t", "func_id", "size_mb", "cls", "warm_dur", "cold_dur")
MIX = "azure_day_chunked"
#: the digest of the mix's whole day at seed 0, recorded when it equalled
#: the program
PINNED = "1d7fd34e56df43ce"
TABLE_KEYS = ("n_funcs", "n_minutes", "rpm_total", "large_frac",
              "small_large_ratio", "funcs_per_app", "zipf_a",
              "diurnal_depth")


def _stream():
    return json.loads((BENCH / "traffic" / f"{MIX}.json")
                      .read_text())["stream"]


def _digest(trace) -> str:
    h = hashlib.sha256()
    for f in FIELDS:
        h.update(np.ascontiguousarray(getattr(trace, f)).tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("seed", [0, 1, 3_000_000_019])
def test_azure_day_copy_equals_program(seed):
    replay = pytest.importorskip("repro.workloads.replay")
    # the mix's parameters over its first two hours
    spec = {**_stream(), "n_minutes": 120}
    got = stream(spec, seed)
    params = {k: v for k, v in spec.items() if k != "kind"}
    want = replay.trace_from_tables(
        replay.synthesize_azure_schema(replay.SchemaConfig(
            seed=seed, **{k: params.pop(k) for k in TABLE_KEYS})),
        replay.ReplayConfig(seed=seed, **params))
    assert len(want) > 100_000
    for f in FIELDS:
        x, y = getattr(got, f), getattr(want, f)
        assert x.dtype == y.dtype and np.array_equal(x, y), f


def test_azure_day_is_pinned():
    assert _digest(stream(_stream(), 0)) == PINNED


def test_unknown_azure_day_parameter_is_refused():
    with pytest.raises(ValueError, match="unknown"):
        stream({"kind": "azure_day", "n_minutes": 10, "rps": 3.0}, 0)
