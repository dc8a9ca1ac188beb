"""The harness end to end at rehearsal size on the CPU."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run
from bench.tests.harness_util import BENCH, CELLS, ROOT, run_cell

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def _metrics_of(cell, kind):
    return {m["name"] for m in BENCH[kind]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_line(capsys, cell):
    rc, line = run_cell(capsys, cell)
    assert rc == 0
    assert list(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == _metrics_of(cell, "end_to_end")
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["count"] >= next(
        w["chips"] for w in BENCH["workloads"] if w["name"] == cell)
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    assert line["checks"] == {name: {"value": 0, "limit": 0} for name in (
        "answers_differ", "latencies_differ", "summary_differ")}


def test_traced_rehearsal_line(capsys):
    rc, line = run_cell(capsys, CELLS[0], trace=1)
    assert rc == 0
    assert list(line) == KEYS[:5] + ["breakdown", "checks"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    # a CPU has no device plane: the device metrics are left out (and in
    # this process the cell's programs may already be compiled)
    assert set(line["metrics"]) <= {"compile_s"}
    assert "busy_s" not in line["device"]


def test_no_tpu_without_rehearsal(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds",
                   "1", "--trace", "0", "--root", str(ROOT)])
    assert rc != 0
    assert capsys.readouterr().out == ""


def _tree_digest(path: Path) -> str:
    h = hashlib.sha256()
    for p in sorted(path.rglob("*")):
        if p.is_file() and "__pycache__" not in p.parts:
            h.update(str(p.relative_to(path)).encode() + p.read_bytes())
    return h.hexdigest()


def test_a_cell_made_only_of_new_files(capsys, tmp_path):
    """A later PR adds a configuration, a mix and a metric as files and
    entries; no file the benchmark has is edited."""
    before = _tree_digest(ROOT / "bench"), (ROOT / "BENCHMARK.json"
                                            ).read_bytes()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "metrics").mkdir()
    config = json.loads((ROOT / "bench/configs/kiss_stress_10gb.json")
                        .read_text())
    config["cluster"]["node_mb"] = [2048.0, 1024.0]
    (tmp_path / "bench/configs/edge_pair.json").write_text(
        json.dumps(config))
    (tmp_path / "bench/traffic/bursty_edge.json").write_text(json.dumps({
        "stream": {"kind": "edge", "duration_s": 300.0,
                   "burst_rate_mult": 3.0, "burst_fraction": 0.2},
        "slices": 3, "rehearsal": {"job_events": 256}}))
    (tmp_path / "bench/metrics/jobs_run.py").write_text(
        "def read(ctx):\n    return ctx['jobs']\n")
    bench["configs"].append({
        "name": "edge_pair", "source": "https://arxiv.org/abs/2502.12540",
        "file": "bench/configs/edge_pair.json", "reduced": ["node_mb"],
        "why": "test"})
    bench["workloads"] = [{"name": "bursty_pair", "config": "edge_pair",
                           "traffic": "bursty_edge", "chips": 1,
                           "why": "test"}]
    bench["end_to_end"].append({"name": "jobs_run", "unit": "jobs",
                                "better": "higher", "bound": 0.25,
                                "source": "host_clock",
                                "workloads": ["bursty_pair"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    rc, line = run_cell(capsys, "bursty_pair", root=tmp_path)
    assert rc == 0 and line["correct"] is True
    assert set(line["metrics"]) == {"setup_s", "jobs_run"}
    assert line["metrics"]["jobs_run"]["value"] >= 1
    assert (_tree_digest(ROOT / "bench"),
            (ROOT / "BENCHMARK.json").read_bytes()) == before


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's paths has
    no program to measure: the run fails and prints no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearsal"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
