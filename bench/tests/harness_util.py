"""Running the harness in the test process and reading its result."""
import json
from pathlib import Path

from bench import run

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


def run_cell(capsys, workload, *extra, seed=7, seconds=0.5, trace=0,
             root=ROOT):
    """``(exit code, result line or None)`` of one rehearsal run."""
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace),
                   "--root", str(root), "--rehearsal", *extra])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(out[-1]) if out else None)
