"""Run one benchmark cell and print its result as one JSON line.

    python3 bench/run.py --workload stress_replay --seed 7 --seconds 30 --trace 0

The cell is looked up in ``BENCHMARK.json`` at the root (``--root``).
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  ``--rehearsal`` runs the cell at the tiny
size its traffic file gives, on whatever devices JAX has; it is for
tests, never a measurement.  ``--control bf16`` feeds the program
bfloat16-rounded inputs: the control that the check has to call
incorrect.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--root", default=".",
                    help="directory that holds BENCHMARK.json")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--control", choices=("bf16",), default=None)
    return ap.parse_args(argv)


def main(argv=None, t0: float | None = None) -> int:
    args = parse(argv)
    # libtpu logs to a fixed /tmp path unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # run as a script, Python puts this directory first on the path, where
    # its modules would shadow top-level ones of the same name
    sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]
    for p in (str(CHECKOUT), str(CHECKOUT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import harness
    return harness.run(args, time.perf_counter() if t0 is None else t0)


if __name__ == "__main__":
    sys.exit(main(t0=T0))
