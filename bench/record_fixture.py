"""Record a trace fixture for ``bench/tests/test_devtrace.py`` on the chip.

    python3 bench/record_fixture.py --workload stress_replay --seed 7

Runs the cell's warm-up job, profiles one job as a traced run does, and
writes
``bench/tests/fixtures/trace_<workload>.json``: a trimmed copy of the
trace (the longest events of each line) and the reduction of that copy,
which the test then pins.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
PER_LINE = 400


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]
    sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]
    import jax
    from jax.profiler import TraceAnnotation

    import repro.sim as sim
    from bench import cells, devtrace, gen
    from bench.harness import job_slice, scenario
    from repro.sim.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell = cells.load(HERE.parent, args.workload)
    n = int(cell.spec["job_events"])
    trace = gen.stream(cell.spec["stream"], args.seed)
    scn = scenario(cell)

    def call(job):
        return sim.simulate(scn, job,
                            chunk_events=cell.spec.get("chunk_events"))

    call(job_slice(trace, 0, n))
    capture = devtrace.Capture()
    with capture.recording():
        with TraceAnnotation("bench.job_prep"):
            job = job_slice(trace, n, n)
        with TraceAnnotation("bench.call"):
            call(job)
    planes = capture.planes()
    lo, hi, _ = devtrace.window(planes)
    kept = devtrace.trim(planes, lo, hi, PER_LINE)
    out = HERE / "tests" / "fixtures" / f"trace_{args.workload}.json"
    out.write_text(json.dumps({
        "cell": args.workload, "seed": args.seed, "chips": cell.chips,
        "steps": n, "recorded_on": jax.devices()[0].device_kind,
        "full": devtrace.reduce(planes, cell.chips),
        "planes": kept, "reduced": devtrace.reduce(kept, cell.chips)}))
    print(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
