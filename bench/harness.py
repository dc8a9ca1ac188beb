"""One run of one cell: set-up, the measured window, the check against
the configuration's plain reference, and the result line.

Set-up (``setup_s``) runs from process start to the first timed job: the
compile cache, the seed's trace, the cell's scenario and one untimed
warm-up job of the cell's own shape.  The window then runs jobs back to
back, each one ``simulate`` call on a fixed-size slice of the trace
re-zeroed with ``shifted()``, and starts a new job while less than
``--seconds`` have passed; it ends when the last job returns, its
results in host arrays.  With ``--trace 1`` the profiler records the
window's first job.  After the window one job, drawn from the seed, is
replayed by the reference and compared.
"""
from __future__ import annotations

import contextlib
import json
import sys
import time
from pathlib import Path

import numpy as np

from bench import cells, check, devtrace, gen

COMPILE_EVENT = "/jax/core/compile"


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (its
    ``/jax/core/compile*`` durations) and persistent-cache hits/misses."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self.hits = self.misses = 0

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event.startswith(COMPILE_EVENT):
            self.seconds += secs
            self.count += event.endswith("backend_compile_duration")

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    @contextlib.contextmanager
    def listening(self):
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)
        try:
            yield self
        finally:
            mon.unregister_event_duration_listener(self._duration)
            mon.unregister_event_listener(self._event)

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "programs": self.count,
                "cache_hits": self.hits, "cache_misses": self.misses}


def scenario(cell: cells.Cell):
    """The one scenario a job runs, from the configuration's cluster."""
    from repro.sim import Scenario
    kw = dict(cell.config["cluster"])
    kw["node_mb"] = tuple(kw["node_mb"])
    return Scenario(**kw)


def job_slice(trace, start: int, n: int):
    """Events ``[start, start + n)`` of ``trace``, re-zeroed."""
    from repro.core.types import Trace
    return Trace(*(None if a is None else a[start:start + n]
                   for a in trace)).shifted()


def device_info(devs: list) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": (max(peaks) if all(p is not None
                                                    for p in peaks)
                                  else None)}


def check_devices(cell: cells.Cell, rehearsal: bool):
    """The devices the cell runs on, or ``None`` (with the reason on
    stderr) where this machine has no TPU or too few chips."""
    import jax
    devs = jax.devices()
    if not rehearsal and devs[0].platform != "tpu":
        print(f"bench: no TPU, JAX found {devs[0].platform}", file=sys.stderr)
        return None
    if len(devs) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        return None
    return devs[:cell.chips]


def window(call, jobs, seconds: float, capture):
    """Jobs back to back until ``seconds`` have passed; the profiler
    records the first when ``capture`` is given.  Returns each job's
    ``(events, result)`` and the window's length."""
    from jax.profiler import TraceAnnotation
    done = []
    start = time.perf_counter()
    while True:
        traced = capture is not None and not done
        with (capture.recording() if traced else contextlib.nullcontext()):
            with TraceAnnotation("bench.job_prep"):
                job = jobs(len(done))
            with TraceAnnotation("bench.call"):
                done.append((len(job), call(job)))
        if time.perf_counter() - start >= seconds:
            return done, time.perf_counter() - start


def run(args, t0: float) -> int:
    cell = cells.load(Path(args.root).resolve(), args.workload,
                      args.rehearsal)
    devs = check_devices(cell, args.rehearsal)
    if devs is None:
        return 3
    from jax.profiler import TraceAnnotation

    import repro.sim as sim
    from repro.sim.compile_cache import enable_compile_cache

    enable_compile_cache()
    clock = CompileClock()
    spec = cell.spec
    n, slices = int(spec["job_events"]), int(spec["slices"])
    chunk = spec.get("chunk_events")
    prep = check.round_bf16 if args.control == "bf16" else (lambda t: t)
    with clock.listening():
        trace = gen.stream(spec["stream"], args.seed)
        # the slices are spread evenly over the trace
        stride = len(trace) // slices
        if stride < n:
            raise RuntimeError(f"{cell.name}: the seed's trace has "
                               f"{len(trace)} events, fewer than {slices} "
                               f"jobs of {n}")
        scn = scenario(cell)

        def call(job):
            return sim.simulate(scn, job, chunk_events=chunk)

        call(prep(job_slice(trace, 0, n)))
        setup_s = time.perf_counter() - t0
        at_setup = clock.snapshot()
        capture = devtrace.Capture() if args.trace else None
        done, window_s = window(
            call, lambda j: prep(job_slice(trace, (j % slices) * stride, n)),
            args.seconds, capture)
        in_window = {k: v - at_setup[k]
                     for k, v in clock.snapshot().items()}
        device = device_info(devs)

    # the check, after the window: one job drawn from the seed, replayed
    # by the reference on the slice as the trace has it
    jobs = len(done)
    events = sum(m for m, _ in done)
    k = int(np.random.default_rng(args.seed).integers(jobs))
    with TraceAnnotation("bench.reference"):
        ref_t = time.perf_counter()
        want = cell.reference.replay(
            cell.config["cluster"], job_slice(trace, (k % slices) * stride,
                                              n))
        counts = check.compare(done[k][1], want)
        ref_s = time.perf_counter() - ref_t

    reduced = devtrace.reduce(capture.planes(), cell.chips) if capture \
        else None
    ctx = {"setup_s": setup_s, "window_s": window_s, "jobs": jobs,
           "events": events, "steps": n, "chips": cell.chips,
           "compile_s": at_setup["compile_s"], "profile": reduced}
    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        v = m.read(ctx)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    if reduced is not None:
        device["busy_s"] = float(np.mean(reduced["busy_s"]))
        device["window_s"] = reduced["window_s"]
    line = {"correct": check.verdict(counts), "attempted": events,
            "failed": counts["answers_differ"], "metrics": metrics,
            "device": device}
    if args.trace:
        line["breakdown"] = {k: reduced[k] if reduced else []
                             for k in ("device_ops", "idle_gaps")}
    line["checks"] = {name: {"value": counts[name], "limit": lim}
                      for name, lim in check.LIMITS.items()}
    print(json.dumps(line), flush=True)
    print(json.dumps({"run": {
        "workload": cell.name, "seed": args.seed, "control": args.control,
        "rehearsal": args.rehearsal, "jobs": jobs, "job_events": n,
        "stream_events": len(trace), "window_s": window_s,
        "setup_s": setup_s, "setup": at_setup, "in_window": in_window,
        "reference_s": ref_s, "checked_job": k,
        "answers_compared": counts["answers_compared"]}}),
        file=sys.stderr, flush=True)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    return 0
