"""Benchmark driver — one module per paper figure/table plus beyond-paper
benchmarks.  Prints ``name,us_per_call,derived`` CSV lines and writes a
machine-readable ``results/BENCH_<suite>.json`` per suite (parsed rows +
wall-clock + any structured payload the suite returns) so the performance
trajectory is trackable across commits.

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run fig7 fig9  # filter by prefix

Suites return either ``list[str]`` (CSV lines) or ``(list[str], payload)``
where ``payload`` is a JSON-serializable dict (e.g. the stable-keyed
``Result.summary()`` dicts from ``repro.sim``).

Every suite's wall-clock is split into ``compile_s`` (XLA compilation
time, measured through ``jax.monitoring``'s event-duration stream) and
``execute_s`` (everything else): a new lane that triggers one extra
compile is a very different signal from a steady-state slowdown, and
``benchmarks.compare`` gates only the latter.  Compiled programs persist
across runs in the cache ``repro.sim.compile_cache`` places
(``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``).  Each
suite also writes a ``results/BENCH_<suite>.manifest.json`` (schema,
wall split, versions) so a results directory is self-describing.
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

RESULTS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "results")

_compile_secs = 0.0


def _install_compile_listener() -> None:
    """Accumulate XLA compile seconds into ``_compile_secs``.

    jax.monitoring fans every ``record_event_duration_secs`` call out to
    registered listeners; the ``/jax/core/compile*`` keys cover trace +
    backend compile.  Listeners cannot be unregistered, so install one
    global accumulator and read deltas around each suite."""
    import jax.monitoring

    def _on_duration(event: str, duration: float, **kw) -> None:
        global _compile_secs
        if event.startswith("/jax/core/compile"):
            _compile_secs += duration

    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def _parse_row(line: str) -> dict:
    name, us, derived = line.split(",", 2)
    try:
        us_val = float(us)
    except ValueError:
        us_val = None
    return {"name": name, "us_per_call": us_val, "derived": derived}


def _write_json(suite_key: str, doc: dict) -> None:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"BENCH_{suite_key}.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)


def _write_manifest(suite_key: str, manifest: dict) -> None:
    from repro.sim.telemetry import write_manifest
    os.makedirs(RESULTS_DIR, exist_ok=True)
    write_manifest(manifest, os.path.join(
        RESULTS_DIR, f"BENCH_{suite_key}.manifest.json"))


def main() -> None:
    from repro.sim.compile_cache import enable_compile_cache
    from repro.sim.telemetry import BENCH_MANIFEST_SCHEMA, versions

    from . import (chains, cold_start, continuum_bench, drops, failures,
                   fairness, giga_sweep, policy_independence, pool_step,
                   replay, roofline, serving_bench, stress, sweep_speed,
                   telemetry, vertical, workload_analysis)

    enable_compile_cache()
    _install_compile_listener()
    suites = [
        ("workload_analysis(Figs2-5)", workload_analysis.run),
        ("cold_start(Figs7-8)", cold_start.run),
        ("drops(Fig9)", drops.run),
        ("fairness(Figs10-13)", fairness.run),
        ("policy_independence(Figs14-16)", policy_independence.run),
        ("stress(sec6.5)", stress.run),
        ("serving_integration", serving_bench.run),
        ("sweep_speed(beyond-paper)", sweep_speed.run),
        ("giga_sweep(beyond-paper)", giga_sweep.run),
        ("continuum+cluster+chains(beyond-paper)", continuum_bench.run),
        ("chains_slo(beyond-paper)", chains.run),
        ("failures(beyond-paper)", failures.run),
        ("telemetry(beyond-paper)", telemetry.run),
        ("pool_step(beyond-paper)", pool_step.run),
        ("replay(azure-2019)", replay.run),
        ("vertical(beyond-paper)", vertical.run),
        ("roofline(dry-run)", roofline.run),
    ]
    filters = sys.argv[1:]
    print("name,us_per_call,derived")
    failed = 0
    vers = versions()
    for name, fn in suites:
        if filters and not any(f in name for f in filters):
            continue
        suite_key = name.split("(")[0].replace("+", "_")
        print(f"# --- {name} ---", flush=True)
        t0, c0 = time.perf_counter(), _compile_secs
        try:
            ret = fn()
            wall_s = time.perf_counter() - t0
            compile_s = _compile_secs - c0
            lines, payload = ret if isinstance(ret, tuple) else (ret, None)
            for line in lines:
                print(line, flush=True)
            doc = {"suite": name, "wall_s": wall_s,
                   "compile_s": compile_s,
                   "execute_s": max(wall_s - compile_s, 0.0),
                   "rows": [_parse_row(l) for l in lines]}
            if payload is not None:
                doc["payload"] = payload
            _write_json(suite_key, doc)
            _write_manifest(suite_key, {
                "schema": BENCH_MANIFEST_SCHEMA, "suite": name,
                "suite_key": suite_key, "wall_s": wall_s,
                "compile_s": compile_s,
                "execute_s": max(wall_s - compile_s, 0.0),
                "n_rows": len(lines), "versions": vers})
        except Exception as e:
            failed += 1
            wall_s = time.perf_counter() - t0
            print(f"{name},0,ERROR:{e}")
            traceback.print_exc()
            _write_json(suite_key,
                        {"suite": name, "wall_s": wall_s, "error": str(e)})
            _write_manifest(suite_key, {
                "schema": BENCH_MANIFEST_SCHEMA, "suite": name,
                "suite_key": suite_key, "wall_s": wall_s,
                "error": str(e), "versions": vers})
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
