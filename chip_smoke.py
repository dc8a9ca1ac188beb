"""Smoke run of the KiSS simulator on a TPU, through its public entry points.

    python chip_smoke.py              # one chip: the phases below
    python chip_smoke.py --chips 4    # four chips: the sharded sweep only

One chip runs these phases.  Each is compared bitwise against the numpy
oracle (``engine="ref"``) or against another JAX run:

1. ``device``       platform, device kind, device count, JAX version;
2. ``replay``       the 1,008,612-event Azure-Functions-2019-schema day of
                    ``benchmarks/replay.py`` on its 4-node cluster, through
                    ``simulate(..., chunk_events=65536)`` in ``gather`` mode,
                    against the oracle over the whole trace;
3. ``paper_sweep``  one ``sweep`` of KiSS over the paper's memory x split
                    grid plus the unified baseline at each memory, on the
                    1-hour edge trace; every 5th lane against the oracle;
4. ``fused``        the replay's first 100k events with ``mode="fused"``
                    against ``gather`` (the Pallas kernel must be in the
                    program as a ``tpu_custom_call``), and one vmapped
                    fused ``sweep`` bucket against the gather sweep;
5. ``heavy_carry``  failures + autoscale (with node scaling) + telemetry
                    + resize under GreedyDual in one scenario, and a
                    chains scenario with ``slack_aware`` routing on a
                    chained trace, each against the oracle.

``--chips 4`` runs only the capacity-planning grid of
``benchmarks/giga_sweep.py`` with ``devices=4`` against ``devices=None``,
checks from the output shardings that the lanes sit on all four
devices, and checks every 97th lane against the oracle.

Each phase prints one JSON line; the last line is the verdict
``{"ok": true, "device": {...}}``.  The script exits non-zero, with no
verdict, when JAX finds no TPU, when a phase raises, or when a
comparison differs.  ``compile_s`` is XLA compile time (from
``jax.monitoring``), ``execute_s`` the rest of the JAX run's wall time;
every run ends in host numpy arrays, so the device work is complete.
Events/s are one unrepeated run each, not a benchmark.  The compile
cache is set up by ``repro.sim.compile_cache.enable_compile_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.monitoring  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import GB, MEMORY_GB, SPLITS  # noqa: E402
from benchmarks.giga_sweep import grid as giga_grid  # noqa: E402
from benchmarks.replay import CHUNK, NODE_MB, SCHEMA  # noqa: E402
from repro.cluster.engine import lower_chunk_program  # noqa: E402
from repro.sim import (Autoscale, Chains, Scenario, simulate,  # noqa: E402
                       sweep)
from repro.sim.compile_cache import enable_compile_cache  # noqa: E402
from repro.workloads import (ChainConfig, chained_trace,  # noqa: E402
                             edge_trace, synthesize_azure_schema,
                             trace_from_tables)

FUSED_EVENTS = 100_000      # replay prefix run with mode="fused"
FUSED_SWEEP_EVENTS = 20_000  # replay prefix of the vmapped fused sweep
HEAVY_EVENTS = 100_000      # replay prefix of the heavy-carry scenario
# lanes of the four-chip sweep: the lost bool scatter updates showed on
# one chip at 1,024 vmapped lanes of this grid (not at 512), and every
# 1,024 lanes cost about a minute of one-chip time on a v5e
GIGA_LANES = 1024
PAPER_ORACLE_STRIDE = 5     # every 5th paper-sweep lane against the oracle
GIGA_ORACLE_STRIDE = 97     # every 97th giga lane against the oracle
ONE_RUN = "events/s from one unrepeated run, not a benchmark"

_compile = {"s": 0.0, "hits": 0, "misses": 0}


def _listen() -> None:
    """Accumulate compile seconds and persistent-cache hits/misses."""
    def on_duration(event: str, secs: float, **kw) -> None:
        if event.startswith("/jax/core/compile"):
            _compile["s"] += secs

    def on_event(event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            _compile["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            _compile["misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


class _Clock:
    """Wall, compile and cache counters over the JAX runs of one phase."""

    def __init__(self):
        self.wall = 0.0
        self.compile_s = 0.0
        self.hits = self.misses = 0

    def run(self, fn, *args, **kwargs):
        c0, h0, m0 = _compile["s"], _compile["hits"], _compile["misses"]
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        self.wall += time.perf_counter() - t0
        self.compile_s += _compile["s"] - c0
        self.hits += _compile["hits"] - h0
        self.misses += _compile["misses"] - m0
        return out

    def fields(self, events: int | None = None) -> dict:
        execute_s = max(self.wall - self.compile_s, 0.0)
        out = {"compile_s": self.compile_s, "execute_s": execute_s,
               "cache_hits": self.hits, "cache_misses": self.misses}
        if events:
            out.update(events_per_s=events / execute_s if execute_s else None,
                       note=ONE_RUN)
        return out


def _same(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return False
    return bool(np.array_equal(a, b, equal_nan=a.dtype.kind == "f"))


def _diff(a, b) -> list[str]:
    """Fields in which two :class:`repro.sim.Result` differ."""
    bad = [k for k in ("node", "outcome")
           if not _same(getattr(a, k), getattr(b, k))]
    if a.summary() != b.summary():
        bad.append("summary")
    for k in ("epoch_fracs", "epoch_active", "invalidated"):
        x, y = getattr(a, k), getattr(b, k)
        if (x is None) != (y is None) or (x is not None and not _same(x, y)):
            bad.append(k)
    for k in ("telemetry", "chains"):
        x, y = getattr(a, k), getattr(b, k)
        if (x is None) != (y is None):
            bad.append(k)
        elif x is not None:
            bad += [f"{k}.{f.name}" for f in dataclasses.fields(x)
                    if not _same(getattr(x, f.name), getattr(y, f.name))]
    x, y = a.vertical, b.vertical
    if (x is None) != (y is None) or (
            x is not None and (x.keys() != y.keys()
                               or not all(_same(x[k], y[k]) for k in x))):
        bad.append("vertical")
    return bad


def _check(what: str, a, b) -> None:
    bad = _diff(a, b)
    if bad:
        raise AssertionError(f"{what}: differs in {bad}")


def _report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def device_phase() -> dict:
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    _report("device", **info, jax=jax.__version__)
    return info


def _timed(fn, *args, **kwargs):
    t0 = time.perf_counter()
    return fn(*args, **kwargs), time.perf_counter() - t0


def replay_phase():
    """The Azure-schema day end to end on the chip, against the oracle."""
    t0 = time.perf_counter()
    tr = trace_from_tables(synthesize_azure_schema(SCHEMA))
    ingest_s = time.perf_counter() - t0
    kiss = Scenario.cluster(NODE_MB, routing="size_aware", max_slots=256,
                            name="kiss")
    clock = _Clock()
    with ThreadPoolExecutor(1) as host:
        # the numpy oracle runs on the host while the chip replays
        oracle = host.submit(_timed, simulate, kiss, tr, engine="ref")
        got = clock.run(simulate, kiss, tr, chunk_events=CHUNK)
        ref, oracle_s = oracle.result()
    _check("replay vs oracle", got, ref)
    _report("replay", events=len(tr), lanes=1, mode="gather",
            chunk_events=CHUNK, ingest_s=ingest_s, **clock.fields(len(tr)),
            oracle_events=len(tr), oracle_s=oracle_s, oracle="agree")
    return tr, kiss, got


def paper_sweep_phase() -> None:
    """The paper's KiSS-vs-baseline grid as one vmapped sweep."""
    tr = edge_trace(seed=0, duration_s=3600.0)
    grid = ([Scenario.kiss(gb * GB, small_frac=f)
             for gb in MEMORY_GB for f in SPLITS]
            + [Scenario.baseline(gb * GB) for gb in MEMORY_GB])
    clock = _Clock()
    res = clock.run(sweep, tr, grid)
    lanes = range(0, len(grid), PAPER_ORACLE_STRIDE)
    for i in lanes:
        _check(f"paper sweep lane {grid[i].label}", res[i],
               simulate(grid[i], tr, engine="ref"))
    _report("paper_sweep", events=len(tr), lanes=len(grid),
            **clock.fields(len(tr) * len(grid)),
            oracle_lanes=len(lanes), oracle="agree")


def fused_phase(tr, kiss: Scenario, gathered) -> None:
    """``mode="fused"`` against ``gather``, single run and vmapped sweep.
    The compiled Pallas kernel shows as a ``tpu_custom_call`` in the
    program (interpret mode would inline it as plain HLO)."""
    prefix = tr.head(FUSED_EVENTS)
    lowered = lower_chunk_program(kiss.to_cluster_config(), prefix,
                                  mode="fused", chunk_events=CHUNK)
    if "tpu_custom_call" not in lowered.as_text():
        raise AssertionError("fused program holds no tpu_custom_call")
    clock = _Clock()
    got = clock.run(simulate, kiss, prefix, mode="fused", chunk_events=CHUNK)
    n = len(prefix)
    if not (_same(got.node, gathered.node[:n])
            and _same(got.outcome, gathered.outcome[:n])):
        raise AssertionError("fused replay prefix differs from gather")
    _check("fused vs gather prefix", got,
           simulate(kiss, prefix, chunk_events=CHUNK))
    _report("fused", events=n, lanes=1, chunk_events=CHUNK,
            tpu_custom_call=True, **clock.fields(n), compared_with="gather",
            oracle="agree")

    short = tr.head(FUSED_SWEEP_EVENTS)
    lanes = [dataclasses.replace(kiss, small_frac=(f,) * kiss.n_nodes,
                                 name=f"kiss-{f}") for f in (0.7, 0.8, 0.9)]
    lanes.append(dataclasses.replace(kiss, unified=(True,) * kiss.n_nodes,
                                     name="baseline"))
    clock = _Clock()
    fused = clock.run(sweep, short, lanes, mode="fused")
    for s, a, b in zip(lanes, fused, sweep(short, lanes)):
        _check(f"fused sweep lane {s.label}", a, b)
    _report("fused_sweep", events=len(short), lanes=len(lanes),
            **clock.fields(len(short) * len(lanes)), compared_with="gather",
            oracle="agree")


def heavy_carry_phase(tr) -> None:
    """Every optional carry at once, and chains, against the oracle."""
    prefix = tr.head(HEAVY_EVENTS)
    heavy = Scenario.cluster(
        NODE_MB, routing="size_aware", replacement="greedy_dual",
        max_slots=256, name="heavy",
        failures=((1800.0, 3600.0, 1), (5000.0, 5600.0, 3)),
        autoscale=Autoscale(epoch_events=4096, spawn_drop_frac=0.05,
                            retire_drop_frac=0.001),
        telemetry=4096, resize="fair_share")
    clock = _Clock()
    got = clock.run(simulate, heavy, prefix)
    _check("heavy carry vs oracle", got,
           simulate(heavy, prefix, engine="ref"))
    _report("heavy_carry", events=len(prefix), lanes=1,
            **clock.fields(len(prefix)), oracle="agree")

    ctr = chained_trace(ChainConfig(seed=0))
    chained = Scenario.cluster(NODE_MB, routing="slack_aware", max_slots=256,
                               chains=Chains(slack=2.0), telemetry=1024,
                               name="chains")
    clock = _Clock()
    got = clock.run(simulate, chained, ctr)
    _check("chains vs oracle", got, simulate(chained, ctr, engine="ref"))
    _report("chains", events=len(ctr), lanes=1, **clock.fields(len(ctr)),
            chains=len(got.chains), oracle="agree")


def sharded_phase(n_dev: int) -> None:
    """The capacity-planning grid sharded over ``n_dev`` chips against the
    same grid on one chip, bitwise, with the lane placement that the
    sharded sweep read from its outputs' shards."""
    tr = edge_trace(seed=0, duration_s=600.0)
    grid = giga_grid(GIGA_LANES)
    one, many = _Clock(), _Clock()
    base = one.run(sweep, tr, grid)
    got = many.run(sweep, tr, grid, devices=n_dev)
    bad = [i for i, (a, b) in enumerate(zip(got, base))
           if not (_same(a.node, b.node) and _same(a.outcome, b.outcome)
                   and a.summary() == b.summary())]
    rows = got[0].run_info["lane_devices"]
    fields = dict(events=len(tr), lanes=len(grid), devices=n_dev,
                  lanes_per_device=rows,
                  one_chip=one.fields(len(tr) * len(grid)),
                  sharded=many.fields(len(tr) * len(grid)),
                  compared_with="devices=None")
    if bad:
        # which side the oracle takes, on the first few differing lanes
        judged = {}
        for i in bad[:3]:
            ref = simulate(grid[i], tr, engine="ref")
            judged[i] = {"sharded": not _diff(got[i], ref),
                         "one_chip": not _diff(base[i], ref)}
        routings = [grid[i].routing for i in bad]
        _report("sharded_sweep", **fields, differing_lanes=len(bad),
                by_routing={r: routings.count(r) for r in set(routings)},
                oracle_agrees_with=judged, agree=False)
        raise AssertionError(f"devices={n_dev} differs on {len(bad)} lanes")
    if len(rows) != n_dev or len(set(rows.values())) != 1:
        raise AssertionError(f"lanes not split over {n_dev} devices: {rows}")
    # equal is not enough: a lane sample, spread over every device's
    # block and every routing policy, against the oracle
    sample = range(0, len(grid), GIGA_ORACLE_STRIDE)
    wrong = [grid[i].label for i in sample
             if _diff(got[i], simulate(grid[i], tr, engine="ref"))]
    if wrong:
        _report("sharded_sweep", **fields, oracle_lanes=len(sample),
                oracle_differs=wrong, agree=False)
        raise AssertionError(f"{len(wrong)} sampled lanes differ from the "
                             "oracle")
    _report("sharded_sweep", **fields, oracle_lanes=len(sample),
            oracle="agree", agree=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the sharded sweep across four chips")
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print(f"chip_smoke: no TPU, JAX found {jax.devices()[0].platform}",
              file=sys.stderr)
        return 2
    if jax.device_count() < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{jax.device_count()} device(s)", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    _listen()
    info = device_phase()
    _report("compile_cache", dir=cache)
    if args.chips > 1:
        sharded_phase(args.chips)
    else:
        tr, kiss, gathered = replay_phase()
        paper_sweep_phase()
        fused_phase(tr, kiss, gathered)
        heavy_carry_phase(tr)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
