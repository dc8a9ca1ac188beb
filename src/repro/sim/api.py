"""The front door: ``simulate(scenario, trace)`` and ``sweep``.

One entrypoint for every configuration (single node, heterogeneous
cluster, any registered policy, failure schedules, node add/remove) and
both engines:

* ``engine="jax"`` — the jitted ``lax.scan`` programs of
  ``repro.cluster``: the chunk program, over the whole trace as one chunk
  or ``chunk_events`` at a time, and the epoch grid for autoscaled
  scenarios; sweeps run vmapped, one device program per group of
  like-shaped scenarios.
* ``engine="ref"`` — the sequential numpy oracle, one event at a time
  (``repro.core.continuum``); slower, bit-identical, the ground truth the
  JAX engine is equivalence-tested against.
"""
from __future__ import annotations

from typing import Iterable, Sequence

import numpy as np
from jax.profiler import TraceAnnotation

from ..cluster.engine import (STEP_MODES, _simulate_cluster_autoscale_jax,
                              _simulate_cluster_autoscale_ref,
                              _simulate_cluster_jax, _simulate_cluster_ref,
                              _sweep_cluster, _sweep_cluster_autoscale,
                              check_chunk_events, check_devices,
                              check_step_mode)
from ..core.types import Trace
from .chains import metrics_from_arrays
from .result import Result
from .scenario import Scenario
from .telemetry import series_from_arrays, trace_fingerprint

_ENGINES = ("jax", "ref")


def _check_engine(engine: str) -> None:
    if engine not in _ENGINES:
        raise ValueError(f"engine must be one of {_ENGINES}, got {engine!r}")


def _check_chunkable(scenario: Scenario, chunk_events) -> int | None:
    """Shared ``chunk_events`` validation for simulate/sweep."""
    chunk = check_chunk_events(chunk_events)
    if chunk is not None and scenario.autoscale is not None:
        raise ValueError(
            "chunk_events does not compose with autoscale yet: the "
            "autoscaled engines run an outer lax.scan over whole epochs, "
            "which already bounds per-step work — drop chunk_events or "
            "the Autoscale")
    return chunk


def _n_chunks(t_len: int, chunk: int | None) -> int:
    """The chunks a run of the chunk program takes (``chunk=None`` is the
    whole trace as one chunk; an empty trace takes none)."""
    return -(-t_len // (chunk or max(t_len, 1)))


def _telw(scenario: Scenario) -> int | None:
    """The scenario's telemetry window length (None = telemetry off) —
    the engine-level form of the :class:`Telemetry` knob."""
    t = scenario.telemetry
    return t.window_events if t is not None else None


def _chain_plan(scenario: Scenario, trace: Trace):
    """Compile the scenario's :class:`Chains` knob against ``trace``
    into the engine-level ``ChainPlan`` (None = chains off)."""
    if scenario.chains is None:
        return None
    if not trace.has_chains:
        raise ValueError(
            "Scenario(..., chains=...) needs a chained trace "
            "(Trace.chain_id/stage/chain_len set) — e.g. "
            "repro.workloads.chained_trace")
    return scenario.chains.compile(trace)


def _fingerprint(trace: Trace) -> str:
    """``trace_fingerprint`` under its span, which counts the bytes hashed."""
    n = sum(np.asarray(a).nbytes for a in trace if a is not None)
    with TraceAnnotation("sim.fingerprint", bytes=n):
        return trace_fingerprint(trace)


def _wrap(scenario: Scenario, trace: Trace, raw, extras: dict,
          fracs, telw: int | None, info: dict, plan=None) -> Result:
    """Assemble the :class:`Result`: lift the engine-level telemetry
    window arrays into a :class:`TelemetrySeries` and the per-chain
    arrays into a :class:`ChainMetrics`, attach the run info, and (for
    autoscaled runs) the epoch-boundary time axis."""
    tel = (series_from_arrays(extras["telemetry"], trace, telw)
           if telw is not None else None)
    ch = (metrics_from_arrays(extras["chains"], plan)
          if plan is not None else None)
    ep_t = None
    if scenario.autoscale is not None and len(trace):
        e = scenario.autoscale.epoch_events
        n_ep = -(-len(trace) // e)
        t = np.asarray(trace.t, np.float32)
        ep_t = t[np.minimum((np.arange(n_ep) + 1) * e - 1, len(trace) - 1)]
    return Result(scenario=scenario, raw=raw, epoch_fracs=fracs,
                  epoch_active=extras.get("active"),
                  node_up=extras.get("node_up"),
                  invalidated=extras.get("invalidated"),
                  telemetry=tel, chains=ch, run_info=info, epoch_t=ep_t,
                  vertical=extras.get("vertical"))


def simulate(scenario: Scenario, trace: Trace, *, engine: str = "jax",
             mode: str = "gather", rng_seed: int = 0,
             chunk_events: int | None = None) -> Result:
    """Run one scenario over ``trace`` and return the unified
    :class:`Result`.

    ``mode`` selects the JAX scan-step formulation (|STEP_MODES|, see
    ``repro.cluster.engine.STEP_MODES``; ``"fused"`` runs the Pallas
    evict-and-place kernel from ``repro.kernels.pool_step`` — compiled by
    Mosaic on TPU, interpreted bit-identically elsewhere); it is ignored
    by the reference engine.  ``rng_seed``
    fixes the cloud cold-start draws (common random numbers: both engines
    and every scenario of a sweep price offloads identically).

    ``chunk_events`` (a positive int, default ``None`` = the whole trace
    as one chunk) sets how many events the JAX engine's chunk program
    takes at a time: the trace is split host-side into fixed-size chunks
    and each chunk runs through the same ``lax.scan`` step with the pool
    state threaded between chunks as a donated carry.  Outcomes are
    **bit-identical** for any chunk size (``lax.scan`` is sequential
    either way) but peak device memory is bounded by one chunk — what
    makes million-invocation Azure-2019 replays practical (see
    ``repro.workloads.replay``).  The reference engine is already
    one-event-at-a-time and ignores it (after validation), so the same
    call runs on both engines.

    An autoscaled scenario (``scenario.autoscale`` set) runs the epoch
    grid instead; the returned :class:`Result` then
    carries the per-epoch split trajectory in ``.fracs`` (and, with node
    scaling, the membership trajectory in ``.active``).  A failure
    schedule (``scenario.failures``) composes with either path — and
    with ``chunk_events`` — the result additionally exposes
    ``.node_up``, ``.node_downtime_pct`` and ``.invalidated``.
    """
    _check_engine(engine)
    check_step_mode(mode)
    chunk = _check_chunkable(scenario, chunk_events)
    chunks = (_n_chunks(len(trace), chunk)
              if engine == "jax" and scenario.autoscale is None else 0)
    with TraceAnnotation("sim.simulate", events=len(trace), chunks=chunks):
        return _simulate(scenario, trace, engine, mode, rng_seed, chunk)


def _simulate(scenario: Scenario, trace: Trace, engine: str, mode: str,
              rng_seed: int, chunk: int | None) -> Result:
    """The body of :func:`simulate`, inside its ``sim.simulate`` span."""
    cfg = scenario.to_cluster_config()
    asc, fails = scenario.autoscale, scenario.failures
    telw = _telw(scenario)
    plan = _chain_plan(scenario, trace)
    info = {"engine": engine,
            "mode": mode if engine == "jax" else None,
            "chunk_events": chunk if engine == "jax" else None,
            "devices": None,   # single runs are never sharded
            "rng_seed": rng_seed,
            "trace_fingerprint": _fingerprint(trace)}
    fracs = None
    if asc is not None:
        if engine == "jax":
            raw, fracs, extras = _simulate_cluster_autoscale_jax(
                cfg, asc, trace, rng_seed, mode, failures=fails,
                telemetry=telw, chains=plan)
        else:
            raw, fracs, extras = _simulate_cluster_autoscale_ref(
                cfg, asc, trace, rng_seed, failures=fails, telemetry=telw,
                chains=plan)
    elif engine == "ref":
        raw, extras = _simulate_cluster_ref(
            cfg, trace, rng_seed, failures=fails, telemetry=telw,
            chains=plan)
    else:
        raw, extras = _simulate_cluster_jax(
            cfg, trace, rng_seed, mode, chunk, failures=fails,
            telemetry=telw, chains=plan)
    return _wrap(scenario, trace, raw, extras, fracs, telw, info, plan)


def sweep(trace: Trace, scenarios: Iterable[Scenario], *,
          engine: str = "jax", mode: str | Sequence[str] = "gather",
          rng_seed: int = 0, chunk_events: int | None = None,
          devices: int | str | None = None) -> list[Result]:
    """Evaluate many scenarios on one trace; results in input order.

    ``mode`` (|STEP_MODES|) is one step formulation for every lane, or a
    per-scenario sequence — lanes bucket by mode like any other static
    shape, so a sweep mixing ``"fused"`` and ``"vmap"`` lanes simply
    compiles one program per mode group.

    Scenarios sharing stacked shapes (``n_nodes``, ``max_slots``, and —
    for autoscaled scenarios — the epoch length) are batched into ONE
    vmapped ``lax.scan`` program; mixed shapes simply split into one
    program per group — callers no longer need to hand-partition their
    grids the way ``sweep_cluster`` required.  Static, failure-injected,
    and autoscaled scenarios mix freely: failure lanes bucket by mask
    shape (pinned by the shared trace and ``n_nodes``) with their
    compiled masks vmapped as data, and autoscaled lanes vmap (min_frac,
    max_frac, gain), the node-scaling thresholds, initial membership, and
    any failure masks as data.

    ``chunk_events`` sets the chunk size for every lane (see
    :func:`simulate`): each group's chunk loop threads ONE
    stacked donated carry across all of its lanes, so replay-scale
    traces sweep with the same bounded footprint as a single run.
    Autoscaled scenarios do not compose with it (yet) and raise.

    ``devices`` shards each group's stacked lane axis across that many
    JAX devices with ``shard_map`` (``"all"`` = every visible device,
    ``None`` = the exact pre-sharding single-device programs).  Each
    device runs its shard of the already-vmapped scan, so results are
    **bit-identical** to the unsharded sweep for any device count on CPU
    meshes and, for 1,024 lanes of the ``benchmarks/giga_sweep.py`` grid,
    on four TPU v5e chips; lane
    counts that don't divide are padded with no-op duplicate lanes that
    are sliced off before ``Result`` assembly; each result's
    ``run_info["lane_devices"]`` maps every device to the lane rows (pad
    lanes included) its shard of the outputs held.  On CPU, set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before the
    first jax import* to turn host cores into a device mesh (see
    ``docs/sweeps.md``).  The reference engine validates and then
    ignores it, like ``chunk_events``.
    """
    _check_engine(engine)
    scenarios = list(scenarios)
    if not scenarios:
        raise ValueError("sweep: scenarios must be non-empty")
    if isinstance(mode, str):
        modes = [mode] * len(scenarios)
    else:
        modes = list(mode)
        if len(modes) != len(scenarios):
            raise ValueError(
                f"sweep: per-scenario mode needs {len(scenarios)} "
                f"entries, got {len(modes)}")
    for m in modes:
        check_step_mode(m)
    chunk = None
    for s in scenarios:
        chunk = _check_chunkable(s, chunk_events)
    dev = check_devices(devices)
    if engine == "ref":
        # validated above, then ignored — the oracle is sequential
        # anyway (the chunk_events precedent)
        return [simulate(s, trace, engine="ref", rng_seed=rng_seed)
                for s in scenarios]
    chunks = (_n_chunks(len(trace), chunk)
              if any(s.autoscale is None for s in scenarios) else 0)
    with TraceAnnotation("sim.sweep", events=len(trace),
                         lanes=len(scenarios), chunks=chunks):
        return _sweep(trace, scenarios, modes, rng_seed, chunk, dev)


def _sweep(trace: Trace, scenarios: list, modes: list, rng_seed: int,
           chunk: int | None, dev: int | None) -> list[Result]:
    """The JAX body of :func:`sweep`, inside its ``sim.sweep`` span."""
    plans = [_chain_plan(s, trace) for s in scenarios]
    groups: dict[tuple[int, int, int | None, bool, int | None, bool, bool,
                       str], list[int]] = {}
    for i, s in enumerate(scenarios):
        epoch = s.autoscale.epoch_events if s.autoscale else None
        # failure-free lanes keep the cheap unmasked programs (static and
        # autoscaled alike); failure lanes compile the masked twin and
        # vmap their schedules as data; telemetry lanes bucket by window
        # length (the stacked accumulator shape); chain lanes bucket by
        # chains on/off only — deadlines are per-lane *data*, so
        # {no-deadline, tight, loose} variants share one program; resize
        # lanes bucket by on/off only — which policy and what floor are
        # per-lane data, so a {static, fair_share} grid shares one
        # program; the step mode is a static formulation choice, so
        # mixed-mode sweeps bucket by it too
        failing = s.failures is not None
        groups.setdefault(
            (s.n_nodes, s.max_slots, epoch, failing, _telw(s),
             plans[i] is not None, s.resize is not None, modes[i]),
            []).append(i)
    results: list[Result | None] = [None] * len(scenarios)
    base_info = {"engine": "jax", "chunk_events": chunk,
                 "devices": dev, "rng_seed": rng_seed,
                 "trace_fingerprint": _fingerprint(trace)}
    for ((_, _, epoch, failing, telw, chained, _, gmode),
         idxs) in groups.items():
        cfgs = [scenarios[i].to_cluster_config() for i in idxs]
        chs = [plans[i] for i in idxs] if chained else None
        info = {**base_info, "mode": gmode}
        placement = None
        if dev:     # sharded groups record the lane rows each device held
            placement = info["lane_devices"] = {}
        fails = [scenarios[i].failures for i in idxs] if failing else None
        if epoch is None:
            outs = [(raw, None, extras) for raw, extras in _sweep_cluster(
                trace, cfgs, rng_seed=rng_seed, mode=gmode,
                chunk_events=chunk, failures=fails, telemetry=telw,
                chains=chs, devices=dev, placement=placement)]
        else:
            outs = _sweep_cluster_autoscale(
                trace, cfgs, [scenarios[i].autoscale for i in idxs], fails,
                rng_seed=rng_seed, mode=gmode, telemetry=telw, chains=chs,
                devices=dev, placement=placement)
        for i, (raw, fracs, extras) in zip(idxs, outs):
            results[i] = _wrap(scenarios[i], trace, raw, extras, fracs,
                               telw, info, plans[i])
    return results


# the mode lists in the docstrings derive from the engine's STEP_MODES
# tuple (f-string docstrings are not recognized by CPython, so splice)
_MODES_DOC = " | ".join(f'``"{m}"``' for m in STEP_MODES)
simulate.__doc__ = simulate.__doc__.replace("|STEP_MODES|", _MODES_DOC)
sweep.__doc__ = sweep.__doc__.replace("|STEP_MODES|", _MODES_DOC)
