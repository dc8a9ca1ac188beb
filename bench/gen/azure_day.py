"""One day of the Azure Functions 2019 trace's schema: a numpy copy of the
program's ``repro.workloads.replay`` path, ``synthesize_azure_schema``
expanded by ``trace_from_tables``.

The dataset (Shahrad et al., "Serverless in the Wild", USENIX ATC '20)
is not redistributable, so its three per-day tables are drawn from a
seed: per-function per-minute invocation counts (Zipf popularity, split
between small and large apps at a fixed aggregate ratio, a diurnal
curve), per-function duration percentiles (lognormal-shaped) and per-app
memory percentiles (small 30-60 MB or large 300-400 MB at the median).
The tables are then expanded event by event: a minute with ``k``
invocations places them evenly with a per-(function, minute) phase; a
function's container size is one draw from its app's memory curve;
warm durations are draws from its duration curve; a cold start adds a
size-affine lognormal overhead.  Every time lands on a 1/64 s grid and
every size on a whole MB, so float32 pool arithmetic is exact.

The seed draws both the tables and the expansion.  The trigger column,
which nothing downstream reads, is not drawn.
"""
from __future__ import annotations

import hashlib

import numpy as np

from repro.core.types import Trace

_Q = 64.0  # time quantum: 1/64 s
MINUTES_PER_DAY = 1440
DURATION_PCT_LEVELS = (0.0, 1.0, 25.0, 50.0, 75.0, 99.0, 100.0)
MEMORY_PCT_LEVELS = (1.0, 5.0, 25.0, 50.0, 75.0, 95.0, 99.0, 100.0)
#: z-scores of the duration table's levels, the open ends clipped at 3.5
_Z = np.array([-3.5, -2.3263478740408408, -0.6744897501960817, 0.0,
               0.6744897501960817, 2.3263478740408408, 3.5])
#: an app's memory percentiles as factors of its median
_SPREAD = np.array([0.6, 0.7, 0.85, 1.0, 1.15, 1.35, 1.5, 1.7])

#: every parameter of the stream, with the program's ``SchemaConfig`` and
#: ``ReplayConfig`` defaults; a traffic file overrides any of them
DEFAULTS = dict(
    n_funcs=120, n_minutes=240, rpm_total=300.0, large_frac=0.08,
    small_large_ratio=5.0, funcs_per_app=3, zipf_a=1.3, diurnal_depth=0.3,
    threshold_mb=225.0, cold_base_s=2.0, cold_per_mb_s=0.16,
    cold_sigma=0.35)


def _quant(x):
    return np.round(np.asarray(x) * _Q) / _Q


def _hex(seed: int, kind: str, i: int) -> str:
    return hashlib.blake2s(f"{seed}/{kind}/{i}".encode(),
                           digest_size=16).hexdigest()


def _u64(*parts: str) -> int:
    h = hashlib.blake2s("\x1f".join(parts).encode(), digest_size=8)
    return int.from_bytes(h.digest(), "little")


def _interp(u, levels, values):
    """Inverse-CDF draw of ``u`` from a percentile curve made monotone."""
    return np.interp(u, np.asarray(levels) / 100.0,
                     np.maximum.accumulate(np.asarray(values, np.float64)))


def tables(p: dict, seed: int) -> dict:
    """The three tables: per-function owner, app and function hashes and
    app index, invocation counts ``[F, M]``, duration percentiles (ms)
    ``[F, 7]``, and per-app memory percentiles (MB) ``[A, 8]``."""
    rng = np.random.default_rng(seed)
    n, m = p["n_funcs"], p["n_minutes"]
    n_apps = max(1, n // max(p["funcs_per_app"], 1))
    app_of = np.sort(rng.integers(0, n_apps, n))
    app_owner = [_hex(seed, "owner", a % max(n_apps // 2, 1))
                 for a in range(n_apps)]
    app_hash = [_hex(seed, "app", a) for a in range(n_apps)]

    # the app's memory band decides its class and its share of the rate;
    # popularity is normalised within each band
    n_large = (max(1, round(p["large_frac"] * n_apps))
               if p["large_frac"] > 0 else 0)
    large_app = np.zeros(n_apps, bool)
    large_app[rng.permutation(n_apps)[:n_large]] = True
    large_fn = large_app[app_of]
    w = np.minimum(rng.zipf(p["zipf_a"], size=n).astype(np.float64), 1e4)
    r = p["small_large_ratio"]
    share = np.where(large_fn, 1.0 / (1.0 + r), r / (1.0 + r))
    for band in (large_fn, ~large_fn):
        if band.any():
            w[band] /= w[band].sum()
    rates = p["rpm_total"] * share * w
    if not large_fn.any() or large_fn.all():
        rates = p["rpm_total"] * w
    diurnal = 1.0 + p["diurnal_depth"] * np.sin(
        2 * np.pi * np.arange(m) / MINUTES_PER_DAY)
    counts = rng.poisson(rates[:, None] * diurnal[None, :]).astype(np.int64)

    base = np.where(large_app, rng.uniform(300, 400, n_apps),
                    rng.uniform(30, 60, n_apps))
    med_s = np.where(large_fn, rng.lognormal(np.log(2.0), 0.5, n),
                     rng.lognormal(np.log(0.5), 0.5, n))
    sigma = rng.uniform(0.5, 1.0, n)
    return {
        "owner": [app_owner[a] for a in app_of],
        "app": [app_hash[a] for a in app_of],
        "func": [_hex(seed, "func", i) for i in range(n)],
        "app_of": app_of, "counts": counts,
        "dur_pcts": 1000.0 * med_s[:, None]
        * np.exp(sigma[:, None] * _Z[None, :]),
        "mem_pcts": base[:, None] * _SPREAD[None, :]}


def expand(tab: dict, p: dict, seed: int) -> Trace:
    """The tables as a sorted, quantized trace; function ids are dense in
    the order of the (owner, app, function) hashes."""
    n = len(tab["func"])
    canon = sorted(range(n), key=lambda i: (tab["owner"][i], tab["app"][i],
                                            tab["func"][i]))
    n_min = tab["counts"].shape[1]
    ts, fids, sizes, warms, colds = [], [], [], [], []
    for fid, i in enumerate(canon):
        counts = tab["counts"][i]
        total = int(counts.sum())
        if total == 0:
            continue
        rng = np.random.default_rng(np.random.SeedSequence(
            [seed, _u64(tab["owner"][i], tab["app"][i], tab["func"][i])]))
        size = float(np.maximum(np.round(_interp(
            rng.random(), MEMORY_PCT_LEVELS,
            tab["mem_pcts"][tab["app_of"][i]])), 1.0))
        phases = rng.random(n_min)
        # invocation j of a minute holding k lands at 60 (m + (j + phase) / k)
        minute = np.repeat(np.arange(n_min), counts)
        k = counts[minute]
        j = np.arange(total) - np.repeat(np.cumsum(counts) - counts, counts)
        ts.append(60.0 * (minute + (j + phases[minute]) / k))
        warms.append(_interp(rng.random(total), DURATION_PCT_LEVELS,
                             tab["dur_pcts"][i]) / 1000.0)
        colds.append((p["cold_base_s"] + p["cold_per_mb_s"] * size)
                     * rng.lognormal(0.0, p["cold_sigma"], total))
        fids.append(np.full(total, fid, np.int32))
        sizes.append(np.full(total, size, np.float32))
    t = _quant(np.concatenate(ts))
    order = np.argsort(t, kind="stable")
    size = np.concatenate(sizes)[order]
    warm = np.maximum(_quant(np.concatenate(warms)), 1 / _Q)
    cold_extra = np.maximum(_quant(np.concatenate(colds)), 1 / _Q)
    return Trace(
        t=t[order].astype(np.float32),
        func_id=np.concatenate(fids)[order],
        size_mb=size,
        cls=(size >= p["threshold_mb"]).astype(np.int32),
        warm_dur=warm[order].astype(np.float32),
        cold_dur=(warm + cold_extra)[order].astype(np.float32))


def generate(params: dict, seed: int) -> Trace:
    unknown = set(params) - set(DEFAULTS)
    if unknown:
        raise ValueError(
            f"azure_day stream: unknown parameters {sorted(unknown)}")
    p = {**DEFAULTS, **params}
    return expand(tables(p, seed), p, seed)
