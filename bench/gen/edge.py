"""The calibrated edge mix: a numpy copy of the program's
``repro.workloads.azure.synthesize`` (the stream ``edge_trace`` draws).

Per-function Poisson arrivals with Zipf rate shares, diurnal thinning and
optional bursts; container sizes small 30-60 MB and large 300-400 MB;
lognormal warm and cold-start durations; every time on a 1/64 s grid and
every size a whole MB, so float32 pool arithmetic is exact.
"""
from __future__ import annotations

import numpy as np

from repro.core.types import Trace

_Q = 64.0  # time quantum: 1/64 s

#: every parameter of the stream, with the program's ``TraceConfig``
#: defaults; a traffic file overrides any of them
DEFAULTS = dict(
    n_small_funcs=220, n_large_funcs=8, duration_s=4 * 3600.0,
    small_rps=2.5, large_rps=0.5,
    small_size_range=(30, 60), large_size_range=(300, 400),
    small_warm_med=0.5, large_warm_med=2.0, warm_sigma=0.8,
    small_cold_med=4.0, small_cold_sigma=1.0,
    large_cold_med=15.0, large_cold_sigma=1.3,
    diurnal_depth=0.3, burst_rate_mult=1.0, burst_fraction=0.0,
    zipf_a=1.3)


def _quant(x):
    return np.round(np.asarray(x) * _Q) / _Q


def _rates(rng, n_funcs: int, total_rps: float, zipf_a: float):
    w = np.minimum(rng.zipf(zipf_a, size=n_funcs).astype(np.float64), 1e4)
    return total_rps * w / w.sum()


def _arrivals(rng, rate: float, p: dict):
    """Inhomogeneous Poisson arrivals by thinning."""
    duration = p["duration_s"]
    peak = rate * (1 + p["diurnal_depth"]) * max(p["burst_rate_mult"], 1.0)
    n = rng.poisson(peak * duration)
    if n == 0:
        return np.zeros(0)
    t = np.sort(rng.uniform(0, duration, n))
    lam = rate * (1 + p["diurnal_depth"] * np.sin(2 * np.pi * t / 86400.0))
    if p["burst_fraction"] > 0 and p["burst_rate_mult"] > 1:
        in_burst = (t / 600.0 % 1.0) < p["burst_fraction"]  # 10-min cycle
        lam = np.where(in_burst, lam * p["burst_rate_mult"], lam)
    keep = rng.uniform(0, peak, len(t)) < lam
    return t[keep]


def generate(params: dict, seed: int) -> Trace:
    unknown = set(params) - set(DEFAULTS)
    if unknown:
        raise ValueError(f"edge stream: unknown parameters {sorted(unknown)}")
    p = {**DEFAULTS, **params}
    rng = np.random.default_rng(seed)
    classes = []
    for cls, n, rps, sizes, warm_med, cold_med, cold_sigma, fid0 in (
            (0, p["n_small_funcs"], p["small_rps"], p["small_size_range"],
             p["small_warm_med"], p["small_cold_med"],
             p["small_cold_sigma"], 0),
            (1, p["n_large_funcs"], p["large_rps"], p["large_size_range"],
             p["large_warm_med"], p["large_cold_med"],
             p["large_cold_sigma"], 10_000)):
        classes.append((cls, n, _rates(rng, n, rps, p["zipf_a"]), sizes,
                        warm_med, cold_med, cold_sigma, fid0))
    drawn = [rng.integers(c[3][0], c[3][1] + 1, c[1]) for c in classes]

    ts, fids, szs, clss, warms, colds = [], [], [], [], [], []
    for (cls, n, rates, _, warm_med, cold_med, cold_sigma, fid0), size in \
            zip(classes, drawn):
        for i in range(n):
            t = _arrivals(rng, rates[i], p)
            if len(t) == 0:
                continue
            ts.append(t)
            fids.append(np.full(len(t), fid0 + i, np.int32))
            szs.append(np.full(len(t), size[i], np.float32))
            clss.append(np.full(len(t), cls, np.int32))
            warms.append(rng.lognormal(np.log(warm_med), p["warm_sigma"],
                                       len(t)))
            colds.append(rng.lognormal(np.log(cold_med), cold_sigma, len(t)))

    t = np.concatenate(ts)
    order = np.argsort(t, kind="stable")
    warm = np.maximum(_quant(np.concatenate(warms)), 1 / _Q)
    cold_extra = np.maximum(_quant(np.concatenate(colds)), 1 / _Q)
    return Trace(
        t=_quant(t)[order].astype(np.float32),
        func_id=np.concatenate(fids)[order],
        size_mb=np.concatenate(szs)[order],
        cls=np.concatenate(clss)[order],
        warm_dur=warm[order].astype(np.float32),
        cold_dur=(warm + cold_extra)[order].astype(np.float32))
