"""Profiler capture and the reduction from a device trace to metrics.

A trace is reduced from plain data, the same structure whether it was
just read from the profiler or loaded from a recorded fixture::

    [{"name": "/device:TPU:0",
      "lines": [{"name": "XLA Modules", "events": [[start_ns, dur_ns, name],
                                                   ...]}, ...]}, ...]

Busy time on a device is the union of the intervals of its program
executions (the ``XLA Modules`` line, or the ``XLA Ops`` line where a
device has no module line), clipped to the traced window; the window is
the span of the benchmark's own host annotations around the traced job.
"""
from __future__ import annotations

import contextlib
import glob
import os
import shutil
import tempfile
from collections import defaultdict

#: the host annotations that bound the traced window
WINDOW_SPANS = ("bench.job_prep", "bench.call")
BUSY_LINES = ("XLA Modules", "XLA Ops")
OPS_LINE = "XLA Ops"
TOP = 10
TPU_TRACE_MODE = "TRACE_ONLY_XLA"


class Capture:
    """A profiler trace of one stretch of the run, written to a fresh
    directory under ``TMPDIR`` and read back (then deleted) by
    :meth:`planes`, after the measured window.

    The TPU's default trace mode records every op of every scan step and
    fills its trace buffers within one 65,536-step chunk of a replay, so
    the capture asks for :data:`TPU_TRACE_MODE`, the TPU profiler's mode
    for XLA programs, to bound what a whole job records."""

    def __init__(self):
        self.dir = None

    @contextlib.contextmanager
    def recording(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.advanced_configuration = {"tpu_trace_mode": TPU_TRACE_MODE}
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        try:
            yield self
        finally:
            jax.profiler.stop_trace()

    def planes(self) -> list | None:
        if self.dir is None:
            return None
        try:
            return load(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def load(log_dir: str) -> list:
    """The planes of the one ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    return [{"name": p.name,
             "lines": [{"name": ln.name,
                        "events": [[e.start_ns, e.duration_ns, e.name]
                                   for e in ln.events]}
                       for ln in p.lines]}
            for p in data.planes]


def device_planes(planes: list) -> list:
    """Accelerator planes (``/device:<KIND>:<i>``), in device order."""
    devs = [p for p in planes if p["name"].startswith("/device:")
            and not p["name"].startswith("/device:CUSTOM")]

    def index(p):
        tail = p["name"].rsplit(":", 1)[-1]
        return int(tail) if tail.isdigit() else 0
    return sorted(devs, key=index)


def union(intervals) -> list:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: list = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals) -> float:
    return float(sum(b - a for a, b in intervals))


def gaps(busy, lo: float, hi: float) -> list:
    """The idle intervals of ``[lo, hi]`` between disjoint ``busy`` ones."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append([t, a])
        t = max(t, b)
    if hi > t:
        out.append([t, hi])
    return out


def short(name: str) -> str:
    """An event's name without its HLO text or program fingerprint:
    ``%fusion.3 = f32[..] fusion(..)`` -> ``%fusion.3``,
    ``jit_run(1234)`` -> ``jit_run``."""
    return name.split(" = ", 1)[0].split("(", 1)[0]


def _line(plane: dict, name: str):
    return next((ln for ln in plane["lines"] if ln["name"] == name), None)


def busy_intervals(plane: dict) -> list:
    for name in BUSY_LINES:
        ln = _line(plane, name)
        if ln is not None and ln["events"]:
            return union([s, s + d] for s, d, _ in ln["events"])
    return []


def window(planes: list):
    """``(start_ns, end_ns, host_events)``: the span of the benchmark's
    window annotations, and the events of the host line that holds them."""
    for p in planes:
        if p["name"].startswith("/device:"):
            continue
        for ln in p["lines"]:
            spans = [e for e in ln["events"] if e[2] in WINDOW_SPANS]
            if spans:
                return (min(s for s, _, _ in spans),
                        max(s + d for s, d, _ in spans), ln["events"])
    return None


def innermost(host_events) -> list:
    """``[start, end, name]`` segments of one thread's nested host events,
    each named by the innermost event open over it (the one that started
    last); time no event covers has no segment."""
    segs, stack, t = [], [], None

    def close(upto):
        nonlocal t
        while stack and stack[-1][0] <= upto:
            end, name = stack.pop()
            if end > t:
                segs.append([t, end, name])
                t = end
        if stack and upto > t:
            segs.append([t, upto, stack[-1][1]])
        t = max(t, upto)

    for s, d, name in sorted(host_events, key=lambda e: (e[0], -e[1])):
        if t is None:
            t = s
        close(s)
        stack.append((s + d, name))
    if stack:
        close(max(end for end, _ in stack))
    return segs


def attribute(idle, segs) -> dict:
    """Seconds of the sorted, disjoint ``idle`` intervals by what the host
    was doing (:func:`innermost` segments)."""
    out: dict = defaultdict(float)
    i = 0
    for lo, hi in idle:
        while i < len(segs) and segs[i][1] <= lo:
            i += 1
        covered, k = 0.0, i
        while k < len(segs) and segs[k][0] < hi:
            part = min(segs[k][1], hi) - max(segs[k][0], lo)
            if part > 0:
                out[segs[k][2]] += part / 1e9
                covered += part
            k += 1
        if hi - lo > covered:
            out["(no host span)"] += (hi - lo - covered) / 1e9
    return out


def reduce(planes: list, devices: int) -> dict | None:
    """Busy, idle and op time of the first ``devices`` accelerator planes
    over the traced window; ``None`` where the trace holds no device
    work or no window annotations."""
    win = window(planes)
    devs = device_planes(planes)[:devices]
    if win is None or not devs:
        return None
    lo, hi, host = win
    span, segs = hi - lo, innermost(host)
    per_dev, ops, idle = [], defaultdict(float), defaultdict(float)
    for p in devs:
        busy = clip(busy_intervals(p), lo, hi)
        per_dev.append(total(busy) / 1e9)
        ln = next((x for x in (_line(p, n) for n in (OPS_LINE,) + BUSY_LINES)
                   if x is not None and x["events"]), None)
        for s, d, name in (ln["events"] if ln else ()):
            if s < hi and s + d > lo:
                ops[short(name)] += ((min(s + d, hi) - max(s, lo)) / 1e9
                                     / len(devs))
        for name, sec in attribute(gaps(busy, lo, hi), segs).items():
            idle[name] += sec / len(devs)
    if not any(per_dev):
        return None

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:TOP]
    return {"window_s": span / 1e9, "busy_s": per_dev,
            "device_ops": top(ops), "idle_gaps": top(idle)}


def trim(planes: list, lo: float, hi: float, per_line: int) -> list:
    """A small copy of a trace for a fixture: each line keeps its
    ``per_line`` longest events that overlap ``[lo, hi]``, the window
    spans always among them."""
    out = []
    for p in planes:
        lines = []
        for ln in p["lines"]:
            ev = [e for e in ln["events"] if e[0] < hi and e[0] + e[1] > lo]
            keep = [e for e in ev if e[2] in WINDOW_SPANS]
            rest = sorted((e for e in ev if e[2] not in WINDOW_SPANS),
                          key=lambda e: -e[1])
            keep += rest[:per_line]
            if keep:
                lines.append({"name": ln["name"], "events": sorted(keep)})
        if lines:
            out.append({"name": p["name"], "lines": lines})
    return out
