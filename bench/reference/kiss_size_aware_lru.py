"""The plain reference for a replay cell on a heterogeneous site: KiSS
edge nodes of different sizes behind size-aware routing, in front of a
cloud tier, written out from their documented semantics one invocation
at a time, in plain Python and numpy.

It shares no code with the program, nor with the other references: it
reads the configuration's file and the trace's arrays, and nothing else.
The semantics it follows (the KiSS paper, arXiv:2502.12540, sections
4-5; the program's ``docs/architecture.md``):

* A KiSS node splits its memory into a small pool
  (``node_mb * small_frac``) and a large pool
  (``node_mb * (1 - small_frac)``), each capacity held as a float32.
  An invocation is served by its size class's pool.
* Routing is size-aware: a node is eligible for an invocation when its
  class pool's capacity covers the container's size.  With
  ``h = f mod n_nodes`` for function ``f`` and ``k`` eligible nodes, the
  invocation goes to the ``(h mod k)``-th eligible node in index order;
  with no eligible node it goes to ``h``, whose pool is too small, and
  drops.
* A pool holds containers, each with its function, size, launch order,
  last use and the time it is busy until.  A container is idle at time
  ``t`` once its busy time is ``<= t``.
* Hit: an idle container of the function exists.  The one launched
  first serves; it is used at ``t`` and busy until ``t + warm``.
* Otherwise the invocation cold-starts.  It is dropped when its size
  exceeds the pool's capacity, or when evicting every idle container
  would not free enough memory, or when every slot (``max_slots``) is
  still taken after eviction.  Else the least recently used idle
  containers (oldest last use first, then launch order) are evicted, the
  fewest whose sizes cover what is missing, and the new container is
  placed, busy until ``t + cold``.  A drop leaves the pool as it was.
* A dropped invocation runs in the cloud: its latency is the round trip
  plus its cold time with the cloud's cold-start probability, else its
  warm time.  The cloud's coin flips are one draw for the whole run,
  ``numpy.random.default_rng(0).random(n) < cloud_cold_prob``.  A hit
  costs its warm time and a miss its cold time.
* Times and sizes are float32; ``t + duration`` is rounded to float32.
  Sizes are whole MB.  A capacity need not be a whole number (1,024 MB
  x 0.8 is 819.2, held as the float32 819.20001220703125), so a pool's
  free memory is kept in float32, each add and subtract rounded as the
  program's float32 state is.
"""
from __future__ import annotations

import bisect
import heapq

import numpy as np

HIT, MISS, DROP = 0, 1, 2
#: the cluster keys this reference reads; any other is refused
KNOWN = {"node_mb", "small_frac", "unified", "routing", "replacement",
         "max_slots", "cloud_rtt_s", "cloud_cold_prob"}


def f32(x: float) -> float:
    return float(np.float32(x))


class Pool:
    """One warm pool under LRU, its free memory in float32."""

    def __init__(self, capacity: float, slots: int):
        self.capacity = capacity
        self.free = capacity
        self.slots = slots
        self.launched = 0
        self.containers: dict = {}   # launch number -> [func, size, last_use]
        self.busy: list = []         # heap of (busy_until, launch number)
        self.idle: list = []         # sorted (last_use, launch number)
        self.idle_of: dict = {}      # func -> sorted launch numbers, idle
        self.idle_mb = 0.0

    def _release(self, t: float) -> None:
        while self.busy and self.busy[0][0] <= t:
            _, k = heapq.heappop(self.busy)
            func, size, last_use = self.containers[k]
            bisect.insort(self.idle, (last_use, k))
            bisect.insort(self.idle_of.setdefault(func, []), k)
            self.idle_mb += size

    def _unidle(self, k: int) -> None:
        func, size, last_use = self.containers[k]
        del self.idle[bisect.bisect_left(self.idle, (last_use, k))]
        mine = self.idle_of[func]
        del mine[bisect.bisect_left(mine, k)]
        self.idle_mb -= size

    def access(self, t: float, func: int, size: float, warm_end: float,
               cold_end: float) -> int:
        self._release(t)
        mine = self.idle_of.get(func)
        if mine:
            k = mine[0]
            self._unidle(k)
            self.containers[k][2] = t
            heapq.heappush(self.busy, (warm_end, k))
            return HIT
        if size > self.capacity:
            return DROP
        missing = size - self.free
        victims, freed = 0, 0.0
        if missing > 0:
            if self.idle_mb < missing:
                return DROP
            while freed < missing:
                freed += self.containers[self.idle[victims][1]][1]
                victims += 1
        if len(self.containers) - victims >= self.slots:
            return DROP
        for _, k in self.idle[:victims]:
            self._unidle(k)
            del self.containers[k]
        self.free = f32(f32(self.free + freed) - size)
        k = self.launched
        self.launched += 1
        self.containers[k] = [func, size, t]
        heapq.heappush(self.busy, (cold_end, k))
        return MISS


def capacities(cluster: dict) -> list:
    """Each node's (small, large) pool capacities, as float32 values."""
    frac = float(cluster["small_frac"])
    return [(f32(float(mb) * frac), f32(float(mb) * (1.0 - frac)))
            for mb in cluster["node_mb"]]


def route(caps: list, func: int, c: int, size: float) -> int:
    """The node an invocation of function ``func``, class ``c`` and
    ``size`` MB goes to."""
    h = func % len(caps)
    eligible = [n for n, cap in enumerate(caps) if cap[c] >= size]
    return eligible[h % len(eligible)] if eligible else h


def replay(cluster: dict, trace) -> dict:
    """Per-invocation ``node``, ``outcome`` and ``latency`` of ``trace``
    (a time-sorted :class:`repro.core.types.Trace`) through ``cluster``
    (a configuration file's ``cluster`` entry), and the run's summary."""
    if set(cluster) - KNOWN or cluster["routing"] != "size_aware" \
            or cluster["replacement"] != "lru" or cluster["unified"]:
        raise ValueError("the reference knows size-aware routing over "
                         "split KiSS nodes under LRU only, with the keys "
                         f"{sorted(KNOWN)}")
    caps = capacities(cluster)
    pools = [[Pool(cap, cluster["max_slots"]) for cap in node]
             for node in caps]

    t32 = np.asarray(trace.t, np.float32)
    if np.any(np.diff(t32) < 0):
        raise ValueError("the trace is not sorted by time")
    warm32 = np.asarray(trace.warm_dur, np.float32)
    cold32 = np.asarray(trace.cold_dur, np.float32)
    func = np.asarray(trace.func_id, np.int64)
    cls = np.asarray(trace.cls, np.int64)
    node = np.empty(len(t32), np.int64)
    outcome = np.empty(len(t32), np.int64)
    for i, (t, f, c, s, we, ce) in enumerate(zip(
            t32.tolist(), func.tolist(), cls.tolist(),
            np.asarray(trace.size_mb, np.float32).tolist(),
            (t32 + warm32).tolist(), (t32 + cold32).tolist())):
        nd = node[i] = route(caps, f, c, s)
        outcome[i] = pools[nd][c].access(t, f, s, we, ce)

    warm = warm32.astype(np.float64)
    cold = cold32.astype(np.float64)
    coin = np.random.default_rng(0).random(len(t32)) \
        < cluster["cloud_cold_prob"]
    latency = np.where(outcome == HIT, warm, np.where(
        outcome == MISS, cold,
        cluster["cloud_rtt_s"] + np.where(coin, cold, warm)))
    return {"node": node, "outcome": outcome, "latency": latency,
            "summary": summary(cluster, cls, outcome, warm, cold,
                               latency)}


def _pct(part: int, whole: int) -> float:
    return 100.0 * part / whole if whole else 0.0


def summary(cluster: dict, cls, outcome, warm, cold, latency) -> dict:
    """The run's summary under the program's documented keys (the
    ``Result.summary()`` contract): per-class shares, edge execution
    time, the latency distribution, and the values a run without
    autoscaling, failures, telemetry, chains or resizing reports."""
    count = {(c, o): int(np.count_nonzero((cls == c) & (outcome == o)))
             for c in (0, 1) for o in (HIT, MISS, DROP)}
    hits = count[0, HIT] + count[1, HIT]
    misses = count[0, MISS] + count[1, MISS]
    drops = count[0, DROP] + count[1, DROP]
    total = hits + misses + drops
    small = sum(count[0, o] for o in (HIT, MISS, DROP))
    large = total - small
    exec_s = float(np.sum(np.where(outcome == HIT, warm, 0.0))
                   + np.sum(np.where(outcome == MISS, cold, 0.0)))
    n_nodes = len(cluster["node_mb"])
    frac = f32(cluster["small_frac"])
    return {
        "cold_start_pct": _pct(misses, total),
        "drop_pct": _pct(drops, total),
        "hit_rate": _pct(hits, total),
        "small_cold_start_pct": _pct(count[0, MISS], small),
        "large_cold_start_pct": _pct(count[1, MISS], large),
        "small_drop_pct": _pct(count[0, DROP], small),
        "large_drop_pct": _pct(count[1, DROP], large),
        "serviceable": hits + misses,
        "total": total,
        "exec_time_s": exec_s,
        "serviceable_mean_s": exec_s / (hits + misses) if hits + misses
        else 0.0,
        "n_nodes": n_nodes,
        "offload_pct": _pct(drops, total),
        "latency_mean_s": float(np.mean(latency)),
        "latency_p50_s": float(np.percentile(latency, 50)),
        "latency_p95_s": float(np.percentile(latency, 95)),
        "latency_p99_s": float(np.percentile(latency, 99)),
        "n_epochs": 1, "frac_final_mean": frac, "frac_min": frac,
        "frac_max": frac, "downtime_pct": 0.0, "n_invalidated": 0,
        "n_active_final": n_nodes, "n_active_min": n_nodes,
        "n_windows": 0, "n_chains": 0, "chain_latency_mean_s": 0.0,
        "chain_p95_s": 0.0, "deadline_miss_pct": 0.0,
        "utilization_ratio": 0.0, "bottleneck_events": 0,
    }
