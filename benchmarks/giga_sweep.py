"""Beyond-paper: device-mesh sharded giga-sweeps.

The capacity-planning workload the ROADMAP targets: one ``sweep()`` call
over a ~10k-scenario cross product (routing policy x split x node memory),
sharded across a device mesh with ``devices=``.  The lane axis is
embarrassingly parallel (no cross-lane reductions anywhere in the scan),
so results should stay bit-identical to the single-device run, which
this suite re-verifies on every invocation (it holds on CPU meshes, and
on four TPU v5e chips for 1,024 lanes of this grid).

Everything runs in the calling process, over the requested device counts
that ``jax.device_count()`` can serve (larger counts are skipped and
reported).  On a CPU host, a multi-device rehearsal gets its devices from
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set by the caller
before the first jax import, as CI's ``tests-multidevice`` job does.

``GIGA_SWEEP_LANES`` scales the grid (default 10240 lanes; CI bench-smoke
sets a small count), ``GIGA_SWEEP_DEVICES`` the device counts swept
(default ``1,2,4,8``).
"""
from __future__ import annotations

import os
import time

import numpy as np

from .common import GB, MEMORY_GB, SPLITS, csv_line

DEFAULT_LANES = 10240
DEFAULT_DEVICES = "1,2,4,8"


def grid(lanes: int):
    """A lane grid crossing routing x split x node memory, one shape
    bucket (n_nodes=2) so the whole sweep shards as a single program."""
    from repro.sim import Scenario, routing_policies

    # slack_aware needs chain data; every other registered policy sweeps
    routings = sorted(r for r in routing_policies() if r != "slack_aware")
    out = []
    i = 0
    while len(out) < lanes:
        gb = MEMORY_GB[i % len(MEMORY_GB)]
        fr = SPLITS[(i // len(MEMORY_GB)) % len(SPLITS)]
        ro = routings[(i // (len(MEMORY_GB) * len(SPLITS))) % len(routings)]
        # nudge the split per repeat so every lane is a distinct scenario
        f = min(0.95, fr + 1e-4 * (i // (len(MEMORY_GB) * len(SPLITS)
                                         * len(routings))))
        out.append(Scenario(node_mb=(gb * GB / 2, gb * GB / 2),
                            small_frac=f, routing=ro, max_slots=64))
        i += 1
    return out


def run():
    import jax

    from repro.sim import sweep
    from repro.workloads import edge_trace

    lanes = int(os.environ.get("GIGA_SWEEP_LANES", DEFAULT_LANES))
    asked = [int(d) for d in os.environ.get(
        "GIGA_SWEEP_DEVICES", DEFAULT_DEVICES).split(",")]
    avail = jax.device_count()
    counts = [d for d in asked if d <= avail]
    skipped = [d for d in asked if d > avail]
    if not counts:
        raise ValueError(f"GIGA_SWEEP_DEVICES={asked}: none fits the "
                         f"{avail} visible device(s)")

    tr = edge_trace(seed=0, duration_s=600.0)
    scns = grid(lanes)
    base = sweep(tr, scns)          # unsharded reference (and warm-up)
    times = {}
    for d in counts:
        rs = sweep(tr, scns, devices=d)           # compile
        t0 = time.perf_counter()
        rs = sweep(tr, scns, devices=d)           # measure
        times[str(d)] = time.perf_counter() - t0
        if not all(a.summary() == b.summary()
                   and np.array_equal(a.node, b.node)
                   and np.array_equal(a.outcome, b.outcome)
                   for a, b in zip(base, rs)):
            raise RuntimeError(f"sharded sweep (devices={d}) diverged from "
                               "unsharded — bitwise identity violated")
    doc = {"lanes": lanes, "events": len(tr), "device_counts": counts,
           "skipped_device_counts": skipped, "times": times,
           "platform": jax.devices()[0].platform,
           "host_cores": os.cpu_count()}
    lines = []
    for d in counts:
        t = times[str(d)]
        lines.append(csv_line(
            f"giga_sweep_d{d}", t * 1e6 / lanes,
            f"{lanes / t:.0f} lanes/s ({lanes} lanes x {len(tr)} events, "
            f"{d} {doc['platform']} device(s))"))
    dmax = counts[-1]
    lines.append(csv_line(
        f"giga_sweep_speedup_d{dmax}", times[str(dmax)] * 1e6,
        f"{times[str(counts[0])] / max(times[str(dmax)], 1e-9):.2f}x vs "
        f"{counts[0]} device(s) ({doc['host_cores']} host core(s); "
        f"skipped {skipped or 'none'} — more than the {avail} visible)"))
    lines.append(csv_line(
        "giga_sweep_bitwise", 0.0,
        "sharded == unsharded verified at every device count"))
    return lines, {"giga_sweep": doc}


if __name__ == "__main__":
    for line in run()[0]:
        print(line)
