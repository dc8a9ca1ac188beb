"""Whether the timed path's answers are right: a comparison with the
configuration's plain reference (``bench/reference/<name>.py``), which
imports nothing of the program.

Three counts are compared, each with limit 0: the invocations whose
routed node or hit/miss/drop outcome differs from the reference's, those
whose end-to-end latency differs, and the keys of ``Result.summary()``
whose value differs.  The comparison is exact because the traffic puts
every time on a 1/64 s grid and every size on a whole MB, so the
program's float32 and the reference's float64 arithmetic agree to the
bit.  The control (``round_bf16``) feeds the program the same trace with
its float32 fields rounded through bfloat16 on the JAX side, and has to
come out as not correct.
"""
from __future__ import annotations

import numpy as np

#: the numbers compared, each with its limit
LIMITS = {"answers_differ": 0, "latencies_differ": 0, "summary_differ": 0}


def compare(got, want: dict) -> dict:
    """Counts of ``got`` (a :class:`repro.sim.Result`) against ``want``
    (the reference's ``replay``), with what was compared."""
    n = len(want["outcome"])
    if len(got.outcome) != n:
        answers = latencies = max(n, len(got.outcome))
    else:
        answers = int(np.count_nonzero(
            (np.asarray(got.node) != want["node"])
            | (np.asarray(got.outcome) != want["outcome"])))
        latencies = int(np.count_nonzero(
            np.asarray(got.latencies) != want["latency"]))
    s, w = got.summary(), want["summary"]
    return {"answers_differ": answers, "latencies_differ": latencies,
            "summary_differ": sum(s.get(k) != v for k, v in w.items())
            + len(s.keys() - w.keys()),
            "answers_compared": n}


def verdict(counts: dict) -> bool:
    return counts["answers_compared"] > 0 and all(
        counts[k] <= lim for k, lim in LIMITS.items())


def round_bf16(trace):
    """The control's input: every float32 field of ``trace`` rounded
    through bfloat16 by JAX."""
    import jax.numpy as jnp
    return trace.replace(**{
        f: np.asarray(jnp.asarray(getattr(trace, f)).astype(jnp.bfloat16)
                      .astype(jnp.float32))
        for f in ("t", "size_mb", "warm_dur", "cold_dur")})
