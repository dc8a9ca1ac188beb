"""Share of the traced job in which the device idled while the host was
not waiting on one of the job's programs, in %: the device's idle time
(traced job - busy) less the idle time the trace attributes to the
program's ``sim.wait`` span, over the traced job.  The part of the idle
share that host work alone holds.  Found only where the program opens
``sim.*`` spans; a ``sim.wait`` that falls out of the top idle spans
idled less than the last one listed."""


def read(ctx):
    p = ctx["profile"]
    if p is None:
        return None
    idle = dict(p["idle_gaps"])
    if not any(name.startswith("sim.") for name in idle):
        return None
    busy = sum(p["busy_s"]) / len(p["busy_s"])
    return 100.0 * (p["window_s"] - busy - idle.get("sim.wait", 0.0)) \
        / p["window_s"]
