"""Simulated invocations per second of wall time over the window: every
event of every job, over the time from the first job's start to the last
job's return."""


def read(ctx):
    return ctx["events"] / ctx["window_s"]
