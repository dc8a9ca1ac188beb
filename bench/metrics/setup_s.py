"""Seconds from process start to the start of the first timed job."""


def read(ctx):
    return ctx["setup_s"]
