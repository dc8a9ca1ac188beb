"""Seconds JAX spent in set-up tracing, lowering and compiling or
loading programs from the persistent cache (its ``/jax/core/compile*``
durations)."""


def read(ctx):
    return ctx["compile_s"] if ctx["compile_s"] > 0 else None
