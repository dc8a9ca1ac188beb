"""The general traffic generator: a stream spec (a dict from a traffic
file) and a seed in, a sorted :class:`repro.core.types.Trace` out.

``spec["kind"]`` names a module of this package (``edge``) whose
``generate(spec, seed)`` builds the stream; a new kind is a new module,
found by name.  The modules are numpy-only copies of the program's
generators, pinned by ``bench/tests/test_gen.py``.
"""
from __future__ import annotations

import importlib
import re

_KIND = re.compile(r"[a-z][a-z0-9_]*\Z")


def stream(spec: dict, seed: int):
    """The trace that ``spec`` describes, drawn from ``seed``."""
    kind = spec["kind"]
    if not _KIND.match(kind):
        raise ValueError(f"stream kind {kind!r} is not a module name")
    params = {k: v for k, v in spec.items() if k != "kind"}
    return importlib.import_module(f"{__name__}.{kind}").generate(params,
                                                                  seed)
