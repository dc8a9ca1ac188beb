"""The benchmark's tests run on the CPU with a compile cache of their
own, both set before JAX is imported.  The program is imported from the
checkout's ``src``."""
import os
import sys
import tempfile
from pathlib import Path

_SRC = str(Path(__file__).resolve().parents[2] / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="bench-tests-jax-cache-"))
