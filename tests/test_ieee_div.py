"""``core.ieee.ieee_div`` rounds every quotient exactly like numpy's f32
division, so the JAX engines' divisions cannot drift from the oracle's
on a backend whose own division is approximate."""
import jax
import numpy as np
import pytest

from repro.core.ieee import div, ieee_div


def _bits(x):
    return np.asarray(x, np.float32).view(np.int32)


@pytest.mark.parametrize("decades", [(-6, 7), (-1, 2), (2, 6)])
def test_matches_numpy_bitwise(decades):
    rng = np.random.default_rng(sum(decades) + 10)
    n = 200_000
    lo, hi = decades
    a = (rng.standard_normal(n) * 10.0 ** rng.integers(lo, hi, n)
         ).astype(np.float32)
    b = (np.abs(rng.standard_normal(n)) * 10.0 ** rng.integers(lo, hi, n)
         + np.float32(1e-6)).astype(np.float32)
    got = jax.jit(ieee_div)(a, b)
    assert np.array_equal(_bits(got), _bits(a / b))


def test_engine_shaped_operands():
    """What the engines divide: quantized counts and sizes (integer MB,
    1/64 s grid), signed zeros, and scalars broadcast against rows."""
    rng = np.random.default_rng(0)
    a = (rng.integers(-4096, 4096, 5000) / 64).astype(np.float32)
    a[:3] = (0.0, -0.0, 1.0)
    b = rng.integers(1, 8192, 5000).astype(np.float32)
    assert np.array_equal(_bits(ieee_div(a, b)), _bits(a / b))
    col = np.float32(7.0)
    assert np.array_equal(_bits(ieee_div(a, col)), _bits(a / col))
    assert np.array_equal(_bits(ieee_div(a, np.float32(1e-6))),
                          _bits(a / np.float32(1e-6)))


def test_div_dispatches_on_namespace():
    a, b = np.float32(1.0), np.float32(3.0)
    assert isinstance(div(np, a, b), np.floating)
    assert _bits(div(jax.numpy, a, b)) == _bits(a / b)
