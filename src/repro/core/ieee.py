"""Correctly rounded float32 division for the JAX engines.

The JAX engines stay bit-identical to the numpy oracle only if every f32
operation inside the scan rounds exactly as numpy does.  XLA:TPU divides
with a reciprocal approximation that is not always correctly rounded: on
a TPU v5e the autoscaler's split update came out one ulp off the
oracle's.  One ulp is enough to reorder evictions, after which the two
engines diverge.  :func:`div` is the one place the engines divide: numpy
keeps its own (IEEE) division, and JAX gets the IEEE round-to-nearest-
even quotient from a long division of the integer significands, which
uses no float division at all and so rounds the same on every backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_MANT = 0x7FFFFF          # f32 fraction field
_HIDDEN = 0x800000        # implicit leading significand bit
_SIGN = -(2 ** 31)        # the sign bit as an int32


def div(xp, a, b):
    """``a / b`` in float32 for the namespace ``xp`` the caller computes
    in (``numpy`` in the oracle, ``jax.numpy`` in the engines)."""
    if xp is np:
        return a / b
    return ieee_div(a, b)


def ieee_div(a, b) -> jax.Array:
    """``a / b`` rounded to nearest-even, bit for bit like IEEE-754 f32.

    Domain: ``a`` finite (a zero or subnormal ``a`` gives a signed zero),
    ``b`` positive and normal, and a quotient in the normal range — every
    division the engines make (they divide by ``max(x, 1e-6)`` or by a
    count of at least one)."""
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    a, b = jnp.broadcast_arrays(a, b)
    ia = jax.lax.bitcast_convert_type(a, jnp.int32)
    ib = jax.lax.bitcast_convert_type(b, jnp.int32)
    ea = (ia >> 23) & 0xFF
    eb = (ib >> 23) & 0xFF
    ma = (ia & _MANT) | _HIDDEN
    mb = (ib & _MANT) | _HIDDEN
    # q = floor(ma * 2**25 / mb), in [2**24, 2**26), by restoring division
    q = (ma >= mb).astype(jnp.int32)
    rem = jnp.where(ma >= mb, ma - mb, ma)
    for _ in range(25):
        rem = rem << 1
        bit = rem >= mb
        rem = jnp.where(bit, rem - mb, rem)
        q = (q << 1) | bit.astype(jnp.int32)
    wide = q >= (1 << 25)                 # ma >= mb: one more bit than kept
    mant = jnp.where(wide, q >> 2, q >> 1)
    guard = jnp.where(wide, (q >> 1) & 1, q & 1)
    sticky = (rem != 0) | (wide & ((q & 1) != 0))
    mant = mant + (guard & (sticky | ((mant & 1) != 0)).astype(jnp.int32))
    carry = mant == (1 << 24)
    mant = jnp.where(carry, mant >> 1, mant)
    exp = ea - eb + 126 + wide.astype(jnp.int32) + carry.astype(jnp.int32)
    sign = (ia ^ ib) & _SIGN
    bits = jnp.where(ea == 0, sign, sign | (exp << 23) | (mant & _MANT))
    return jax.lax.bitcast_convert_type(bits, jnp.float32)
