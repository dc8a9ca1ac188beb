"""Jitted public wrappers around the Pallas kernels with automatic backend
dispatch:

* TPU backend            -> compiled Pallas kernels
* everything else        -> the pure-jnp oracles in ``ref.py``
* ``interpret=True``     -> Pallas interpret mode (CPU kernel validation)

The dry-run lowers on host devices, so it exercises the oracle path; on a
real TPU mesh the Pallas kernels are used inside ``shard_map`` with
per-shard shapes (see models/attention.py).
"""
from __future__ import annotations

import jax

from . import ref as _ref


def _use_kernels() -> bool:
    return jax.default_backend() == "tpu"


def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                    q_offset=0, interpret=False):
    if _use_kernels() or interpret:
        from .flash_attention import flash_attention_pallas
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      scale=scale, q_offset=q_offset,
                                      interpret=interpret)
    return _ref.flash_attention(q, k, v, causal=causal, window=window,
                                scale=scale, q_offset=q_offset)


def decode_attention(q, k_cache, v_cache, slot_pos, cur_pos, *,
                     window=None, scale=None, interpret=False):
    if _use_kernels() or interpret:
        from .decode_attention import decode_attention_pallas
        return decode_attention_pallas(q, k_cache, v_cache, slot_pos,
                                       cur_pos, window=window, scale=scale,
                                       interpret=interpret)
    return _ref.decode_attention(q, k_cache, v_cache, slot_pos, cur_pos,
                                 window=window, scale=scale)


def ssm_scan(x, dt, a, b, c, *, h0=None, interpret=False):
    if _use_kernels() or interpret:
        from .ssm_scan import ssm_scan_pallas
        return ssm_scan_pallas(x, dt, a, b, c, h0=h0, interpret=interpret)
    return _ref.ssm_scan(x, dt, a, b, c, h0=h0)


def wkv6(r, k, v, w, u, *, state=None, interpret=False):
    if _use_kernels() or interpret:
        from .wkv6 import wkv6_pallas
        return wkv6_pallas(r, k, v, w, u, state=state, interpret=interpret)
    return _ref.wkv6(r, k, v, w, u, state=state)


# single-step decode updates are tiny elementwise ops; the oracle IS the
# implementation (no kernel warranted).
ssm_decode_step = _ref.ssm_decode_step
wkv6_decode_step = _ref.wkv6_decode_step
