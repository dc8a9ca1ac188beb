"""Fused Pallas pool-step kernel: the ``"fused"`` step backend.

One Pallas pass over the stacked ``[pools, slots]`` axes fuses the three
pieces of the miss path that ``core.pool_jax._evict_prefix`` expresses as
an argsort composite:

1. **(priority, seq) ranking by counting** — instead of the double
   stable ``argsort``, each slot counts the evictable bytes of every
   slot strictly before it in the eviction order::

       before_i = sum_j [ (pri_j, seq_j) <lex (pri_i, seq_i) ] * sz_j

   with ``sz_j = idle_j ? size_j : 0``.  This is *bitwise* identical to
   the sort + prefix-sum: among idle slots ``(pri, seq)`` is a strict
   total order (``seq`` strictly increases per insert), non-idle slots
   contribute zero bytes so their position is irrelevant, and traces are
   quantized (integer MB, 1/64 s grid) so the f32 sums are exact in any
   reduction order.
2. **prefix-sum eviction** — ``evict_i = idle_i & (before_i < deficit -
   1e-9)``, the identical epsilon as ``_evict_prefix``.
3. **slot placement** — first slot empty after eviction, plus the
   ``empty_exists`` admission bit.

TPU layout.  Every ``[P, S]`` operand is one whole-array VMEM block (a
block's last two dims must be multiples of (8, 128) or equal the array's
dims, which a per-pool ``(1, S)`` block is not), and the kernel loops
over the pool rows.  Per row, the ``j`` side of the rank matrix is taken
in column chunks of at most ``_J_CHUNK`` slots, so VMEM holds a
``[_J_CHUNK, S]`` slab rather than ``[S, S]`` (or ``[P, S, S]``).  A row
becomes a column without a reshape or transpose: broadcast it to a
square, keep the diagonal, and reduce across lanes with ``max`` (exact
for every value, ``inf`` included).  Interpret mode takes the column
with a plain reshape instead, which Mosaic does not lower but the CPU
runs about 13x faster at ``[8, 256]``; both give the same bits.  The per-pool deficits come in as a
``[P, 1]`` column, and the four per-pool scalars leave as one lane-dense
``[P, 128]`` f32 row per pool (slot indices are exact in f32).
``interpret=True`` (the default off-TPU) runs the same body on the CPU.

Boolean masks cross the kernel boundary as int32; the wrapper restores
the ``core.pool_jax`` step-backend contract.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.pool_jax import register_step_backend

_J_CHUNK = 256   # rank-matrix rows per slab (VMEM ~ _J_CHUNK * S * 4 B)
_LANES = 128     # width of the per-pool scalar output row


def _column(row, interpret: bool):
    """A ``(1, w)`` f32 row as a ``(w, 1)`` column: on the TPU broadcast,
    keep the diagonal, max-reduce across lanes; in interpret mode a
    reshape."""
    w = row.shape[1]
    if interpret:
        return row.reshape(w, 1)
    sq = jnp.broadcast_to(row, (w, w))
    eye = (jax.lax.broadcasted_iota(jnp.int32, (w, w), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (w, w), 1))
    return jnp.max(jnp.where(eye, sq, -jnp.inf), axis=1, keepdims=True)


def _evict_place_kernel(deficit_ref, pri_ref, seq_ref, size_ref, idle_ref,
                        valid_ref, evict_ref, stats_ref, *, p: int, s: int,
                        interpret: bool):
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, s), 1).astype(jnp.float32)
    out_lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

    def pool(k, carry):
        row = pl.ds(k, 1)
        pri = pri_ref[row, :]                  # [1, S] (+inf on non-idle)
        seq = seq_ref[row, :]
        size = size_ref[row, :]
        idle = idle_ref[row, :] != 0
        valid = valid_ref[row, :] != 0
        sz = jnp.where(idle, size, 0.0)        # evictable bytes per slot

        # before_i = bytes of every slot j evicted strictly before slot i,
        # with j down the sublanes of each slab and i across the lanes
        # (each slab is loaded from the refs: Mosaic cannot broadcast a
        # slice taken at a lane offset of an already-loaded row)
        before = jnp.zeros((1, s), jnp.float32)
        for lo in range(0, s, _J_CHUNK):
            cols = pl.ds(lo, min(_J_CHUNK, s - lo))
            pri_j = _column(pri_ref[row, cols], interpret)
            seq_j = _column(seq_ref[row, cols], interpret)
            sz_j = _column(jnp.where(idle_ref[row, cols] != 0,
                                     size_ref[row, cols], 0.0), interpret)
            less = (pri_j < pri) | ((pri_j == pri) & (seq_j < seq))
            before = before + jnp.sum(jnp.where(less, sz_j, 0.0), axis=0,
                                      keepdims=True)
        evict = idle & (before < deficit_ref[row, :] - 1e-9)

        empty = ~(valid & ~evict)
        freed = jnp.sum(jnp.where(evict, size, 0.0))
        avail = jnp.sum(sz)
        # first empty slot (argmax of a bool row: 0 when there is none)
        has_empty = jnp.max(jnp.where(empty, 1.0, 0.0))
        first = jnp.min(jnp.where(empty, lane, jnp.float32(s)))
        ins = jnp.where(has_empty > 0, first, 0.0)
        evict_ref[row, :] = evict.astype(jnp.int32)
        stats_ref[row, :] = jnp.where(
            out_lane == 0, freed, jnp.where(
                out_lane == 1, avail, jnp.where(
                    out_lane == 2, ins, jnp.where(out_lane == 3, has_empty,
                                                  0.0))))
        return carry

    jax.lax.fori_loop(0, p, pool, 0)


def fused_evict_place_impl(pri, seq, size, idle, valid, deficit, *,
                           interpret: bool):
    """The raw ``pallas_call`` (explicit ``interpret``) — the registered
    backend resolves ``interpret`` from the platform; benchmarks and the
    interpret-mode unit tests call this directly."""
    p, s = pri.shape
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    evict, stats = pl.pallas_call(
        functools.partial(_evict_place_kernel, p=p, s=s,
                          interpret=interpret),
        in_specs=[vmem] * 6,
        out_specs=[vmem, vmem],
        out_shape=[
            jax.ShapeDtypeStruct((p, s), jnp.int32),
            jax.ShapeDtypeStruct((p, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(deficit.reshape(p, 1), pri, seq, size, idle.astype(jnp.int32),
      valid.astype(jnp.int32))
    return (evict != 0, stats[:, 0], stats[:, 2].astype(jnp.int32),
            stats[:, 1], stats[:, 3] != 0)


@register_step_backend("fused")
def fused_evict_place(pri, seq, size, idle, valid, deficit):
    """Step-backend entry: compiled Pallas on TPU, interpret elsewhere
    (resolved at trace time, so jitted programs bake the right lowering
    in and CPU CI exercises the same kernel body bit-for-bit)."""
    return fused_evict_place_impl(
        pri, seq, size, idle, valid, deficit,
        interpret=jax.default_backend() != "tpu")
