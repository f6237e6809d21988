"""Pallas TPU kernels: fused slot gather/scatter for the ssm/rglru
recurrent-state pools.

Slot-state families keep one fixed-size state row per sequence in a
shared (S, F) pool (conv tails, SSD state, LRU hidden).  Each decode
dispatch gathers every batch row's slot into a (B, F) working set and
scatters it back afterwards; in jnp both sides lower to O(B·F) dynamic
gathers inside the fori_loop.  Here the slot indices ride in scalar
prefetch and drive the BlockSpec index maps directly, so each grid step
is one routed DMA copy:

  gather    grid (B,): block b reads pool row ``slots[b]``; rows
            flagged ``fresh`` (first token — no state yet) emit zeros
            instead of whatever the slot holds.
  scatter   grid (S,): the pool is updated row-by-row from an inverse
            map built on the host (``src[s] = which batch row writes
            slot s, else -1``), which sidesteps in-place aliasing: slot
            rows nobody writes copy through unchanged.  Rows with
            ``valid_len == 0`` are routed to trash slot 0 by the caller
            (same contract as layers.slot_state_scatter); duplicate
            writers can only collide on the trash slot, whose content
            no live token ever reads.

TP composition: the serve sub-mesh shards these pools over channels
(tp_spec "channels") and both kernels index only the slot axis — the
feature axis is contiguous within every block — so they run directly on
channel shards without forcing a reshard.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _gather_kernel(slots_ref, fresh_ref, pool_ref, o_ref):
    b = pl.program_id(0)
    row = pool_ref[...]
    o_ref[...] = jnp.where(fresh_ref[b] != 0, jnp.zeros_like(row), row)


def _row_spec(rows, index_map):
    # one pool row per grid step, viewed as (1, F/128, 128): the minor
    # two block dims then span the whole row, which Mosaic accepts at
    # any F/128 (a (1, F) block of an (S, F) array would need S == 1)
    return pl.BlockSpec((1, rows, 128), index_map)


def slot_gather_rows(pool, slots, fresh, *, interpret):
    """pool (S, F), slots (B,) int32, fresh (B,) int32 (nonzero → emit
    zeros).  F % 128 == 0.  Returns (B, F) in pool dtype."""
    from jax.experimental.pallas import tpu as pltpu
    s, f = pool.shape
    b = slots.shape[0]
    r = f // 128
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[_row_spec(r, lambda bi, sl, fr: (sl[bi], 0, 0))],
        out_specs=_row_spec(r, lambda bi, sl, fr: (bi, 0, 0)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, r, 128), pool.dtype),
        name="slot_gather_rows",
        interpret=interpret,
    )(slots, fresh, pool.reshape(s, r, 128))
    return out.reshape(b, f)


def _scatter_kernel(src_ref, has_ref, pool_ref, val_ref, o_ref):
    s = pl.program_id(0)
    o_ref[...] = jnp.where(has_ref[s] != 0, val_ref[...], pool_ref[...])


def slot_scatter_rows(pool, slots, values, *, interpret):
    """pool (S, F); slots (B,) int32 destination per batch row; values
    (B, F) (already in pool dtype).  F % 128 == 0.  Returns the updated
    (S, F) pool — semantics of ``pool.at[slots].set(values)`` given the
    pool invariant that duplicate slots only occur at trash slot 0."""
    from jax.experimental.pallas import tpu as pltpu
    s, f = pool.shape
    b = values.shape[0]
    r = f // 128
    src = jnp.full((s,), -1, jnp.int32).at[slots].set(
        jnp.arange(b, dtype=jnp.int32))
    has = (src >= 0).astype(jnp.int32)
    src_c = jnp.maximum(src, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s,),
        in_specs=[
            _row_spec(r, lambda si, sc, hs: (si, 0, 0)),
            _row_spec(r, lambda si, sc, hs: (sc[si], 0, 0)),
        ],
        out_specs=_row_spec(r, lambda si, sc, hs: (si, 0, 0)),
    )
    out = pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, r, 128), pool.dtype),
        name="slot_scatter_rows",
        interpret=interpret,
    )(src_c, has, pool.reshape(s, r, 128), values.reshape(b, r, 128))
    return out.reshape(s, f)
