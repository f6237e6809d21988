"""Pallas TPU kernel: decode attention over the per-row contiguous K/V
views the N-step decode loop keeps resident (transformer._loop_views).

One query token per row attends its (B, S+1, KV, hd) view — slot j holds
logical position j; slot S is the trash row inactive rows write to — so
the kernel needs no block-table indirection at all: the view IS the
sequence, already gathered once per dispatch.  Per-row positions ride in
scalar prefetch; masking is ``kpos <= pos[b]`` (plus the sliding
window), which hides every unwritten slot and the trash row (its kpos
exceeds any live frontier).  Grid (B, n_kv_blocks) with the kv axis
innermost/sequential; online softmax in VMEM scratch; GQA folds the
head group into the logits tile exactly like flash_decode.

TP composition: every tile indexes the kv-head axis contiguously, so
under the serve sub-mesh the kernel runs directly on kv-head shards —
the same layout ``sharding.serve_cache_pspecs`` gives the block pools
the views were gathered from — without forcing a reshard.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _scratch(shape, dtype=jnp.float32):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def _view_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                 *, scale, block_kv, n_kv_blocks, kv_heads, group, window):
    b, kb = pl.program_id(0), pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    h, hd = q_ref.shape[1], q_ref.shape[2]
    q = q_ref[0].astype(jnp.float32).reshape(kv_heads, group, hd)
    kt = jnp.swapaxes(k_ref[0].astype(jnp.float32), 0, 1)   # (KV, bk, hd)
    vt = jnp.swapaxes(v_ref[0].astype(jnp.float32), 0, 1)

    logits = jax.lax.dot_general(
        q, kt, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale          # (KV, G, bk)
    kpos = kb * block_kv + jax.lax.broadcasted_iota(jnp.int32,
                                                    logits.shape, 2)
    valid = kpos <= pos_ref[b]
    if window:
        valid &= kpos > pos_ref[b] - window
    logits = jnp.where(valid, logits, NEG_INF)
    logits = logits.reshape(h, logits.shape[-1])             # (H, bk)

    # m/l scratches are lane-padded to (H, 128) with every lane equal
    # (Mosaic wants 128-lane minors; a (H, 1) scratch relayouts every
    # access) — the keepdims row-stats broadcast across all lanes, and
    # per-row consumers slice lane 0
    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, logits.max(-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new[:, :1])
    l_scr[...] = l_prev * alpha + p.sum(-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.reshape(kv_heads, group, -1), vt,
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                  # (KV, G, hd)
    acc_scr[...] = acc_scr[...] * alpha[:, :1] + pv.reshape(h, hd)
    m_scr[...] = m_new

    @pl.when(kb == n_kv_blocks - 1)
    def _fin():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...][:, :1], 1e-30)
                    ).astype(o_ref.dtype)


def decode_view_attend_bhd(q, k, v, pos, *, scale, window=0, block_kv=128,
                           interpret):
    """q (B,H,hd); k,v (B,S,KV,hd) per-row contiguous views (slot j =
    logical position j); pos (B,) int32 per-row query positions.
    hd % 128 == 0, S % block_kv == 0.  ``scale`` is passed explicitly so
    zero-padded head lanes don't perturb the softmax temperature.
    Returns (B,H,hd).
    """
    from jax.experimental.pallas import tpu as pltpu
    b, h, hd = q.shape
    s, kvh = k.shape[1], k.shape[2]
    group = h // kvh
    nk = s // block_kv

    kernel = functools.partial(
        _view_kernel, scale=scale, block_kv=block_kv, n_kv_blocks=nk,
        kv_heads=kvh, group=group, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((1, h, hd), lambda bi, ki, ps: (bi, 0, 0)),
            pl.BlockSpec((1, block_kv, kvh, hd),
                         lambda bi, ki, ps: (bi, ki, 0, 0)),
            pl.BlockSpec((1, block_kv, kvh, hd),
                         lambda bi, ki, ps: (bi, ki, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, hd), lambda bi, ki, ps: (bi, 0, 0)),
        scratch_shapes=[_scratch((h, 128)), _scratch((h, 128)),
                        _scratch((h, hd))],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        name="decode_view_attend",
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32).reshape(b), q, k, v)
