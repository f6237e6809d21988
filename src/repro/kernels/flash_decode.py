"""Pallas TPU kernel: single-token decode attention over a blocked KV
cache (the decode_32k / long_500k serving hot loop).

One query vector per (batch, head) attends over the cache in block_kv
chunks streamed HBM->VMEM; online softmax in VMEM scratch.  Grid
(B, nk) with nk innermost/sequential.  GQA folds the head group into the
leading axis of the logits tile ((KV, G, bk) batched dot on the MXU).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _scratch(shape):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, jnp.float32)


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, block_kv, n_kv_blocks, kv_heads, group):
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    h, hd = q_ref.shape[1], q_ref.shape[2]
    q = q_ref[0].astype(jnp.float32).reshape(kv_heads, group, hd)
    k = k_ref[0].astype(jnp.float32)             # (bk, KV, hd)
    v = v_ref[0].astype(jnp.float32)
    kt = jnp.swapaxes(k, 0, 1)                   # (KV, bk, hd)
    vt = jnp.swapaxes(v, 0, 1)

    logits = jax.lax.dot_general(
        q, kt, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale      # (KV, G, bk)
    kpos = kb * block_kv + jax.lax.broadcasted_iota(jnp.int32,
                                                    logits.shape, 2)
    logits = jnp.where(kpos < len_ref[0, 0], logits, NEG_INF)
    logits = logits.reshape(h, logits.shape[-1])         # (H, bk)

    # m/l scratches are lane-padded (rows, 128) with every lane equal
    # (see decode_view): per-row consumers slice lane 0
    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, logits.max(-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new[:, :1])                   # (H, bk)
    l_scr[...] = l_prev * alpha + p.sum(-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.reshape(kv_heads, group, -1), vt,
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)              # (KV, G, hd)
    acc_scr[...] = acc_scr[...] * alpha[:, :1] + pv.reshape(h, hd)
    m_scr[...] = m_new

    @pl.when(kb == n_kv_blocks - 1)
    def _fin():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...][:, :1], 1e-30)
                    ).astype(o_ref.dtype)


def _paged_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale, block_size, n_blocks,
                  kv_heads, group, chunk, window):
    """Per-(batch, logical-block) step over a paged pool.  The BlockSpec
    index_map already routed k_ref/v_ref to physical block
    block_tables[b, i] via scalar prefetch.  Each row carries ``chunk``
    query tokens at positions pos[b]..pos[b]+chunk-1 (chunk=1 for batched
    decode, >1 for a prefill chunk); masking is causal per query position,
    which also hides every unwritten pool slot (their kpos exceeds the
    frontier)."""
    b, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    h, hd = q_ref.shape[2], q_ref.shape[3]
    # (C,H,hd) -> (KV, C*G, hd): fold the chunk into the per-kv-head
    # query group so the MXU sees one batched (KV, C*G, bs) dot
    q = (q_ref[0].astype(jnp.float32)
         .reshape(chunk, kv_heads, group, hd)
         .swapaxes(0, 1)
         .reshape(kv_heads, chunk * group, hd))
    kt = jnp.swapaxes(k_ref[0].astype(jnp.float32), 0, 1)   # (KV, bs, hd)
    vt = jnp.swapaxes(v_ref[0].astype(jnp.float32), 0, 1)

    logits = jax.lax.dot_general(
        q, kt, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale        # (KV, C*G, bs)
    kpos = i * block_size + jax.lax.broadcasted_iota(jnp.int32,
                                                     logits.shape, 2)
    qpos = pos_ref[b] + jax.lax.broadcasted_iota(jnp.int32,
                                                 logits.shape, 1) // group
    valid = kpos <= qpos
    if window:
        valid &= kpos > qpos - window
    logits = jnp.where(valid, logits, NEG_INF)
    logits = logits.reshape(chunk * h, logits.shape[-1])   # (C*H, bs)

    m_prev, l_prev = m_scr[...], l_scr[...]             # (C*H, 128)
    m_new = jnp.maximum(m_prev, logits.max(-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new[:, :1])
    l_scr[...] = l_prev * alpha + p.sum(-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.reshape(kv_heads, chunk * group, -1), vt,
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                # (KV, C*G, hd)
    acc_scr[...] = acc_scr[...] * alpha[:, :1] + pv.reshape(chunk * h, hd)
    m_scr[...] = m_new

    @pl.when(i == n_blocks - 1)
    def _fin():
        o = acc_scr[...] / jnp.maximum(l_scr[...][:, :1], 1e-30)
        o_ref[0] = (o.reshape(kv_heads, chunk, group, hd)
                    .swapaxes(0, 1)
                    .reshape(chunk, h, hd)).astype(o_ref.dtype)


def flash_decode_paged_bhd(q, k_pool, v_pool, block_tables, pos, *,
                           window=0, interpret):
    """Paged decode/prefill-chunk attention (the repro.serve hot loop).

    q (B,C,H,hd) — C query tokens per row (C=1 batched decode, C>1 a
    prefill chunk); k_pool,v_pool (nb, bs, KV, hd) — shared physical
    block pools, already containing this call's new tokens; block_tables
    (B, NB) int32 maps each sequence's logical block i to a physical
    block; pos (B,) int32 absolute position of each row's first query.
    hd % 128 == 0.  Returns (B,C,H,hd).

    Grid (B, NB) with the logical-block axis innermost/sequential; the
    block tables ride in scalar prefetch so the k/v BlockSpec index_map
    can DMA exactly the physical block each step needs.
    """
    from jax.experimental.pallas import tpu as pltpu
    b, c, h, hd = q.shape
    bs, kvh = k_pool.shape[1], k_pool.shape[2]
    nb_seq = block_tables.shape[1]
    group = h // kvh
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(
        _paged_kernel, scale=scale, block_size=bs, n_blocks=nb_seq,
        kv_heads=kvh, group=group, chunk=c, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nb_seq),
        in_specs=[
            pl.BlockSpec((1, c, h, hd), lambda bi, ki, bt, ps: (bi, 0, 0, 0)),
            pl.BlockSpec((1, bs, kvh, hd),
                         lambda bi, ki, bt, ps: (bt[bi, ki], 0, 0, 0)),
            pl.BlockSpec((1, bs, kvh, hd),
                         lambda bi, ki, bt, ps: (bt[bi, ki], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, c, h, hd),
                               lambda bi, ki, bt, ps: (bi, 0, 0, 0)),
        scratch_shapes=[_scratch((c * h, 128)), _scratch((c * h, 128)),
                        _scratch((c * h, hd))],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, c, h, hd), q.dtype),
        name="flash_decode_paged",
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(pos, jnp.int32).reshape(b), q, k_pool, v_pool)


def flash_decode_bhd(q, k, v, length, *, block_kv=512, interpret):
    """q (B,H,hd); k,v (B,S,KV,hd); length scalar int32 (#valid slots).
    hd % 128 == 0, S % block_kv == 0.  Returns (B,H,hd)."""
    b, h, hd = q.shape
    s, kvh = k.shape[1], k.shape[2]
    group = h // kvh
    nk = s // block_kv
    scale = 1.0 / math.sqrt(hd)
    len_arr = jnp.asarray(length, jnp.int32).reshape(1, 1)

    kernel = functools.partial(_kernel, scale=scale, block_kv=block_kv,
                               n_kv_blocks=nk, kv_heads=kvh, group=group)
    return pl.pallas_call(
        kernel,
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((1, 1), lambda bi, ki: (0, 0)),
            pl.BlockSpec((1, h, hd), lambda bi, ki: (bi, 0, 0)),
            pl.BlockSpec((1, block_kv, kvh, hd), lambda bi, ki: (bi, ki, 0, 0)),
            pl.BlockSpec((1, block_kv, kvh, hd), lambda bi, ki: (bi, ki, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, hd), lambda bi, ki: (bi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        scratch_shapes=[_scratch((h, 128)), _scratch((h, 128)),
                        _scratch((h, hd))],
        name="flash_decode",
        interpret=interpret,
    )(len_arr, q, k, v)
