"""Pallas TPU kernel: blocked (flash) attention forward with GQA, causal
masking, and sliding-window support — the prefill hot-spot at 32k.

Layout: q (B, H, S, hd), k/v (B, KV, S, hd).  Grid is
(B, H, nq, nk) with the kv axis innermost ("arbitrary" semantics —
sequential revisits of the same output block); the online-softmax
accumulators (m, l, acc) live in VMEM scratch and the output block is
written on the last kv iteration.  MXU-aligned tiles: block_q x hd and
block_kv x hd with hd padded to 128 by the wrapper (ops.py).

Sliding windows shrink the kv range per q block *statically is not
possible in a rectangular grid*, so out-of-window blocks are masked; the
wrapper clamps nk to ceil((window + block_q)/block_kv) extra blocks only
when the whole sequence is windowed (cost model in ops.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, causal, window, block_q, block_kv, n_kv_blocks,
            seq_len):
    qb = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)          # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (bq, bk)

    qpos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                   logits.shape, 0)
    kpos = kb * block_kv + jax.lax.broadcasted_iota(jnp.int32,
                                                    logits.shape, 1)
    mask = kpos < seq_len
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    logits = jnp.where(mask, logits, NEG_INF)

    # m/l scratches are lane-padded (bq, 128) with every lane equal
    # (see decode_view): per-row consumers slice lane 0
    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, logits.max(-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new[:, :1])
    l_new = l_prev * alpha + p.sum(-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha[:, :1] + jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(kb == n_kv_blocks - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...][:, :1], 1e-30)
                       ).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal=True, window=0,
                         block_q=128, block_kv=128, valid_len=None,
                         interpret):
    """q: (B, H, Sq, hd); k, v: (B, KV, Sk, hd); hd multiple of 128,
    Sq % block_q == 0, Sk % block_kv == 0.  Returns (B, H, Sq, hd)."""
    b, h, sq, hd = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    group = h // kvh
    scale = 1.0 / math.sqrt(hd)
    nq = sq // block_q
    nk = sk // block_kv
    grid = (b, h, nq, nk)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, n_kv_blocks=nk,
        seq_len=valid_len if valid_len is not None else sk)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda bi, hi, qi, ki, g=group: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda bi, hi, qi, ki, g=group: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        scratch_shapes=[
            _scratch((block_q, 128)),
            _scratch((block_q, 128)),
            _scratch((block_q, hd)),
        ],
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)


def _scratch(shape):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, jnp.float32)
