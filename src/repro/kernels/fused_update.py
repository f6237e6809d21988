"""Pallas TPU kernel: fused SGD-momentum(+LARS trust ratio) parameter
update — the memory-bound op sitting exactly where LSGD's deferred update
lands (trainer applies `pending` at the top of each step).

Unfused, XLA issues ~5 HBM round-trips over (w, m, g); fused it is one
read of each + one write of (w, m): the roofline floor for the update is
(2+3)*bytes/HBM_bw and this kernel reaches it structurally.  Tiles are
(8, 128)-aligned (VREG lanes) and streamed block-by-block through VMEM.

Math (PyTorch/paper convention, upcast to f32 in-kernel):
    g' = trust * g + wd * w
    m' = mu * m + g'
    w' = w - lr * (g' + mu * m')   if nesterov else   w - lr * m'
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
SUBLANE = 8
BLOCK_ROWS = 256            # (256, 128) f32 tiles = 128 KiB per operand


def _kernel(w_ref, m_ref, g_ref, s_ref, w_out, m_out, *, momentum,
            weight_decay, nesterov):
    w = w_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    lr = s_ref[0, 0]
    trust = s_ref[0, 1]
    gp = g * trust + weight_decay * w
    m_new = momentum * m + gp
    upd = gp + momentum * m_new if nesterov else m_new
    w_new = w - lr * upd
    w_out[...] = w_new.astype(w_out.dtype)
    m_out[...] = m_new.astype(m_out.dtype)


def fused_sgd_update_2d(w, m, g, scalars, *, momentum, weight_decay,
                        nesterov, interpret):
    """w,m,g: (R, 128) with R % BLOCK_ROWS == 0; scalars: (1,2) f32
    [lr, trust]."""
    rows = w.shape[0]
    grid = (rows // BLOCK_ROWS,)
    blk = lambda i: (i, 0)
    return pl.pallas_call(
        functools.partial(_kernel, momentum=momentum,
                          weight_decay=weight_decay, nesterov=nesterov),
        grid=grid,
        in_specs=[pl.BlockSpec((BLOCK_ROWS, LANE), blk),
                  pl.BlockSpec((BLOCK_ROWS, LANE), blk),
                  pl.BlockSpec((BLOCK_ROWS, LANE), blk),
                  pl.BlockSpec((1, 2), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((BLOCK_ROWS, LANE), blk),
                   pl.BlockSpec((BLOCK_ROWS, LANE), blk)],
        out_shape=[jax.ShapeDtypeStruct(w.shape, w.dtype),
                   jax.ShapeDtypeStruct(m.shape, m.dtype)],
        name="fused_sgd_update",
        interpret=interpret,
    )(w, m, g, scalars)
