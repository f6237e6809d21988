"""Pallas TPU kernel: Mamba-2 SSD intra-chunk block (arXiv:2405.21060).

The chunked SSD algorithm (models/ssm.py) spends its FLOPs in three
batched matmuls per (batch, chunk, head):

    scores = C B^T                (l, l)
    y_diag = (scores ⊙ L) (x·dt)  (l, p)   L = exp(dA_i − dA_j)·[i ≥ j]
    states = (B ⊙ decay·dt)^T x   (n, p)

This kernel fuses all three per grid cell (B·nc, H): one VMEM-resident
pass over the chunk, no (l, l) score tensor in HBM.  Operands are
head-major (ops.py transposes from the model's (bc, l, h, ·) layout).
The inter-chunk recurrence (tiny, sequential) stays in jnp.  Tiles: l = chunk (128/256),
p and n padded to 128 lanes by ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, dt_ref, dacol_ref, darow_ref, b_ref, c_ref, y_ref,
            st_ref):
    x = x_ref[0, 0].astype(jnp.float32)              # (l, p)
    dt = dt_ref[0, 0].astype(jnp.float32)            # (l, 1)
    da = dacol_ref[0, 0].astype(jnp.float32)         # (l, 1)
    da_row = darow_ref[0, 0].astype(jnp.float32)     # (1, l)
    bb = b_ref[0, 0].astype(jnp.float32)             # (l, n)
    cc = c_ref[0, 0].astype(jnp.float32)             # (l, n)
    l = x.shape[0]

    scores = jax.lax.dot_general(cc, bb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (l,l)
    decay = jnp.exp(da - da_row)                     # exp(dA_i - dA_j)
    ii = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    m = (scores * jnp.where(ii >= jj, decay, 0.0))
    xdt = x * dt
    y = jax.lax.dot_general(m, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)       # (l,p)
    da_last = da[l - 1:]                             # (1, 1)
    dte = jnp.exp(da_last - da) * dt                 # decay to chunk end
    st = jax.lax.dot_general(bb * dte, x, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)      # (n,p)
    y_ref[0, 0] = y.astype(y_ref.dtype)
    st_ref[0, 0] = st.astype(st_ref.dtype)


def ssd_chunk_bhcp(x, dt, dacum, B, C, *, interpret):
    """Head-major layout: x (bc, h, l, p); dt/dacum (bc, h, l); B, C
    (bc, h, l, n) (group dim already repeated to heads).  Returns
    (y (bc, h, l, p), states (bc, h, n, p)).

    The per-position scalars ride as (l, 1) columns (and dacum also as
    a (1, l) row for the decay matrix), so every block's minor two dims
    are either full extents or lane multiples — no 1-D values and no
    width-1 head tiles in the kernel."""
    bc, h, l, p = x.shape
    n = B.shape[-1]
    grid = (bc, h)
    tile = lambda *blk: pl.BlockSpec(blk, lambda i, j: (i, j, 0, 0))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[tile(1, 1, l, p), tile(1, 1, l, 1), tile(1, 1, l, 1),
                  tile(1, 1, 1, l), tile(1, 1, l, n), tile(1, 1, l, n)],
        out_specs=[tile(1, 1, l, p), tile(1, 1, n, p)],
        out_shape=[jax.ShapeDtypeStruct((bc, h, l, p), x.dtype),
                   jax.ShapeDtypeStruct((bc, h, n, p), jnp.float32)],
        name="ssd_chunk",
        interpret=interpret,
    )(x, dt[..., None], dacum[..., None], dacum[:, :, None, :], B, C)
