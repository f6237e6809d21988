"""Pallas TPU kernels: absorbed-query MLA decode attends over the
compressed latent cache (view-resident and paged forms).

MLA decode absorbs the per-head key up-projection into the query
(``q_lat = q_nope @ W_k``) so attention runs directly against the
shared (rank-r) latent stream plus the small rotary key — the score is
``q_lat·c_kv + q_rope·k_rope`` and the value is the latent itself (the
value up-projection is applied after attention, outside the kernel).
Both kernels stream the latent sequence with an online softmax over a
(C*H, r) accumulator; every head attends the SAME latent row, so there
is no GQA grouping — heads fold straight into the query-row axis.

  mla_views_attend   latents already gathered into per-row contiguous
                     views (B, S+1, r): grid (B, n_blocks), per-row
                     positions in scalar prefetch, masking
                     ``kpos <= qpos`` (the trailing trash slot S always
                     masks — live frontiers stop at S-1).
  mla_paged_attend   latents in the shared block pools (nb, bs, r):
                     grid (B, n_blocks_per_seq) with the per-sequence
                     block table in scalar prefetch routing each
                     block's DMA, like flash_decode's paged kernel.
                     Trash block 0 only ever backs rows whose every
                     kpos exceeds qpos, so it is masked by position
                     alone.

``scale`` is explicit (1/sqrt(d_nope + d_rope)) so zero-padding r/rd
up to the 128-lane tile contributes nothing to the dots and nothing to
the temperature.

TP composition: the latent pools are replicated over the serve
sub-mesh's "model" axis by construction (tp_spec records
"latent-replicated/heads" — only the head projections shard), so both
kernels run replicated on the latent stream without forcing any
reshard; the sharded per-head work stays in the surrounding einsums.
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


def _mla_body(pos_ref, ql_ref, qr_ref, ckv_ref, kr_ref, o_ref,
              m_scr, l_scr, acc_scr, *, scale, block, n_blocks, chunk,
              heads, r):
    b, kb = pl.program_id(0), pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    rows = chunk * heads
    ql = ql_ref[0].astype(jnp.float32).reshape(rows, ql_ref.shape[-1])
    qr = qr_ref[0].astype(jnp.float32).reshape(rows, qr_ref.shape[-1])
    ckv = ckv_ref[0].astype(jnp.float32)                  # (block, r_pad)
    kr = kr_ref[0].astype(jnp.float32)                    # (block, rd_pad)

    logits = (jax.lax.dot_general(ql, ckv, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
              + jax.lax.dot_general(qr, kr, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
              ) * scale                                   # (rows, block)
    kpos = kb * block + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    qpos = pos_ref[b] + jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                                 0) // heads
    logits = jnp.where(kpos <= qpos, logits, NEG_INF)

    # m/l scratches are lane-padded to (rows, 128) with every lane
    # equal (Mosaic wants 128-lane minors; a (rows, 1) scratch
    # relayouts every access) — row-stats broadcast across the lanes,
    # per-row consumers slice lane 0
    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, logits.max(-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new[:, :1])
    l_scr[...] = l_prev * alpha + p.sum(-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha[:, :1] + jax.lax.dot(
        p, ckv, preferred_element_type=jnp.float32)       # (rows, r_pad)
    m_scr[...] = m_new

    @pl.when(kb == n_blocks - 1)
    def _fin():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...][:, :1], 1e-30)
                    ).reshape(chunk, heads, r).astype(o_ref.dtype)


def _mla_paged_body(bt_ref, pos_ref, *args, **kwargs):
    # block-table routing lives entirely in the BlockSpec index maps;
    # the compute body only needs the positions
    _mla_body(pos_ref, *args, **kwargs)


def mla_views_attend(q_lat, q_rope, ckv, kr, pos, *, scale, block=128,
                     interpret):
    """q_lat (B,C,H,r), q_rope (B,C,H,rd); ckv (B,S,r), kr (B,S,rd)
    per-row contiguous latent views (slot j = position j); pos (B,).
    r % 128 == 0, rd % 128 == 0, S % block == 0.  Returns (B,C,H,r).
    """
    from jax.experimental.pallas import tpu as pltpu
    b, c, h, r = q_lat.shape
    rd = q_rope.shape[-1]
    s = ckv.shape[1]
    nk = s // block

    kernel = functools.partial(
        _mla_body, scale=scale, block=block, n_blocks=nk, chunk=c,
        heads=h, r=r)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((1, c, h, r), lambda bi, ki, ps: (bi, 0, 0, 0)),
            pl.BlockSpec((1, c, h, rd), lambda bi, ki, ps: (bi, 0, 0, 0)),
            pl.BlockSpec((1, block, r), lambda bi, ki, ps: (bi, ki, 0)),
            pl.BlockSpec((1, block, rd), lambda bi, ki, ps: (bi, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, c, h, r),
                               lambda bi, ki, ps: (bi, 0, 0, 0)),
        scratch_shapes=[_scratch((c * h, 128)), _scratch((c * h, 128)),
                        _scratch((c * h, r))],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, c, h, r), q_lat.dtype),
        name="mla_views_attend",
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32).reshape(b), q_lat, q_rope, ckv, kr)


def mla_paged_attend(q_lat, q_rope, ckv_pool, kr_pool, block_tables, pos,
                     *, scale, interpret):
    """q_lat (B,C,H,r), q_rope (B,C,H,rd); pools (nb, bs, r)/(nb, bs, rd)
    shared across sequences; block_tables (B, n_blocks_per_seq) with
    trash block 0 backing unassigned entries; pos (B,) position of each
    row's first query.  r % 128 == 0, rd % 128 == 0.  Returns (B,C,H,r).
    """
    from jax.experimental.pallas import tpu as pltpu
    b, c, h, r = q_lat.shape
    rd = q_rope.shape[-1]
    bs = ckv_pool.shape[1]
    nbs = block_tables.shape[1]

    kernel = functools.partial(
        _mla_paged_body, scale=scale, block=bs, n_blocks=nbs, chunk=c,
        heads=h, r=r)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nbs),
        in_specs=[
            pl.BlockSpec((1, c, h, r), lambda bi, ki, bt, ps: (bi, 0, 0, 0)),
            pl.BlockSpec((1, c, h, rd),
                         lambda bi, ki, bt, ps: (bi, 0, 0, 0)),
            pl.BlockSpec((1, bs, r), lambda bi, ki, bt, ps: (bt[bi, ki], 0, 0)),
            pl.BlockSpec((1, bs, rd),
                         lambda bi, ki, bt, ps: (bt[bi, ki], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, c, h, r),
                               lambda bi, ki, bt, ps: (bi, 0, 0, 0)),
        scratch_shapes=[_scratch((c * h, 128)), _scratch((c * h, 128)),
                        _scratch((c * h, r))],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, c, h, r), q_lat.dtype),
        name="mla_paged_attend",
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(pos, jnp.int32).reshape(b),
      q_lat, q_rope, ckv_pool, kr_pool)
