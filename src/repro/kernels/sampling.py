"""Pallas TPU kernels: fused on-device token sampling (greedy argmax,
gumbel-max, and top-k + gumbel-max).

The decode loop samples every row every step; in jnp that is a
full-vocab ``top_k`` + ``categorical`` materializing (B, V)
intermediates per step.  These kernels stream the vocab in lane-width
blocks and keep only O(1) scratch per row:

  greedy    grid (B, n_vocab_blocks): streaming argmax with the
            first-occurrence tie rule of ``jnp.argmax`` (strictly-
            greater updates; in-block ties resolve to the lowest
            column).
  gumbel    same stream over ``lg / temperature + gumbel`` — the
            gumbel-max trick IS ``jax.random.categorical`` (bit-exact:
            categorical lowers to argmax(gumbel + logits)), so the
            noise is generated outside the kernel from the engine's
            stateless fold_in(rid, position) keys and streamed in as a
            second operand.  In-kernel PRNG is a follow-on.
  top-k     grid (B, 2, n_vocab_blocks), two sequential phases per row:
            phase 0 maintains a k-entry running top-k in VMEM by k
            unrolled max-extractions per block (same kth as
            ``lax.top_k`` including duplicate values — ALL entries
            tied with the kth survive, matching the oracle's
            ``lg < kth`` mask); phase 1 streams the gumbel argmax over
            ``lg >= kth`` survivors.

All three mask padded vocab lanes by column index, so callers pad V up
to the block multiple with anything.  Operations follow the oracle's
exact float order (cast to f32, divide by temperature, add gumbel) so
sampled tokens are identical, not merely close.

TP composition: sampling runs on the frontier logits after the vocab
all-gather, i.e. replicated over the serve sub-mesh — nothing to
shard, nothing to reshard.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_BLOCK_V = 512


def _scratch(shape, dtype=jnp.float32):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def _smem_scalar(dtype=jnp.float32):
    # the running argmax/kth-value state is a single scalar per row
    # program: a (1, 1) VMEM scratch would burn a full vector tile and
    # relayout on every access, so it lives in scalar memory
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.SMEM((1, 1), dtype)


def _masked_block(lg_ref, kb, *, vocab, block_v):
    vals = lg_ref[0].astype(jnp.float32)                    # (1, bv)
    cols = kb * block_v + jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)
    return jnp.where(cols < vocab, vals, -jnp.inf), cols


def _stream_argmax(score, cols, best_scr, idx_scr, *, vocab):
    m = score.max()
    j = jnp.where(score == m, cols, vocab).min()            # first occurrence
    take = m > best_scr[0, 0]
    idx_scr[0, 0] = jnp.where(take, j, idx_scr[0, 0])
    best_scr[0, 0] = jnp.where(take, m, best_scr[0, 0])


def _emit(o_ref, idx_scr):
    # the (1, 1, 1) output tile takes a vector store: broadcast the
    # scalar-memory index instead of storing a scalar into VMEM
    o_ref[...] = jnp.full(o_ref.shape, idx_scr[0, 0], jnp.int32)


def _greedy_kernel(lg_ref, o_ref, best_scr, idx_scr, *, vocab, block_v,
                   n_blocks):
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        best_scr[0, 0] = -jnp.inf
        idx_scr[0, 0] = 0

    vals, cols = _masked_block(lg_ref, kb, vocab=vocab, block_v=block_v)
    _stream_argmax(vals, cols, best_scr, idx_scr, vocab=vocab)

    @pl.when(kb == n_blocks - 1)
    def _fin():
        _emit(o_ref, idx_scr)


def _gumbel_kernel(lg_ref, g_ref, o_ref, best_scr, idx_scr, *, vocab,
                   block_v, n_blocks, temperature):
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        best_scr[0, 0] = -jnp.inf
        idx_scr[0, 0] = 0

    vals, cols = _masked_block(lg_ref, kb, vocab=vocab, block_v=block_v)
    g = g_ref[0].astype(jnp.float32)
    score = jnp.where(cols < vocab, g + vals / temperature, -jnp.inf)
    _stream_argmax(score, cols, best_scr, idx_scr, vocab=vocab)

    @pl.when(kb == n_blocks - 1)
    def _fin():
        _emit(o_ref, idx_scr)


def _topk_gumbel_kernel(lg_ref, g_ref, o_ref, topk_scr, kth_scr, best_scr,
                        idx_scr, *, vocab, block_v, n_blocks, k,
                        temperature):
    ph, kb = pl.program_id(1), pl.program_id(2)
    vals, cols = _masked_block(lg_ref, kb, vocab=vocab, block_v=block_v)

    @pl.when((ph == 0) & (kb == 0))
    def _init_topk():
        topk_scr[...] = jnp.full_like(topk_scr, -jnp.inf)

    @pl.when(ph == 0)
    def _phase0():
        # merge this block into the k running maxima: k unrolled
        # max-extractions (first occurrence knocked out each round)
        # yield exactly lax.top_k's kth value, duplicates included
        cand = jnp.concatenate([topk_scr[...], vals], axis=1)
        ccols = jax.lax.broadcasted_iota(jnp.int32, cand.shape, 1)
        width = cand.shape[1]
        tops = []
        for _ in range(k):
            m = cand.max()
            first = jnp.where(cand == m, ccols, width).min()
            cand = jnp.where(ccols == first, -jnp.inf, cand)
            tops.append(m)
        # build the (1, k) row without a 1-D stack intermediate: a (k,)
        # vector has no VREG layout on TPU (jnp.stack of scalars lowers
        # through one), so concatenate (1, 1) tiles along lanes instead
        merged = jnp.concatenate([m.reshape(1, 1) for m in tops], axis=1)
        topk_scr[...] = jnp.pad(
            merged, ((0, 0), (0, topk_scr.shape[1] - k)),
            constant_values=-jnp.inf)
        kth_scr[0, 0] = tops[-1]

    @pl.when((ph == 1) & (kb == 0))
    def _init_argmax():
        best_scr[0, 0] = -jnp.inf
        idx_scr[0, 0] = 0

    @pl.when(ph == 1)
    def _phase1():
        keep = (vals >= kth_scr[0, 0]) & (cols < vocab)
        g = g_ref[0].astype(jnp.float32)
        score = jnp.where(keep, g + vals / temperature, -jnp.inf)
        _stream_argmax(score, cols, best_scr, idx_scr, vocab=vocab)

    @pl.when((ph == 1) & (kb == n_blocks - 1))
    def _fin():
        _emit(o_ref, idx_scr)


def _rows(x, vp):
    """(B, V) -> (B, 1, Vp): pad V to the block multiple and give each
    row its own unit sublane axis, so a (1, 1, block) tile's minor two
    dims are (full extent, lane multiple) — Mosaic refuses a (1, block)
    tile of a (B, Vp) array unless B == 1."""
    b, v = x.shape
    if v != vp:
        x = jnp.pad(x, ((0, 0), (0, vp - v)))
    return x.reshape(b, 1, vp)


def _vocab_spec(index_map):
    return pl.BlockSpec((1, 1, _BLOCK_V), index_map)


def _out_spec(index_map):
    return pl.BlockSpec((1, 1, 1), index_map)


def greedy_sample(logits, *, interpret):
    """Streaming per-row argmax; logits (B, V) -> (B,) int32, identical
    to ``jnp.argmax(logits, axis=-1)`` including first-occurrence
    ties."""
    from jax.experimental.pallas import tpu as pltpu
    b, v = logits.shape
    vp = -(-v // _BLOCK_V) * _BLOCK_V
    nv = vp // _BLOCK_V
    kernel = functools.partial(_greedy_kernel, vocab=v, block_v=_BLOCK_V,
                               n_blocks=nv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(b, nv),
        in_specs=[_vocab_spec(lambda bi, ki: (bi, 0, ki))],
        out_specs=_out_spec(lambda bi, ki: (bi, 0, 0)),
        scratch_shapes=[_smem_scalar(), _smem_scalar(jnp.int32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, 1), jnp.int32),
        name="greedy_sample",
        interpret=interpret,
    )(_rows(logits, vp))
    return out[:, 0, 0]


def gumbel_sample(logits, gumbel, *, temperature, top_k=0, interpret):
    """Fused temperature/top-k gumbel-max sampling; logits (B, V),
    gumbel (B, V) f32 noise drawn outside from the engine's per-row
    keys.  Token-identical to the jnp oracle
    (top-k mask → /temperature → categorical)."""
    from jax.experimental.pallas import tpu as pltpu
    b, v = logits.shape
    vp = -(-v // _BLOCK_V) * _BLOCK_V
    nv = vp // _BLOCK_V
    lg, g = _rows(logits, vp), _rows(gumbel, vp)
    if top_k <= 0:
        name = "gumbel_sample"
        kernel = functools.partial(
            _gumbel_kernel, vocab=v, block_v=_BLOCK_V, n_blocks=nv,
            temperature=float(temperature))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(b, nv),
            in_specs=[_vocab_spec(lambda bi, ki: (bi, 0, ki)),
                      _vocab_spec(lambda bi, ki: (bi, 0, ki))],
            out_specs=_out_spec(lambda bi, ki: (bi, 0, 0)),
            scratch_shapes=[_smem_scalar(), _smem_scalar(jnp.int32)],
        )
    else:
        kpad = -(-int(top_k) // 128) * 128        # lane-pad the top-k scratch
        name = "topk_gumbel_sample"
        kernel = functools.partial(
            _topk_gumbel_kernel, vocab=v, block_v=_BLOCK_V, n_blocks=nv,
            k=int(top_k), temperature=float(temperature))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(b, 2, nv),
            in_specs=[_vocab_spec(lambda bi, ph, ki: (bi, 0, ki)),
                      _vocab_spec(lambda bi, ph, ki: (bi, 0, ki))],
            out_specs=_out_spec(lambda bi, ph, ki: (bi, 0, 0)),
            scratch_shapes=[_scratch((1, kpad)), _smem_scalar(),
                            _smem_scalar(), _smem_scalar(jnp.int32)],
        )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, 1), jnp.int32),
        name=name,
        interpret=interpret,
    )(lg, g)
    return out[:, 0, 0]
