"""Pallas TPU kernels: decode attention over a KV cache.

``flash_decode_bhd`` (contiguous cache): one query vector per (batch,
head) attends over the cache in block_kv chunks streamed HBM->VMEM;
online softmax in VMEM scratch.  Grid (B, nk) with nk innermost.  GQA
folds the head group into the leading axis of the logits tile ((KV, G,
bk) batched dot on the MXU).

``flash_decode_paged_bhd`` (the serving engine's paged pools): the
call's (B, C) query tokens are read in row order and cut into query
runs, maximal stretches whose rows share a block table and whose
positions go up by one (``query_tiles``).  A decode row is a run of
one; a prompt chunk, whether one chunk-wide row or one width-1 row per
token, is one run.  The grid walks blocks of about 128 tokens (one
chunk-wide row if wider); runs are cut at the blocks' edges and into
tiles of at most 128 tokens.  Each tile streams only its live pages,
from the page of its first query's window (0 without a window) to the
page of its last query, ``pages`` at a time with double-buffered async
copies that run ahead across tile and block boundaries.  So the work
follows tiles x live context, not rows x table length, the tokens of a
run read each page once per tile instead of once per row, and VMEM
follows the block, not the call.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

NEG_INF = -1e30
QUERY_TILE = 128    # most query tokens in one tile of a run
SMALL_TILE = 8      # tiles this short (decode runs) take the narrow form
KV_CHUNK = 128      # kv positions (whole pages) fetched per inner step


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


class QueryTiles(NamedTuple):
    """The tiles of a paged attention call (``query_tiles``).  Per tile:
    ``start`` (first token, row-major over (B, C)), ``length`` (tokens),
    ``lo``/``hi`` (first and last logical page it visits); per grid
    block ``block_first``, the index of its first tile, then the tile
    count.  jnp results are padded to B*C tiles, the padding with
    length 0."""
    start: object
    length: object
    lo: object
    hi: object
    block_first: object
    n_tiles: object     # tiles
    n_runs: object      # query runs
    n_pages: object     # pages the tiles visit, summed


def tile_shape(rows: int, chunk: int) -> tuple:
    """(rows of a grid block, most query tokens of a tile) for a call of
    ``rows`` rows of ``chunk`` tokens.  A block holds about QUERY_TILE
    tokens (one chunk-wide row if wider), so VMEM follows the block and
    not the call; a tile fits in its block, in multiples of 8 tokens
    where the block has 8."""
    block_rows = min(rows, max(1, QUERY_TILE // chunk))
    nt = block_rows * chunk
    return block_rows, min(QUERY_TILE, nt if nt < 8 else nt // 8 * 8)


def query_tiles(block_tables, pos, chunk: int, *, block_size: int,
                window: int = 0, xp=np) -> QueryTiles:
    """Query runs and their tiles for a call with block tables (B, NB),
    first positions ``pos`` (B,) and ``chunk`` query tokens a row.

    Token (b, j) sits at position pos[b] + j.  A run starts at token
    (b, 0) unless row b - 1 has the same table and pos[b - 1] + chunk
    == pos[b].  Runs are cut at the grid blocks' edges and then into
    tiles of at most ``tile_shape(B, chunk)[1]`` tokens.  A tile visits
    pages ``hi = last position // block_size`` (at most NB - 1) down to
    ``lo``: 0, or with a window the page of the first query's oldest
    visible position.  Whatever the rows' identity, a token's output
    depends only on (query, table, position), so a run is exact.
    ``xp`` is ``numpy`` (the engine's counters, on the host) or
    ``jax.numpy`` (the kernel's metadata, traced; static shapes)."""
    b, nb = block_tables.shape
    n = b * chunk
    block_rows, tile = tile_shape(b, chunk)
    nt = block_rows * chunk
    bt = xp.asarray(block_tables).astype(xp.int32)
    pos = xp.asarray(pos).astype(xp.int32).reshape(b)
    cont = (xp.all(bt[1:] == bt[:-1], axis=1)
            & (pos[1:] == pos[:-1] + chunk))
    row_start = xp.concatenate([xp.ones((1,), bool), ~cont])
    run_start = xp.concatenate(
        [row_start[:, None], xp.zeros((b, chunk - 1), bool)],
        axis=1).reshape(n)
    t = xp.arange(n, dtype=xp.int32)
    marks = xp.where(run_start | (t % nt == 0), t, 0)
    first = (np.maximum.accumulate(marks) if xp is np
             else jax.lax.cummax(marks, axis=0))
    tile_start = (t - first) % tile == 0
    if xp is np:
        start = np.flatnonzero(tile_start).astype(np.int32)
    else:
        start = jnp.nonzero(tile_start, size=n, fill_value=n)[0]
        start = start.astype(jnp.int32)
    length = xp.concatenate([start[1:], xp.full((1,), n, xp.int32)]) - start
    qpos = (pos[:, None] + xp.arange(chunk, dtype=xp.int32)[None]).reshape(n)
    p0 = qpos[xp.minimum(start, n - 1)]
    hi = xp.clip((p0 + length - 1) // block_size, 0, nb - 1)
    lo = (xp.clip((p0 - window + 1) // block_size, 0, hi) if window
          else xp.zeros_like(hi))
    pages = xp.where(length > 0, hi - lo + 1, 0)
    # every block starts a tile: its first tile is its first token's
    n_tiles = tile_start.sum()
    idx = xp.cumsum(tile_start.astype(xp.int32)) - 1
    block_first = xp.concatenate(
        [idx[::nt], xp.reshape(n_tiles, (1,)).astype(xp.int32)])
    return QueryTiles(start, length, lo, hi, block_first, n_tiles,
                      run_start.sum(), pages.sum())


def _paged_kernel(bt_ref, pos_ref, tiles_ref, first_ref,
                  q_ref, k_hbm, v_hbm, o_ref,
                  o_scr, k_buf, v_buf, m_scr, l_scr, acc_scr, sems, step_ref,
                  *, scale, block_size, pages, kv_heads, group, chunk, window,
                  tile, small):
    """The tiles of one grid block, in token order.

    tiles_ref (4, T): per tile first token, length, first and last
    logical page; first_ref: each block's first tile, then the tile
    count.  q_ref is the block's (nt, H, hd) queries; each tile reads
    ``tile`` (or, when short, ``small``) query rows from its first token
    (from ``nt - rows`` at the block's end, its earlier rows left out of
    the store) and stores them into ``o_scr``, so a tile's rows past its
    length are rewritten by the tiles after it.  K/V pages arrive
    ``pages`` at a time in two slots; the copy of the next step (the
    next chunk, or the next tile's first, in this block or the next) is
    started before the current one is waited on, and ``step_ref``
    carries the slot parity from block to block.  Masking is causal per
    query position (and by window), and every kv position past the
    tile's last page is masked: the slots' stale pages are never read."""
    from jax.experimental.pallas import tpu as pltpu
    h, hd = q_ref.shape[1], q_ref.shape[2]
    nt = o_scr.shape[0]
    bs = block_size
    g = pl.program_id(0)
    base = g * nt
    n_tiles = first_ref[pl.num_programs(0)]

    def fetch(i, c, slot, start):
        """Start (or wait for) the copies of tile i's chunk c: its live
        pages, at most ``pages``, into ``slot``."""
        t0, lo, hi = tiles_ref[0, i], tiles_ref[2, i], tiles_ref[3, i]
        row, first = lax.div(t0, chunk), lo + c * pages

        def page(p, carry):
            blk = bt_ref[row, first + p]
            for j, (src, dst) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                cp = pltpu.make_async_copy(src.at[blk], dst.at[slot, p],
                                           sems.at[slot, j])
                if start:
                    cp.start()
                else:
                    cp.wait()
            return carry

        lax.fori_loop(0, lax.min(hi - first + 1, pages), page, 0)

    @pl.when(g == 0)
    def _first_block():
        # slots start at zero: a page past a tile's last stays finite
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        step_ref[0] = 0
        fetch(0, 0, 0, True)

    def kv_of(page):
        """(pages, bs[, KV], hd) slot -> (KV, nk, hd)."""
        if kv_heads == 1:
            return page.reshape(1, pages * bs, hd)
        return page.reshape(pages * bs, kv_heads, hd).swapaxes(0, 1)

    def run_tile(i, g, mq):
        t0, lo, hi = tiles_ref[0, i], tiles_ref[2, i], tiles_ref[3, i]
        row = lax.div(t0, chunk)
        p0 = pos_ref[row] + t0 - row * chunk
        # the mq query rows from s; the tile's own start d rows in
        s = lax.min(t0 - base, nt - mq)
        d = t0 - base - s
        n_chunks = lax.div(hi - lo, pages) + 1
        rows = mq * h
        m_ref, l_ref = m_scr.at[pl.ds(0, rows)], l_scr.at[pl.ds(0, rows)]
        acc_ref = acc_scr.at[pl.ds(0, rows)]
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # (mq,H,hd) -> (KV, mq*G, hd): the tile's tokens fold into each
        # kv head's query group, one batched dot on the MXU
        q = (q_ref[pl.ds(s, mq)].astype(jnp.float32)
             .reshape(mq, kv_heads, group, hd)
             .swapaxes(0, 1)
             .reshape(kv_heads, mq * group, hd))

        def step(c, g):
            slot = lax.rem(g, 2)
            last = c + 1 == n_chunks

            @pl.when(~last)
            def _():
                fetch(i, c + 1, 1 - slot, True)

            @pl.when(last & (i + 1 < n_tiles))
            def _():
                fetch(i + 1, 0, 1 - slot, True)

            fetch(i, c, slot, False)
            nk = pages * bs
            kt, vt = (kv_of(buf[slot].astype(jnp.float32))
                      for buf in (k_buf, v_buf))           # (KV, nk, hd)
            logits = jax.lax.dot_general(
                q, kt, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * scale  # (KV,mq*G,nk)
            kpos = ((lo + c * pages) * bs
                    + lax.broadcasted_iota(jnp.int32, logits.shape, 2))
            qpos = p0 - d + lax.div(
                lax.broadcasted_iota(jnp.int32, logits.shape, 1), group)
            valid = (kpos <= qpos) & (kpos < (hi + 1) * bs)
            if window:
                valid &= kpos > qpos - window
            logits = jnp.where(valid, logits, NEG_INF).reshape(rows, nk)

            # m/l are lane-padded (rows, 128) with every lane equal (see
            # decode_view): per-row consumers slice lane 0
            m_prev, l_prev = m_ref[...], l_ref[...]
            m_new = jnp.maximum(m_prev, logits.max(-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(logits - m_new[:, :1])
            l_ref[...] = l_prev * alpha + p.sum(-1, keepdims=True)
            pv = jax.lax.dot_general(
                p.reshape(kv_heads, mq * group, nk), vt,
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)        # (KV, mq*G, hd)
            acc_ref[...] = acc_ref[...] * alpha[:, :1] + pv.reshape(rows, hd)
            m_ref[...] = m_new
            return g + 1

        g = lax.fori_loop(0, n_chunks, step, g)
        o = (acc_ref[...] / jnp.maximum(l_ref[...][:, :1], 1e-30)
             ).reshape(kv_heads, mq, group, hd).swapaxes(0, 1).reshape(
                 mq, h, hd)
        before = lax.broadcasted_iota(jnp.int32, o.shape, 0) < d
        o_scr[pl.ds(s, mq)] = jnp.where(before, o_scr[pl.ds(s, mq)], o)
        return g

    def tile_body(i, g):
        if small >= tile:
            return run_tile(i, g, tile)
        return lax.cond(tiles_ref[1, i] <= small,
                        lambda g: run_tile(i, g, small),
                        lambda g: run_tile(i, g, tile), g)

    step_ref[0] = lax.fori_loop(first_ref[g], first_ref[g + 1], tile_body,
                                step_ref[0])
    o_ref[...] = o_scr[...].reshape(o_ref.shape).astype(o_ref.dtype)


def paged_vmem_bytes(tile: int, nt: int, heads: int, head_dim: int,
                     kv_heads: int, pages: int, block_size: int,
                     item: int, kv_item: int) -> int:
    """The paged kernel's VMEM for tiles of ``tile`` query tokens in
    blocks of ``nt``: a bound with room, not a count."""
    return (4 * nt * heads * head_dim * item          # q, out blocks x2
            + nt * heads * head_dim * 4                # o_scr
            + 4 * pages * block_size * kv_heads * head_dim * kv_item * 8
            + 3 * tile * heads * max(head_dim, 128) * 4    # m, l, acc
            + 8 * tile * heads * pages * block_size * 4)   # logits temps


VMEM_CAP = 100 << 20    # of the v5e's 128 MiB
SMEM_CAP = 1 << 20      # the v5e's scalar memory, which holds the tables


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def flash_decode_paged_bhd(q, k_pool, v_pool, block_tables, pos, *,
                           window=0, interpret):
    """Paged decode/prefill-chunk attention (the repro.serve hot loop).

    q (B,C,H,hd) — C query tokens per row (C=1 batched decode or one
    width-1 row per prompt token, C>1 a prefill chunk); k_pool,v_pool
    (nb, bs, KV, hd) — shared physical block pools, already containing
    this call's new tokens; block_tables (B, NB) int32 maps each row's
    logical block i to a physical block; pos (B,) int32 absolute
    position of each row's first query.  hd % 128 == 0.  Returns
    (B,C,H,hd).

    The block tables, positions and the tiles of ``query_tiles`` ride in
    scalar prefetch (tables first: the device trace finds the kernel by
    that operand); the pools stay in HBM and only live pages are
    copied.  The grid walks blocks of ``tile_shape`` rows, so VMEM
    follows the block, the heads and the tile, not the row count.
    """
    from jax.experimental.pallas import tpu as pltpu
    b, c, h, hd = q.shape
    bs, kvh = k_pool.shape[1], k_pool.shape[2]
    group = h // kvh
    pages = max(1, min(KV_CHUNK // bs, block_tables.shape[1]))
    block_rows, tile = tile_shape(b, c)
    nt = block_rows * c
    vmem = paged_vmem_bytes(tile, nt, h, hd, kvh, pages, bs,
                            jnp.dtype(q.dtype).itemsize,
                            jnp.dtype(k_pool.dtype).itemsize)
    if vmem > VMEM_CAP:
        raise ValueError(
            f"flash_decode_paged: {tile}-token tiles of {h} heads of {hd} "
            f"need about {vmem >> 20} MiB of VMEM, over the "
            f"{VMEM_CAP >> 20} MiB it may take")
    # one kv head: (bs, 1, hd) pages tile badly in Mosaic's DMA; XLA
    # keeps the unit axis outside the minor two, so dropping it is free
    page = (bs, kvh, hd) if kvh > 1 else (bs, hd)
    k_pool, v_pool = (x.reshape(x.shape[0], *page) for x in (k_pool, v_pool))
    nb = block_tables.shape[1]
    smem = 4 * (b * _lanes(nb) + 8 * _lanes(b * c) + 2 * _lanes(b))
    if smem > SMEM_CAP:
        raise ValueError(
            f"flash_decode_paged: block tables of {b} rows of {nb} blocks "
            f"need about {smem >> 10} KiB of scalar memory, over the "
            f"{SMEM_CAP >> 10} KiB there is")
    block_tables = jnp.asarray(block_tables, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32).reshape(b)
    qt = query_tiles(block_tables, pos, c, block_size=bs, window=window,
                     xp=jnp)
    tiles = jnp.stack([qt.start, qt.length, qt.lo, qt.hi])

    kernel = functools.partial(
        _paged_kernel, scale=1.0 / math.sqrt(hd), block_size=bs,
        pages=pages, kv_heads=kvh, group=group, chunk=c, window=window,
        tile=tile, small=min(SMALL_TILE, tile))
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(pl.cdiv(b, block_rows),),
        in_specs=[pl.BlockSpec((nt, h, hd), lambda i, *_: (i, 0, 0)),
                  any_, any_],
        out_specs=pl.BlockSpec((block_rows, c, h, hd),
                               lambda i, *_: (i, 0, 0, 0)),
        scratch_shapes=[
            _scratch((nt, h, hd)),
            pltpu.VMEM((2, pages, *page), k_pool.dtype),
            pltpu.VMEM((2, pages, *page), v_pool.dtype),
            _scratch((tile * h, 128)), _scratch((tile * h, 128)),
            _scratch((tile * h, hd)),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, c, h, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=int(min(max(32 << 20, 2 * vmem), VMEM_CAP))),
        name="flash_decode_paged",
        interpret=interpret,
    )(block_tables, pos, tiles, qt.block_first,
      q.reshape(b * c, h, hd), k_pool, v_pool)


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
