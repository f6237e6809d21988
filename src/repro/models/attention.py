"""Attention: GQA/MQA with optional QKV bias, RoPE, sliding window, cross
attention, KV-cache decode, and a blocked (flash-style) jnp implementation
for long sequences.

The blocked implementation is the memory-sane path used by the big dry-run
configs; ``kernels/flash_attention`` is the Pallas TPU version of the same
loop (validated against the naive oracle here).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, dense_init

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def init_attention(key, cfg, *, d_model: Optional[int] = None,
                   num_heads: Optional[int] = None,
                   num_kv_heads: Optional[int] = None):
    d = d_model or cfg.d_model
    h = num_heads or cfg.num_heads
    kv = num_kv_heads or cfg.num_kv_heads
    hd = cfg.head_dim
    pd = cfg.pdtype
    ks = jax.random.split(key, 4)
    p = {"wq": dense_init(ks[0], (d, h * hd), pd),
         "wk": dense_init(ks[1], (d, kv * hd), pd),
         "wv": dense_init(ks[2], (d, kv * hd), pd),
         "wo": dense_init(ks[3], (h * hd, d), pd)}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), pd)
        p["bk"] = jnp.zeros((kv * hd,), pd)
        p["bv"] = jnp.zeros((kv * hd,), pd)
    return p


# ---------------------------------------------------------------------------
# core attention math (q already grouped to kv heads)
# ---------------------------------------------------------------------------


def _group(q, num_kv):
    """(B,S,H,hd) -> (B,S,KV,G,hd)"""
    b, s, h, hd = q.shape
    return q.reshape(b, s, num_kv, h // num_kv, hd)


def naive_attention(q, k, v, *, causal: bool, window: int = 0,
                    q_positions=None, k_positions=None, mask=None):
    """q: (B,Sq,KV,G,hd); k,v: (B,Sk,KV,hd).  Softmax in f32."""
    b, sq, kvh, g, hd = q.shape
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", q, k,
                        preferred_element_type=jnp.float32) * scale
    if q_positions is None:
        q_positions = jnp.arange(sq)
    if k_positions is None:
        k_positions = jnp.arange(sk)
    m = jnp.ones((sq, sk), bool)
    if causal:
        m &= k_positions[None, :] <= q_positions[:, None]
    if window:
        m &= k_positions[None, :] > q_positions[:, None] - window
    if mask is not None:
        m &= mask
    logits = jnp.where(m[None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(v.dtype), v)
    return out


def blocked_attention(q, k, v, *, causal: bool, window: int = 0,
                      block_q: int = 512, block_kv: int = 1024):
    """Flash-style online-softmax attention in pure jnp.

    Memory O(S * block) instead of O(S^2); with a sliding window the kv
    range per q block shrinks statically, so FLOPs are truly sub-quadratic.
    q: (B,Sq,KV,G,hd); k,v: (B,Sk,KV,hd).
    """
    b, sq, kvh, g, hd = q.shape
    sk = k.shape[1]
    block_q = min(block_q, sq)
    block_kv = min(block_kv, sk)
    if sq % block_q or sk % block_kv:
        return naive_attention(q, k, v, causal=causal, window=window)
    scale = 1.0 / math.sqrt(hd)
    n_q = sq // block_q
    outs = []
    for qb in range(n_q):
        q_lo = qb * block_q
        q_blk = jax.lax.dynamic_slice_in_dim(q, q_lo, block_q, axis=1)
        qpos = q_lo + jnp.arange(block_q)
        # static kv block range for this q block
        hi = sk if not causal else min(sk, q_lo + block_q)
        e_blk = -(-hi // block_kv)                      # ceil
        s_blk = 0
        if window:
            s_blk = max(0, (q_lo + 1 - window) // block_kv)
        n_kv = e_blk - s_blk

        def body(carry, i, q_blk=q_blk, qpos=qpos, s_blk=s_blk):
            acc, m_i, l_i = carry
            k_lo = (s_blk + i) * block_kv
            k_blk = jax.lax.dynamic_slice_in_dim(k, k_lo, block_kv, axis=1)
            v_blk = jax.lax.dynamic_slice_in_dim(v, k_lo, block_kv, axis=1)
            kpos = k_lo + jnp.arange(block_kv)
            logits = jnp.einsum("bqkgh,bskh->bkgqs", q_blk, k_blk,
                                preferred_element_type=jnp.float32) * scale
            msk = jnp.ones((block_q, block_kv), bool)
            if causal:
                msk &= kpos[None, :] <= qpos[:, None]
            if window:
                msk &= kpos[None, :] > qpos[:, None] - window
            logits = jnp.where(msk[None, None, None], logits, NEG_INF)
            m_new = jnp.maximum(m_i, logits.max(-1))
            alpha = jnp.exp(m_i - m_new)
            p = jnp.exp(logits - m_new[..., None])
            l_new = l_i * alpha + p.sum(-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskh->bkgqh", p.astype(v.dtype), v_blk
            ).astype(jnp.float32)
            return (acc, m_new, l_new), None

        acc0 = jnp.zeros((b, kvh, g, block_q, hd), jnp.float32)
        m0 = jnp.full((b, kvh, g, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, block_q), jnp.float32)
        (acc, m_i, l_i), _ = jax.lax.scan(
            body, (acc0, m0, l0), jnp.arange(n_kv))
        o = acc / jnp.maximum(l_i[..., None], 1e-30)
        outs.append(jnp.moveaxis(o, 3, 1).astype(q.dtype))  # (B,Bq,KV,G,hd)
    return jnp.concatenate(outs, axis=1)


def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0):
    """Single-token attention over a (possibly ring) KV cache.

    q: (B,1,KV,G,hd); caches: (B,Sc,KV,hd); pos: scalar int32 — position of
    the new token (cache already contains it at pos % Sc).
    Valid slots: arange(Sc) <= pos (full cache) — with a ring buffer every
    slot is valid once pos >= Sc, which the same predicate yields.
    """
    sc = k_cache.shape[1]
    hd = q.shape[-1]
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", q, k_cache,
                        preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(sc) <= pos
    logits = jnp.where(valid[None, None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(v_cache.dtype),
                      v_cache)


def paged_decode_attention(q, k_cache, v_cache, q_positions, *,
                           window: int = 0):
    """Attention over a gathered paged KV cache with per-sequence positions.

    q: (B,C,KV,G,hd) — C new tokens per sequence (C=1 decode, C>1 prefill
    chunk); k_cache,v_cache: (B,S,KV,hd) where slot j holds logical
    position j; q_positions: (B,C) absolute position of each query.
    Slots beyond a sequence's frontier hold garbage — masked off because
    their kpos exceeds every query position.
    """
    sk = k_cache.shape[1]
    hd = q.shape[-1]
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", q, k_cache,
                        preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(sk)
    m = kpos[None, None, :] <= q_positions[:, :, None]          # (B,C,S)
    if window:
        m &= kpos[None, None, :] > q_positions[:, :, None] - window
    logits = jnp.where(m[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(v_cache.dtype),
                      v_cache)


def paged_write_indices(positions, block_tables, block_size, valid_len):
    """(block, slot) scatter targets for writing per-token paged state.

    positions (B,C) absolute token positions; block_tables (B,NB);
    valid_len (B,) or None.  Logical block i of row b lives at physical
    block block_tables[b, i].  Two kinds of padding must land in the
    trash block (physical 0), NEVER clamped onto a real block (that
    would clobber live cache a later query still attends to):

      * tail positions of a fixed-shape chunk that run past the block
        table;
      * columns >= the row's valid_len (a decode row in a fused mixed
        prefill+decode call carries C-1 padding columns whose positions
        land INSIDE the sequence's own table — without the per-row
        valid-length mask they'd overwrite live state).

    Shared by the K/V paged path and the MLA latent paged path — the
    trash-block invariant is regression-tested once and holds for both.
    """
    c = positions.shape[1]
    lblk = positions // block_size
    writable = lblk < block_tables.shape[1]
    if valid_len is not None:
        writable &= jnp.arange(c)[None] < valid_len[:, None]
    blk = jnp.take_along_axis(
        block_tables, jnp.minimum(lblk, block_tables.shape[1] - 1),
        axis=1)                                                 # (B,C)
    blk = jnp.where(writable, blk, 0)
    return blk, positions % block_size


def make_cross_cache(params, kv_x, cfg, num_kv_heads=None):
    """Precompute cross-attention k/v from encoder output (no rope)."""
    kv = num_kv_heads or cfg.num_kv_heads
    hd = cfg.head_dim
    dt = kv_x.dtype
    k = jnp.einsum("bsd,dk->bsk", kv_x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dk->bsk", kv_x, params["wv"].astype(dt))
    if "bk" in params:
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    b, s = kv_x.shape[:2]
    return {"k": k.reshape(b, s, kv, hd), "v": v.reshape(b, s, kv, hd)}


# ---------------------------------------------------------------------------
# full layer application
# ---------------------------------------------------------------------------


def _qkv(params, x, kv_x, cfg, num_heads, num_kv):
    hd = cfg.head_dim
    dt = x.dtype
    q = jnp.einsum("bsd,dk->bsk", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dk->bsk", kv_x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dk->bsk", kv_x, params["wv"].astype(dt))
    if "bq" in params:
        q = q + params["bq"].astype(dt)
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    b, s = x.shape[:2]
    sk = kv_x.shape[1]
    q = q.reshape(b, s, num_heads, hd)
    k = k.reshape(b, sk, num_kv, hd)
    v = v.reshape(b, sk, num_kv, hd)
    return q, k, v


def apply_attention(params, x, cfg, *, positions=None, causal=True,
                    window=0, use_rope=True, cache=None, pos=None,
                    valid_len=None, kv_x=None, cross=False, num_heads=None,
                    num_kv_heads=None, make_cache=False, cache_len=0):
    """Returns (y, new_cache).

    Full-sequence mode (cache is None, x: (B,S,D)):
      computes attention over x (self) or kv_x (cross); if make_cache,
      also returns a cache buffer of length cache_len with k/v written.
    Decode mode (cache provided, x: (B,1,D)):
      writes this token's k/v at pos % Sc (ring for sliding window) and
      attends over the cache.  For cross attention pass a cache with
      precomputed k/v and pos=None (no write).
    """
    h = num_heads or cfg.num_heads
    kv = num_kv_heads or cfg.num_kv_heads
    cross = cross or (kv_x is not None)
    b = x.shape[0]
    dt = x.dtype

    if cache is None:
        src = kv_x if cross else x
        q, k, v = _qkv(params, x, src, cfg, h, kv)
        if positions is None:
            positions = jnp.arange(x.shape[1])[None]
        if use_rope and not cross:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        qg = _group(q, kv)
        with jax.named_scope("attend"):
            if cfg.attn_impl == "pallas" and not cross:
                # Pallas flash kernel (TPU target; interpret mode on
                # CPU) — keeps the score tiles in VMEM (EXPERIMENTS.md
                # §Perf A2)
                from repro.kernels import ops as kops
                o = kops.flash_attention(q, k, v, causal=causal,
                                         window=window)
                o = _group(o, kv)
            elif cfg.attn_impl == "blocked" and not cross:
                o = blocked_attention(qg, k, v, causal=causal, window=window,
                                      block_q=cfg.attn_block_q,
                                      block_kv=cfg.attn_block_kv)
            else:
                o = naive_attention(qg, k, v, causal=causal and not cross,
                                    window=window)
        y = o.reshape(b, x.shape[1], h * cfg.head_dim)
        y = jnp.einsum("bsk,kd->bsd", y, params["wo"].astype(dt))
        new_cache = None
        if make_cache:
            sc = cache_len or x.shape[1]
            sc = min(sc, window) if window else sc
            kc = jnp.zeros((b, sc, kv, cfg.head_dim), dt)
            vc = jnp.zeros((b, sc, kv, cfg.head_dim), dt)
            s = k.shape[1]
            if s >= sc:
                # ring invariant: position p lives at slot p % sc
                shift = s % sc
                kc = jnp.roll(k[:, -sc:], shift, axis=1)
                vc = jnp.roll(v[:, -sc:], shift, axis=1)
            else:
                kc = kc.at[:, :s].set(k)
                vc = vc.at[:, :s].set(v)
            new_cache = {"k": kc, "v": vc}
        return y, new_cache

    # ---- N-step decode loop: per-row contiguous K/V views ----
    if "kview" in cache:
        # The decode loop gathers each row's blocks into a contiguous
        # (B, S+1, KV, hd) view once per dispatch (slot j = logical
        # position j; slot S is the trash row inactive rows write to)
        # and scatters back once after N steps — so each iteration here
        # is a direct per-row write plus the same masked attend,
        # without the per-token pool gather/scatter.
        kc, vc = cache["kview"], cache["vview"]
        sview = kc.shape[1] - 1
        q, k, v = _qkv(params, x, x, cfg, h, kv)
        positions = pos[:, None]                                # (B,1)
        if use_rope:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        rows = jnp.arange(b)
        wpos = jnp.where(valid_len > 0 if valid_len is not None else True,
                         jnp.minimum(pos, sview - 1), sview)
        kc = kc.at[rows, wpos].set(k[:, 0].astype(kc.dtype))
        vc = vc.at[rows, wpos].set(v[:, 0].astype(vc.dtype))
        with jax.named_scope("attend"):
            if cfg.attn_impl == "pallas":
                # view-resident decode attend: the kernel indexes the
                # contiguous view directly inside the fori_loop (per-row
                # positions via scalar prefetch) — no jnp gather/softmax
                # materialization per iteration
                from repro.kernels import ops as kops
                o = kops.decode_view_attend(q[:, 0], kc, vc, pos,
                                            window=window)[:, None]
            else:
                o = paged_decode_attention(_group(q, kv), kc, vc, positions,
                                           window=window)
        y = o.reshape(b, 1, h * cfg.head_dim)
        y = jnp.einsum("bsk,kd->bsd", y, params["wo"].astype(dt))
        return y, {"kview": kc, "vview": vc}

    # ---- paged decode / chunked prefill ----
    if "block_tables" in cache:
        # cache: k/v block pools (nb, bs, KV, hd) + block_tables (B, NB);
        # pos (B,) is the absolute position of the first new token.  x may
        # carry C >= 1 tokens — the same code path serves batched decode
        # (C=1) and budgeted prefill chunks (C=chunk).
        kpool, vpool, bt = cache["k"], cache["v"], cache["block_tables"]
        bs_blk = kpool.shape[1]
        c = x.shape[1]
        q, k, v = _qkv(params, x, x, cfg, h, kv)
        positions = pos[:, None] + jnp.arange(c)[None]          # (B,C)
        if use_rope:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        # scatter the C new k/v rows into each sequence's blocks; padding
        # (past the table or past valid_len) routes to the trash block —
        # see paged_write_indices
        blk, slot = paged_write_indices(positions, bt, bs_blk, valid_len)
        kpool = kpool.at[blk, slot].set(k.astype(kpool.dtype))
        vpool = vpool.at[blk, slot].set(v.astype(vpool.dtype))
        with jax.named_scope("attend"):
            if cfg.attn_impl == "pallas":
                from repro.kernels import ops as kops
                o = kops.flash_decode_paged(q, kpool, vpool, bt, pos,
                                            window=window)
                o = o.reshape(b, c, kv, h // kv, cfg.head_dim)
            else:
                nb_seq = bt.shape[1]
                kc = kpool[bt].reshape(b, nb_seq * bs_blk, kv, cfg.head_dim)
                vc = vpool[bt].reshape(b, nb_seq * bs_blk, kv, cfg.head_dim)
                o = paged_decode_attention(_group(q, kv), kc, vc, positions,
                                           window=window)
        y = o.reshape(b, c, h * cfg.head_dim)
        y = jnp.einsum("bsk,kd->bsd", y, params["wo"].astype(dt))
        return y, {"k": kpool, "v": vpool, "block_tables": bt}

    # ---- decode ----
    kc, vc = cache["k"], cache["v"]
    sc = kc.shape[1]
    if cross:
        q = jnp.einsum("bsd,dk->bsk", x, params["wq"].astype(dt))
        if "bq" in params:
            q = q + params["bq"].astype(dt)
        q = q.reshape(b, 1, h, cfg.head_dim)
        with jax.named_scope("attend"):
            o = naive_attention(_group(q, kv), kc, vc, causal=False)
        y = o.reshape(b, 1, h * cfg.head_dim)
        y = jnp.einsum("bsk,kd->bsd", y, params["wo"].astype(dt))
        return y, cache
    q, k, v = _qkv(params, x, x, cfg, h, kv)
    if use_rope:
        ppos = pos[None, None] if jnp.ndim(pos) == 0 else pos[:, None]
        q = apply_rope(q, ppos, cfg.rope_theta)
        k = apply_rope(k, ppos, cfg.rope_theta)
    slot = pos % sc
    kc = kc.at[:, slot].set(k[:, 0].astype(kc.dtype))
    vc = vc.at[:, slot].set(v[:, 0].astype(vc.dtype))
    with jax.named_scope("attend"):
        o = decode_attention(_group(q, kv), kc, vc, pos, window=window)
    y = o.reshape(b, 1, h * cfg.head_dim)
    y = jnp.einsum("bsk,kd->bsd", y, params["wo"].astype(dt))
    return y, {"k": kc, "v": vc}
