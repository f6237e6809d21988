"""Decoder-only transformer assembly.

Layers are grouped into *runs* of identical (mixer-kind, ffn-kind); each run
is parameter-stacked and executed with ``jax.lax.scan`` (optionally
rematerialized).  This covers every assigned decoder architecture:

  dense GQA stacks            -> one run of ("attn", "dense")
  DeepSeek-V3 (3 dense + MoE) -> runs ("attn","dense")x3, ("attn","moe")x58
  Mamba-2                     -> one run of ("ssm", "none")
  RecurrentGemma (2 rec:1 att)-> alternating short runs
  LLaVA backbone              -> dense run with image-embedding prefix
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import sharding
from repro.models import attention as attn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (apply_mlp, apply_norm, cross_entropy,
                                 dense_init, embed_init, init_mlp, init_norm)

MTP_WEIGHT = 0.3  # DeepSeek-V3 MTP loss weight


# ---------------------------------------------------------------------------
# run structure
# ---------------------------------------------------------------------------


def runs_of(cfg) -> List[Tuple[str, str, int]]:
    kinds = cfg.layer_kinds()
    ffns = list(cfg.ffn_kinds())
    if cfg.family == "ssm" or cfg.d_ff == 0:
        ffns = ["none"] * cfg.num_layers
    else:
        # recurrent/hybrid blocks still carry an MLP
        pass
    out: List[List[Any]] = []
    for k, f in zip(kinds, ffns):
        if out and out[-1][0] == k and out[-1][1] == f:
            out[-1][2] += 1
        else:
            out.append([k, f, 1])
    return [tuple(r) for r in out]


# ---------------------------------------------------------------------------
# single layer
# ---------------------------------------------------------------------------


def init_layer(key, cfg, kind: str, ffn: str):
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"ln1": init_norm(ks[0], cfg.d_model, cfg)}
    if kind in ("attn", "local_attn"):
        if cfg.mla is not None and kind == "attn":
            p["attn"] = mla_mod.init_mla(ks[1], cfg)
        else:
            p["attn"] = attn_mod.init_attention(ks[1], cfg)
    elif kind == "ssm":
        p["ssm"] = ssm_mod.init_ssm(ks[1], cfg)
    elif kind == "rglru":
        p["rglru"] = rglru_mod.init_rglru(ks[1], cfg)
    else:
        raise ValueError(kind)
    if ffn == "dense":
        p["ln2"] = init_norm(ks[2], cfg.d_model, cfg)
        p["mlp"] = init_mlp(ks[3], cfg)
    elif ffn == "moe":
        p["ln2"] = init_norm(ks[2], cfg.d_model, cfg)
        p["moe"] = moe_mod.init_moe(ks[3], cfg)
    return p


def _layer_window(cfg, kind: str) -> int:
    if kind == "local_attn":
        return cfg.rglru.local_window if cfg.rglru else cfg.sliding_window
    return cfg.sliding_window


def apply_layer(p, h, cfg, kind: str, ffn: str, *, positions, cache=None,
                pos=None, valid_len=None, state_slots=None,
                make_cache=False, cache_len=0):
    aux = jnp.zeros((), jnp.float32)
    x = apply_norm(p["ln1"], h, cfg)
    if kind in ("attn", "local_attn"):
        window = _layer_window(cfg, kind)
        if cfg.mla is not None and kind == "attn":
            y, c = mla_mod.apply_mla(p["attn"], x, cfg, positions=positions,
                                     cache=cache, pos=pos,
                                     valid_len=valid_len,
                                     make_cache=make_cache,
                                     cache_len=cache_len)
        else:
            y, c = attn_mod.apply_attention(
                p["attn"], x, cfg, positions=positions, window=window,
                cache=cache, pos=pos, valid_len=valid_len,
                make_cache=make_cache,
                cache_len=min(cache_len, window) if window else cache_len)
    elif kind == "ssm":
        y, c = ssm_mod.apply_ssm(p["ssm"], x, cfg, cache=cache,
                                 make_cache=make_cache, pos=pos,
                                 valid_len=valid_len,
                                 state_slots=state_slots)
    elif kind == "rglru":
        y, c = rglru_mod.apply_rglru(p["rglru"], x, cfg, cache=cache,
                                     make_cache=make_cache, pos=pos,
                                     valid_len=valid_len,
                                     state_slots=state_slots)
    else:
        raise ValueError(kind)
    h = h + y
    if ffn == "dense":
        h = h + apply_mlp(p["mlp"], apply_norm(p["ln2"], h, cfg), cfg)
    elif ffn == "moe":
        # decode/serve paths run dropless: the training-time capacity
        # drop makes a token's output depend on its step's batchmates
        # (and lets padded rows displace real tokens)
        y, aux_moe = moe_mod.apply_moe(p["moe"], apply_norm(p["ln2"], h, cfg),
                                       cfg,
                                       dropless=cache is not None
                                       or make_cache)
        h = h + y
        aux = aux + aux_moe
    return h, c, aux


def init_layer_cache(cfg, kind: str, batch: int, cache_len: int, dtype):
    if kind in ("attn", "local_attn"):
        window = _layer_window(cfg, kind)
        sc = min(cache_len, window) if window else cache_len
        if cfg.mla is not None and kind == "attn":
            a = cfg.mla
            return {"ckv": jnp.zeros((batch, sc, a.kv_lora_rank), dtype),
                    "krope": jnp.zeros((batch, sc, a.qk_rope_head_dim), dtype)}
        return {"k": jnp.zeros((batch, sc, cfg.num_kv_heads, cfg.head_dim),
                               dtype),
                "v": jnp.zeros((batch, sc, cfg.num_kv_heads, cfg.head_dim),
                               dtype)}
    if kind == "ssm":
        return ssm_mod.init_ssm_cache(cfg, batch, dtype)
    if kind == "rglru":
        return rglru_mod.init_rglru_cache(cfg, batch, dtype)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# runs: init / apply (scan over stacked layers)
# ---------------------------------------------------------------------------


def init_run(key, cfg, kind: str, ffn: str, n: int):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: init_layer(k, cfg, kind, ffn))(keys)


def apply_run(rp, h, cfg, kind: str, ffn: str, *, positions, cache=None,
              pos=None, valid_len=None, state_slots=None, make_cache=False,
              cache_len=0):
    """Scan h through a stacked run.  cache (if given) has leading L axis."""
    use_cache = cache is not None
    # every layer of a run shares its block tables: close over one copy
    # so what a kernel derives from them is loop-invariant
    tables = None
    if use_cache and "block_tables" in cache:
        tables = cache["block_tables"][0]
        cache = {k: v for k, v in cache.items() if k != "block_tables"}

    def body(carry, xs):
        if use_cache:
            lp, lc = xs
            if tables is not None:
                lc = dict(lc, block_tables=tables)
        else:
            lp, lc = xs, None
        hh, c, aux = apply_layer(lp, carry, cfg, kind, ffn,
                                 positions=positions, cache=lc, pos=pos,
                                 valid_len=valid_len,
                                 state_slots=state_slots,
                                 make_cache=make_cache,
                                 cache_len=cache_len)
        if c is None:
            c = jnp.zeros((), h.dtype)  # scan needs a concrete ys
        return hh, (c, aux)

    if cfg.remat:
        body = jax.checkpoint(body)
    xs = (rp, cache) if use_cache else rp
    h, (new_cache, auxs) = jax.lax.scan(body, h, xs)
    if not (use_cache or make_cache):
        new_cache = None
    return h, new_cache, auxs.sum()


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------


def init_params(key, cfg):
    runs = runs_of(cfg)
    ks = jax.random.split(key, len(runs) + 4)
    params: Dict[str, Any] = {
        "embed": {"embedding": embed_init(ks[0], (cfg.vocab_size, cfg.d_model),
                                          cfg.pdtype)},
        "final_norm": init_norm(ks[1], cfg.d_model, cfg),
        "layers": {},
    }
    for i, (kind, ffn, n) in enumerate(runs):
        params["layers"][f"run_{i}"] = init_run(ks[2 + i], cfg, kind, ffn, n)
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": dense_init(ks[-2], (cfg.d_model,
                                                      cfg.vocab_size),
                                             cfg.pdtype)}
    if cfg.mtp_depth:
        mk = jax.random.split(ks[-1], 2)
        params["mtp"] = {
            "proj": dense_init(mk[0], (2 * cfg.d_model, cfg.d_model),
                               cfg.pdtype),
            "layer": init_layer(mk[1], cfg, "attn", "dense"
                                if cfg.moe is None else "dense"),
        }
    return params


def _logits(params, h, cfg):
    dt = h.dtype
    if cfg.tie_embeddings:
        w = params["embed"]["embedding"].astype(dt)  # (V, D)
        return jnp.einsum("bsd,vd->bsv", h, w)
    return jnp.einsum("bsd,dv->bsv", h, params["lm_head"]["w"].astype(dt))


def embed_tokens(params, tokens, cfg):
    emb = params["embed"]["embedding"]
    return jnp.take(emb, tokens, axis=0).astype(cfg.cdtype)


@jax.named_scope("lm_head_loss")
def chunked_lm_ce(params, h, labels, cfg, *, mask_from: int = 0):
    """Cross-entropy over sequence chunks: the (B, C, V) logits chunk is
    the only vocab-sized activation alive (vs (B, S, V) in one shot).

    h: (B, S, D) final hidden states; position p predicts labels[p]
    (already shifted by the caller).  Returns mean nll over positions
    >= mask_from.
    """
    b, s, d = h.shape
    chunk = min(cfg.loss_chunk or s, s)
    if s % chunk:
        chunk = s  # fallback: ragged tail not worth the complexity
    n = s // chunk
    hc = h.reshape(b, n, chunk, d).swapaxes(0, 1)        # (n, B, C, D)
    lc = labels.reshape(b, n, chunk).swapaxes(0, 1)      # (n, B, C)

    def body(carry, xs):
        tot, cnt = carry
        hx, lx, idx = xs
        logits = _logits(params, hx, cfg).astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lx[..., None], axis=-1)[..., 0]
        pos = idx * chunk + jnp.arange(chunk)[None]
        m = jnp.broadcast_to((pos >= mask_from), lx.shape
                             ).astype(jnp.float32)
        return (tot + ((logz - ll) * m).sum(), cnt + m.sum()), None

    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hc, lc, jnp.arange(n)))
    return tot / jnp.maximum(cnt, 1.0)


def forward(params, batch, cfg, *, cache=None, pos=None, valid_len=None,
            state_slots=None, make_cache=False, cache_len=0,
            need_logits=True):
    """Returns (logits, new_cache, aux_loss).

    batch: {"tokens": (B,S)} (+ "image_embeds": (B,Si,D) for vlm).
    Decode mode: tokens (B,1) + cache + pos (scalar int32).
    """
    tokens = batch["tokens"]
    h = embed_tokens(params, tokens, cfg)
    n_img = 0
    if cfg.num_image_tokens and "image_embeds" in batch:
        img = batch["image_embeds"].astype(cfg.cdtype)
        n_img = img.shape[1]
        h = jnp.concatenate([img, h], axis=1)
    h = sharding.hint(h, ("pod", "data"), None, None)

    decode = cache is not None and tokens.shape[1] == 1 and n_img == 0
    if decode:
        positions = None
    else:
        positions = jnp.arange(h.shape[1])[None]

    runs = runs_of(cfg)
    new_cache: Optional[Dict[str, Any]] = (
        {} if (cache is not None or make_cache) else None)
    aux = jnp.zeros((), jnp.float32)
    for i, (kind, ffn, n) in enumerate(runs):
        rp = params["layers"][f"run_{i}"]
        rc = cache[f"run_{i}"] if cache is not None else None
        h, nc, a = apply_run(rp, h, cfg, kind, ffn, positions=positions,
                             cache=rc, pos=pos, valid_len=valid_len,
                             state_slots=state_slots,
                             make_cache=make_cache, cache_len=cache_len)
        if new_cache is not None:
            new_cache[f"run_{i}"] = nc
        aux = aux + a
    h = apply_norm(params["final_norm"], h, cfg)
    logits = _logits(params, h, cfg) if need_logits else None
    return logits, new_cache, aux, h


def init_paged_cache(cfg, num_blocks: int, block_size: int, batch: int,
                     blocks_per_seq: int, dtype=None,
                     num_state_slots: int = 0):
    """Paged per-layer decode state, by family:

      attn / local_attn  -> K/V block pools (num_blocks, block_size, ...)
                            + per-sequence block tables
      attn with MLA      -> *latent* block pools: compressed c_kv
                            (kv_lora_rank) + shared rotary key per token —
                            DeepSeek's cache-memory win survives paging
      ssm / rglru        -> fixed-size per-slot recurrent state pools
                            (num_state_slots, ...): conv window + SSD
                            state / LRU hidden.  Not block-paged — the
                            state is O(1) per sequence; a slot is a
                            sequence's whole decode state.

    Physical block 0 / state slot 0 is trash: inactive rows point there,
    so their (masked) writes land somewhere harmless.
    """
    dtype = dtype or cfg.cdtype
    nslots = num_state_slots or batch + 1
    out = {}
    for i, (kind, ffn, n) in enumerate(runs_of(cfg)):
        if kind in ("attn", "local_attn"):
            if cfg.mla is not None and kind == "attn":
                a = cfg.mla
                rc = {"ckv": jnp.zeros((n, num_blocks, block_size,
                                        a.kv_lora_rank), dtype),
                      "krope": jnp.zeros((n, num_blocks, block_size,
                                          a.qk_rope_head_dim), dtype)}
            else:
                rc = {"k": jnp.zeros((n, num_blocks, block_size,
                                      cfg.num_kv_heads, cfg.head_dim),
                                     dtype),
                      "v": jnp.zeros((n, num_blocks, block_size,
                                      cfg.num_kv_heads, cfg.head_dim),
                                     dtype)}
            # the canonical row-count-independent placeholder (see
            # _canonical_block_tables): real tables are broadcast in by
            # with_block_tables at the start of every call, and keeping
            # the resident leaf at (L, 0, 0) keeps every call's jit
            # signature independent of the previous call's row bucket
            rc["block_tables"] = jnp.zeros((n, 0, 0), jnp.int32)
        elif kind == "ssm":
            single = ssm_mod.init_ssm_cache(cfg, nslots, dtype)
            rc = jax.tree.map(
                lambda x: jnp.zeros((n,) + x.shape, x.dtype), single)
        elif kind == "rglru":
            single = rglru_mod.init_rglru_cache(cfg, nslots, dtype)
            rc = jax.tree.map(
                lambda x: jnp.zeros((n,) + x.shape, x.dtype), single)
        else:
            raise NotImplementedError(
                f"paged cache: layer kind {kind!r} has no paged form")
        out[f"run_{i}"] = rc
    return out


def with_block_tables(cache, block_tables):
    """Return ``cache`` with every block-pooled run's tables replaced by
    ``block_tables`` (B, NB) — broadcast over the stacked layer axis.
    Slot-state runs (ssm/rglru) carry no tables and pass through."""
    out = {}
    for run, rc in cache.items():
        if "block_tables" not in rc:
            out[run] = rc
            continue
        n = rc["block_tables"].shape[0]
        nc = {k: v for k, v in rc.items() if k != "block_tables"}
        nc["block_tables"] = jnp.broadcast_to(
            block_tables, (n,) + block_tables.shape)
        out[run] = nc
    return out


def _canonical_block_tables(cache):
    """Zero out the tables leaf to a row-count-independent (L, 0, 0)
    placeholder before the cache goes back to the engine.  Tables are
    replaced via ``with_block_tables`` at the start of every call, so
    between calls the leaf is purely structural — but if it kept this
    call's (L, rows, NB) shape, the NEXT call's jit signature would
    depend on THIS call's row bucket, and serving would compile one
    executable per (previous rows, current rows) pair: mid-serving XLA
    compiles, i.e. multi-second latency spikes the warmup can't cover."""
    out = {}
    for run, rc in cache.items():
        if "block_tables" not in rc:
            out[run] = rc
            continue
        nc = dict(rc)
        n = rc["block_tables"].shape[0]
        nc["block_tables"] = jnp.zeros((n, 0, 0), jnp.int32)
        out[run] = nc
    return out


def paged_step_logits(params, cache, tokens, pos, cfg):
    """Unfused step over a paged cache (the PR-1 engine's inner loop,
    kept as the measurable baseline): full (B, C, V) logits ship to host
    and the host samples.  tokens (B, C) int32; pos (B,) int32."""
    logits, new_cache, _, _ = forward(params, {"tokens": tokens}, cfg,
                                      cache=cache, pos=pos)
    return logits, new_cache


@jax.named_scope("paged_step")
def paged_step(params, cache, slot_buf, tokens, block_tables, meta, cfg,
               *, temperature: float = 0.0, top_k: int = 0, seed: int = 0):
    """Fused continuous-batching step over a paged cache: mixed
    prefill+decode rows, device-side sampling (greedy, or
    temperature/top-k keyed per row), and on-device last-token logit
    slicing.

    tokens: (B, C) int32 — decode rows use only column 0, prefill rows
    carry a prompt chunk; block_tables: (B, NB) int32 per-row block
    tables (broadcast across layers inside the jit — cheaper than the
    host materializing the broadcast every step); meta: (6, B) int32
    packed per-row control inputs (one host->device transfer instead of
    six):

      meta[0] = pos       absolute position of the row's first token
      meta[1] = valid_len number of real tokens in the row (0 disables
                          the row: every KV write goes to the trash
                          block and every recurrent-state write to the
                          trash slot, so a padded/stale row cannot
                          clobber live cache)
      meta[2] = src_slot  rows with src_slot >= 0 read their input
                          token from slot_buf[src_slot] instead of
                          tokens[:, 0]
      meta[3] = dst_slot  slot the sampled token is scattered to
                          (dst_slot < 0 routes to the spare slot S)
      meta[4] = state_slot per-row index into the fixed-size recurrent
                          state pools (ssm/rglru runs); 0 is the trash
                          slot.  Ignored by pure block-pool families.
      meta[5] = rid       request id, the per-row sampling identity:
                          stochastic draws are keyed
                          fold_in(fold_in(seed, rid), position) so the
                          same token is drawn at any dispatch depth and
                          across preemption recompute.  Ignored when
                          temperature <= 0.

    slot_buf: (S+1,) int32 device-resident last-sampled-token-per-slot
    ring — the device-side feedback path that lets the host dispatch
    step k+1 before fetching step k's tokens.  temperature/top_k/seed
    are Python statics (the engine bakes them into its jit wrapper), so
    the greedy executable carries no RNG.

    Returns (next_tokens (B,), slot_buf, cache).  Only the (B,) tokens
    ever ship to host — sampling consumed the frontier logits on
    device, and no logits output is materialized at all (a logprobs API
    would add a (B, k) top-logprobs output here, not the (B, V) block).
    """
    pos, valid_len, src_slot, dst_slot, state_slot, rid = meta
    cache = with_block_tables(cache, block_tables)
    wired = src_slot >= 0
    tok0 = jnp.where(wired, slot_buf[jnp.maximum(src_slot, 0)],
                     tokens[:, 0])
    tokens = tokens.at[:, 0].set(tok0.astype(tokens.dtype))
    _, new_cache, _, h = forward(params, {"tokens": tokens}, cfg,
                                 cache=cache, pos=pos, valid_len=valid_len,
                                 state_slots=state_slot, need_logits=False)
    # slice each row's frontier hidden state on device: logits are only
    # ever needed at the last real token (first generated token for a
    # prompt-completing prefill row, next token for a decode row)
    idx = jnp.maximum(valid_len - 1, 0)
    hf = jnp.take_along_axis(h, idx[:, None, None], axis=1)    # (B,1,D)
    logits = _logits(params, hf, cfg)[:, 0].astype(jnp.float32)
    toks = _sample_rows(logits, rid, pos + valid_len,
                        temperature=temperature, top_k=top_k, seed=seed,
                        impl=cfg.attn_impl)
    spare = slot_buf.shape[0] - 1
    dst = jnp.where(dst_slot >= 0, dst_slot, spare)
    slot_buf = slot_buf.at[dst].set(toks)
    return toks, slot_buf, _canonical_block_tables(new_cache)


@jax.named_scope("sample")
def _sample_rows(logits, rids, positions, *, temperature, top_k, seed,
                 impl="jnp"):
    """Sample one token per row on device.  The sampled token's key is a
    pure function of (seed, rid, absolute position), so the draw is
    identical whether it happens in a depth-1 fused step, inside the
    N-step decode loop, or while recomputing a preempted request.
    ``impl`` follows cfg.attn_impl — "pallas" runs the fused streaming
    sampler (token-identical to the jnp oracle)."""
    from repro.kernels import ops as kops
    from repro.kernels.ref import sample_keys
    keys = (sample_keys(seed, rids, positions)
            if temperature > 0.0 else None)
    return kops.sample_tokens(logits, keys, temperature=temperature,
                              top_k=top_k,
                              impl="pallas" if impl == "pallas" else "jnp")


def _paged_block_size(cache):
    """Tokens per physical block of the paged cache's block pools (K/V
    or MLA latent — they page identically), or 0 when no run is
    block-pooled (pure slot-state families)."""
    for rc in cache.values():
        if "block_tables" in rc:
            pool = rc["ckv"] if "ckv" in rc else rc["k"]
            return pool.shape[2]           # (L, nb, bs, ...)
    return 0


@jax.named_scope("gather_view")
def _gather_view(pool, bt):
    """(L, nb, bs, ...) pool + (B, NB) tables -> (B, NB*bs + 1, ...)
    per-row contiguous views with one trailing trash slot (index S) for
    inactive rows' writes — garbage there carries kpos = S, which every
    causal mask discards."""
    l, _, bs = pool.shape[:3]
    b, nbk = bt.shape
    v = pool[:, bt].reshape((l, b, nbk * bs) + pool.shape[3:])
    pad = jnp.zeros((l, b, 1) + pool.shape[3:], pool.dtype)
    return jnp.concatenate([v, pad], axis=2)


@jax.named_scope("scatter_view")
def _scatter_view(pool, bt, view):
    """Write the (trash-slot-stripped) views back through the tables.
    Real blocks belong to exactly one row, so the only duplicate scatter
    indices are trash placeholders (block 0) — garbage lands where
    garbage belongs."""
    l, _, bs = pool.shape[:3]
    b, nbk = bt.shape
    body = view[:, :, :-1].reshape((l, b, nbk, bs) + pool.shape[3:])
    return pool.at[:, bt].set(body)


def _loop_views(cache, block_tables, state_slot, pos0, cfg=None):
    """Rearrange the paged cache into the decode loop's per-row resident
    form: block pools gather into contiguous views (the pool gather and
    the table indirection are paid once per dispatch instead of once per
    token), slot-state pools gather each row's O(1) state.  ``pos0 == 0``
    rows read zero state (fresh/recomputed rows — decode rows never are,
    but the gather keeps the paged-path semantics).  With
    cfg.attn_impl == "pallas" the slot-state gather runs the fused
    kernel (vmapped over layers); block-pool views stay a jnp gather —
    they feed the Pallas attends and are already once-per-dispatch."""
    use_pallas = cfg is not None and cfg.attn_impl == "pallas"
    fresh = pos0 == 0
    views = {}
    for run, rc in cache.items():
        if "block_tables" in rc:
            if "ckv" in rc:
                views[run] = {
                    "ckv_view": _gather_view(rc["ckv"], block_tables),
                    "kr_view": _gather_view(rc["krope"], block_tables)}
            else:
                views[run] = {
                    "kview": _gather_view(rc["k"], block_tables),
                    "vview": _gather_view(rc["v"], block_tables)}
        else:
            vc = {}
            for name, leaf in rc.items():
                if use_pallas:
                    from repro.kernels import ops as kops
                    vc[f"{name}_view"] = jax.vmap(
                        lambda p: kops.slot_gather(p, state_slot, fresh)
                    )(leaf)
                else:
                    g = leaf[:, state_slot]        # (L, B, ...)
                    mask = fresh.reshape((1, -1) + (1,) * (g.ndim - 2))
                    vc[f"{name}_view"] = jnp.where(mask, 0, g)
            views[run] = vc
    return views


def _scatter_loop_views(cache, views, block_tables, state_slot, cfg=None):
    """Inverse of ``_loop_views``: commit the views back into the
    resident pools.  Slot-state rows all write their own slot (padding
    rows write trash slot 0), and stopped rows' views hold their state
    as of stopping (iterations after are identity updates), so an
    unconditional write-back is exact (valid_len=None in the kernel
    form)."""
    use_pallas = cfg is not None and cfg.attn_impl == "pallas"
    out = {}
    for run, rc in cache.items():
        vc = views[run]
        if "block_tables" in rc:
            if "ckv" in rc:
                out[run] = {
                    "ckv": _scatter_view(rc["ckv"], block_tables,
                                         vc["ckv_view"]),
                    "krope": _scatter_view(rc["krope"], block_tables,
                                           vc["kr_view"]),
                    "block_tables": rc["block_tables"]}
            else:
                out[run] = {
                    "k": _scatter_view(rc["k"], block_tables,
                                       vc["kview"]),
                    "v": _scatter_view(rc["v"], block_tables,
                                       vc["vview"]),
                    "block_tables": rc["block_tables"]}
        elif use_pallas:
            from repro.kernels import ops as kops
            out[run] = {
                name: jax.vmap(
                    lambda p, v: kops.slot_scatter(p, state_slot, None, v)
                )(rc[name], vc[f"{name}_view"].astype(rc[name].dtype))
                for name in rc}
        else:
            out[run] = {
                name: rc[name].at[:, state_slot].set(
                    vc[f"{name}_view"].astype(rc[name].dtype))
                for name in rc}
    return out


@jax.named_scope("paged_decode_loop")
def paged_decode_loop(params, cache, slot_buf, block_tables, meta, cfg,
                      *, num_steps: int, temperature: float = 0.0,
                      top_k: int = 0, seed: int = 0):
    """Run up to ``num_steps`` decode steps per row entirely on device:
    a ``lax.fori_loop`` around the fused step body that advances per-row
    positions, appends KV/latent/recurrent state, samples (greedy or
    temperature/top-k via per-row fold_in keys), and evaluates stop
    conditions on device — so the host pays ONE dispatch (and one
    tokens/meta/tables transfer) per N tokens instead of per token.

    Every row is a decode row (width 1) reading its input token from
    ``slot_buf`` — prefill chunks never enter the loop; the engine runs
    them through ``paged_step`` at dispatch boundaries.  meta (6, B)
    int32:

      meta[0] = pos0      absolute position of the row's first input
                          token (the row's queries run pos0 .. pos0+k)
      meta[1] = steps     loop-step budget for this row: the host's
                          pre-reserved headroom, min(max_new remaining,
                          block/slot capacity granted).  0 disables the
                          row entirely.
      meta[2] = slot      the row's device token slot: read its input
                          from slot_buf[slot] each iteration, write the
                          sample back to the same slot.
      meta[3] = state_slot recurrent-state slot (ssm/rglru runs)
      meta[4] = rid       sampling identity (see ``paged_step``)
      meta[5] = eos       stop token id, or -1 for none.  The eos token
                          itself is emitted, then the row goes inactive.

    Stop conditions, all evaluated on device each iteration:

      * step budget:   i >= steps  (max_new_tokens and host-side
                       capacity metering, incl. pure slot-state
                       families with no device tables);
      * eos:           last sampled token == eos;
      * capacity:      the next write position's block-table entry is
                       the trash block (the device-side ensure-capacity
                       predicate for block-pooled families — if the
                       host under-reserved, e.g. under pool starvation,
                       the row truncates instead of scattering KV into
                       the shared trash block and decoding garbage).

    The attend runs over per-row *resident views*: block pools (K/V or
    MLA latent) gather into contiguous (B, S+1, ...) views once at loop
    entry and scatter back once at exit, and ssm/rglru slot state is
    gathered per row the same way — so each iteration pays a direct
    positional write instead of the per-token pool gather/scatter
    (``_loop_views`` / ``_scatter_loop_views``; correctness rests on
    the engine invariant that a real block belongs to exactly one row).

    A stopped row flips to valid_len=0 for the remaining iterations:
    its KV/latent writes land in its view's trailing trash slot (masked
    by every causal mask, never scattered back), its recurrent-state
    update is the identity, and its token-slot writes go to the spare
    slot, so it cannot perturb live rows — stopping is monotonic, which
    is what lets the host read back a packed prefix per row.

    Returns (tokens (B, N) int32 — row r's generated tokens are the
    first counts[r] columns, counts (B,) int32, eos_hit (B,) bool,
    slot_buf, cache).  Only (B,N)+(B,)+(B,) ship to host — no logits at
    all in the steady state.
    """
    pos0, steps, slot, state_slot, rid, eos = meta
    b = pos0.shape[0]
    nb = block_tables.shape[1]
    block_size = _paged_block_size(cache)
    spare = slot_buf.shape[0] - 1
    # pools -> per-row resident views: the pool gather/scatter and the
    # block-table indirection are paid once per dispatch, not per token
    views = _loop_views(cache, block_tables, state_slot, pos0, cfg)

    def body(i, carry):
        views, slot_buf, out, counts, stopped = carry
        active = (i < steps) & ~stopped
        pos = pos0 + i
        if block_size:
            # device-side ensure-capacity predicate: this iteration
            # writes cache state at `pos`, which must land in a real
            # (reserved) block — the frontier entry of an
            # under-reserved table is still the trash placeholder
            lblk = pos // block_size
            entry = jnp.take_along_axis(
                block_tables, jnp.minimum(lblk, nb - 1)[:, None],
                axis=1)[:, 0]
            active &= (lblk < nb) & (entry != 0)
        valid = active.astype(jnp.int32)
        tokens = slot_buf[slot][:, None]                        # (B, 1)
        _, views, _, h = forward(params, {"tokens": tokens}, cfg,
                                 cache=views, pos=pos, valid_len=valid,
                                 state_slots=state_slot,
                                 need_logits=False)
        logits = _logits(params, h[:, :1], cfg)[:, 0].astype(jnp.float32)
        tok = _sample_rows(logits, rid, pos + 1, temperature=temperature,
                           top_k=top_k, seed=seed, impl=cfg.attn_impl)
        hit = active & (eos >= 0) & (tok == eos)
        out = out.at[:, i].set(jnp.where(active, tok, -1))
        # inactive rows dump their (garbage) sample into the spare slot
        slot_buf = slot_buf.at[jnp.where(active, slot, spare)].set(tok)
        return views, slot_buf, out, counts + valid, stopped | hit

    carry = (views, slot_buf,
             jnp.full((b, num_steps), -1, jnp.int32),
             jnp.zeros((b,), jnp.int32), jnp.zeros((b,), bool))
    views, slot_buf, out, counts, stopped = jax.lax.fori_loop(
        0, num_steps, body, carry)
    cache = _canonical_block_tables(
        _scatter_loop_views(cache, views, block_tables, state_slot, cfg))
    # `stopped` is only ever set by eos (budget/capacity stops come from
    # the predicate, not the carry), so it doubles as the eos flag
    return out, counts, stopped, slot_buf, cache


def init_cache(cfg, batch: int, cache_len: int, dtype=None):
    dtype = dtype or cfg.cdtype
    out = {}
    for i, (kind, ffn, n) in enumerate(runs_of(cfg)):
        single = init_layer_cache(cfg, kind, batch, cache_len, dtype)
        out[f"run_{i}"] = jax.tree.map(
            lambda x: jnp.zeros((n,) + x.shape, x.dtype), single)
    return out


# ---------------------------------------------------------------------------
# losses / steps
# ---------------------------------------------------------------------------


def lm_loss(params, batch, cfg):
    tokens = batch["tokens"]
    chunked = bool(cfg.loss_chunk)
    logits, _, aux, h = forward(params, batch, cfg,
                                need_logits=not chunked)
    n_img = 0
    if cfg.num_image_tokens and "image_embeds" in batch:
        n_img = batch["image_embeds"].shape[1]
    if chunked:
        # position p (of the combined sequence) predicts combined token
        # p+1; image positions (p+1 <= n_img-1) are masked out.
        if n_img:
            labels_full = jnp.concatenate(
                [jnp.zeros((tokens.shape[0], n_img), tokens.dtype),
                 tokens], axis=1)
        else:
            labels_full = tokens
        ce = chunked_lm_ce(params, h[:, :-1], labels_full[:, 1:], cfg,
                           mask_from=max(n_img - 1, 0))
    elif n_img:
        # only text targets (combined position >= n_img) contribute
        pred_logits = logits[:, n_img - 1:-1]
        ce = cross_entropy(pred_logits, tokens[:, :pred_logits.shape[1]])
    else:
        ce = cross_entropy(logits[:, :-1], tokens[:, 1:])
    loss = ce + aux
    metrics = {"ce": ce, "aux": aux}

    if cfg.mtp_depth and n_img == 0:
        # DeepSeek-V3 MTP: one extra block predicting token t+2 from
        # [h_t ; emb(token_{t+1})].
        emb_next = embed_tokens(params, tokens[:, 1:], cfg)
        h_in = jnp.concatenate([h[:, :-1], emb_next], axis=-1)
        h_mtp = jnp.einsum("bsd,dk->bsk", h_in,
                           params["mtp"]["proj"].astype(h.dtype))
        positions = jnp.arange(h_mtp.shape[1])[None]
        h_mtp, _, _ = apply_layer(params["mtp"]["layer"], h_mtp, cfg, "attn",
                                  "dense", positions=positions)
        mtp_logits = _logits(params, h_mtp, cfg)
        mtp_ce = cross_entropy(mtp_logits[:, :-1], tokens[:, 2:])
        loss = loss + MTP_WEIGHT * mtp_ce
        metrics["mtp_ce"] = mtp_ce
    metrics["loss"] = loss
    return loss, metrics


def prefill(params, batch, cfg, cache_len: int):
    logits, cache, aux, _ = forward(params, batch, cfg, make_cache=True,
                                    cache_len=cache_len)
    return logits, cache


def decode_step(params, cache, tokens, pos, cfg):
    """tokens (B,1) int32; pos scalar int32 (position of this token)."""
    logits, new_cache, _, _ = forward(params, {"tokens": tokens}, cfg,
                                      cache=cache, pos=pos)
    return logits[:, 0], new_cache
