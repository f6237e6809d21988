"""Mixture-of-Experts FFN: top-k router + capacity-bounded scatter dispatch,
optional DeepSeek-style shared experts, load-balance auxiliary loss.

Dispatch is sort-free-scatter based (no (T,E,C) one-hot einsum — that tensor
is astronomically large at 32k sequence lengths).  Tokens are ranked within
their expert via a sort + segment-rank, scattered into an (E, C, D) buffer
(expert-parallel over the "data" mesh axis — this is the all-to-all), run
through batched expert matmuls on the MXU, and gathered back weighted by the
router gate.  Overflow beyond capacity C = ceil(T*K*cf/E) is dropped
(standard Switch/GShard semantics).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro import sharding
from repro.models.layers import dense_init, init_mlp, apply_mlp


def init_moe(key, cfg):
    m = cfg.moe
    d, fe = cfg.d_model, m.d_ff_expert
    pd = cfg.pdtype
    ks = jax.random.split(key, 5)
    p = {"router": {"w": dense_init(ks[0], (d, m.num_experts), pd, scale=0.02)},
         "experts": {
             "w_gate": dense_init(ks[1], (m.num_experts, d, fe), pd),
             "w_up": dense_init(ks[2], (m.num_experts, d, fe), pd),
             "w_down": dense_init(ks[3], (m.num_experts, fe, d), pd)}}
    if m.num_shared_experts:
        p["shared"] = init_mlp(ks[4], cfg, d_model=d,
                               d_ff=fe * m.num_shared_experts)
    return p


def _segment_rank(sorted_ids, n):
    """rank of each element within its run of equal ids (ids sorted)."""
    idx = jnp.arange(n)
    is_new = jnp.concatenate([jnp.ones((1,), bool),
                              sorted_ids[1:] != sorted_ids[:-1]])
    seg_start = jnp.where(is_new, idx, 0)
    seg_start = jax.lax.associative_scan(jnp.maximum, seg_start)
    return idx - seg_start


def apply_moe(params, x, cfg, dropless: bool = False):
    """x: (B,S,D) -> (y, aux_loss).  Dispatches to the expert-parallel
    shard_map path when a mesh with a data axis is active (the global
    scatter path triggers XLA's 'involuntary full rematerialization' —
    the (E,C,D) buffer gets replicated; see EXPERIMENTS.md §Perf).

    ``dropless=True`` (decode/serving): capacity is T (top-k ids are
    distinct per token, so no expert can receive more than T tokens) and
    nothing is ever dropped.  Capacity dropping is a *training*
    regularizer whose drop pattern depends on every other token in the
    call — under chunked prefill and padded engine rows that would make
    a token's output depend on the batch it happened to share a step
    with (and let padding columns displace real tokens), breaking
    engine==sequential equivalence.  Decode-time T is budgeted
    (rows * chunk), so the (E, T, D) dispatch buffer stays small."""
    if dropless:
        return apply_moe_scatter(params, x, cfg, dropless=True)
    mesh = sharding.active_mesh()
    if mesh is not None and "data" in mesh.axis_names \
            and cfg.moe.num_experts % dict(
                zip(mesh.axis_names, mesh.devices.shape))["data"] == 0:
        try:
            return apply_moe_ep(params, x, cfg, mesh)
        except Exception:
            pass  # fall back to the portable path
    return apply_moe_scatter(params, x, cfg)


def apply_moe_scatter(params, x, cfg, dropless: bool = False):
    """Portable single-program path (tests / single device)."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    k = m.num_experts_per_tok
    e = m.num_experts
    xt = x.reshape(t, d)

    # -- router (f32 for numerics) --
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        params["router"]["w"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)          # (T,K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # -- load-balance aux loss (Switch/GShard form) --
    me = probs.mean(0)                                        # (E,)
    ce = jnp.zeros((e,), jnp.float32).at[expert_ids.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(me * ce) * m.aux_loss_weight

    # -- capacity & position-in-expert via sort --
    # dropless bound is t, not t*k: top_k ids are distinct per token, so
    # no single expert can receive more than one assignment per token
    cap = (t if dropless
           else int(max(4, -(-t * k * m.capacity_factor // e))))
    tk = t * k
    flat_e = expert_ids.reshape(tk)
    order = jnp.argsort(flat_e, stable=True)
    ranks_sorted = _segment_rank(flat_e[order], tk)
    ranks = jnp.zeros((tk,), jnp.int32).at[order].set(
        ranks_sorted.astype(jnp.int32))
    keep = ranks < cap
    pos = jnp.where(keep, ranks, 0)

    # -- dispatch: scatter tokens into (E, C, D) --
    tok_idx = jnp.repeat(jnp.arange(t), k)
    vals = xt[tok_idx] * keep[:, None].astype(xt.dtype)
    xe = jnp.zeros((e, cap, d), xt.dtype).at[flat_e, pos].add(vals)
    xe = sharding.hint(xe, "data", None, None)

    # -- expert FFN (batched over E on the expert-parallel axis) --
    dt = xe.dtype
    g = jnp.einsum("ecd,edf->ecf", xe, params["experts"]["w_gate"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", xe, params["experts"]["w_up"].astype(dt))
    h = jax.nn.silu(g) * u
    ye = jnp.einsum("ecf,efd->ecd", h, params["experts"]["w_down"].astype(dt))
    ye = sharding.hint(ye, "data", None, None)

    # -- combine: gather back, weight by gate --
    y_slots = ye[flat_e, pos] * (gate_vals.reshape(tk, 1).astype(dt)
                                 * keep[:, None].astype(dt))
    y = y_slots.reshape(t, k, d).sum(1)

    if "shared" in params:
        y = y + apply_mlp(params["shared"], xt, cfg)
    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# expert-parallel shard_map path (intra-pod all_to_all)
# ---------------------------------------------------------------------------


def _moe_local(xt, router_w, w_gate, w_up, w_down, cfg, data_axis: str,
               model_axis=None):
    """Per-data-shard MoE body (inside shard_map; model axis is auto).

    xt: (T_loc, D) local tokens.  Expert weights are the LOCAL shard
    (E_loc = E/data, D, F).  Dispatch: local scatter into (E, C_loc, D),
    all_to_all over the *data* axis only — expert parallelism never
    crosses the pod boundary, matching LSGD's fast/slow split — expert
    FFN on E_loc experts, reverse all_to_all, local combine.
    Capacity is per shard (C_loc = ceil(T_loc*K*cf/E)), the standard
    GShard/Switch enforcement granularity.
    """
    m = cfg.moe
    t, d = xt.shape
    k = m.num_experts_per_tok
    e = m.num_experts
    n_shards = jax.lax.axis_size(data_axis)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True),
                                        1e-9)

    # aux loss from local stats, averaged across shards by the caller
    me = probs.mean(0)
    ce = jnp.zeros((e,), jnp.float32).at[expert_ids.reshape(-1)].add(1.0) \
        / (t * k)
    aux = e * jnp.sum(me * ce) * m.aux_loss_weight

    cap = int(max(4, -(-t * k * m.capacity_factor // e)))
    cap += (-cap) % n_shards          # all_to_all needs divisibility
    tk = t * k
    flat_e = expert_ids.reshape(tk)
    order = jnp.argsort(flat_e, stable=True)
    ranks_sorted = _segment_rank(flat_e[order], tk)
    ranks = jnp.zeros((tk,), jnp.int32).at[order].set(
        ranks_sorted.astype(jnp.int32))
    keep = ranks < cap
    pos = jnp.where(keep, ranks, 0)

    tok_idx = jnp.repeat(jnp.arange(t), k)
    vals = xt[tok_idx] * keep[:, None].astype(xt.dtype)
    xe = jnp.zeros((e, cap, d), xt.dtype).at[flat_e, pos].add(vals)

    # (E, C, D) -> (E_loc, C * n_shards, D): every shard receives the
    # slots destined for its local experts
    xe = jax.lax.all_to_all(xe, data_axis, split_axis=0, concat_axis=1,
                            tiled=True)

    dt = xe.dtype
    g = jnp.einsum("ecd,edf->ecf", xe, w_gate.astype(dt))
    u = jnp.einsum("ecd,edf->ecf", xe, w_up.astype(dt))
    h = jax.nn.silu(g) * u
    ye = jnp.einsum("ecf,efd->ecd", h, w_down.astype(dt))

    ye = jax.lax.all_to_all(ye, data_axis, split_axis=1, concat_axis=0,
                            tiled=True)   # back to (E, C, D)

    y_slots = ye[flat_e, pos] * (gate_vals.reshape(tk, 1).astype(dt)
                                 * keep[:, None].astype(dt))
    y = y_slots.reshape(t, k, d).sum(1)
    if model_axis is not None:
        # row-parallel down-proj: psum of the *token* tensor (delayed past
        # the reverse all_to_all and combine — the slot tensor is ~20x
        # larger; see EXPERIMENTS.md §Perf B3)
        y = jax.lax.psum(y, model_axis)
    return y, aux


def apply_moe_ep(params, x, cfg, mesh):
    """Expert-parallel MoE via partial-auto shard_map (manual over the DP
    axes, auto over `model`)."""
    from jax.sharding import PartitionSpec as P
    m = cfg.moe
    b, s, d = x.shape
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    cdt = x.dtype
    manual = set(dp) | ({"model"} if "model" in mesh.axis_names else set())
    model_axis = "model" if "model" in mesh.axis_names else None

    def body(xt, router_w, w_gate, w_up, w_down):
        # dtype note: any bf16 tensor inside (or crossing the boundary of)
        # this shard_map region trips an XLA *CPU* partitioner crash
        # ("Invalid binary instruction opcode copy") on this build, so the
        # region runs in f32 here.  On a real TPU backend the casts are
        # unnecessary.
        y, aux = _moe_local(xt.reshape(-1, d), router_w, w_gate, w_up,
                            w_down, cfg, "data", model_axis)
        # aux returned per-shard (reduced outside) — a replicated scalar
        # out_spec also trips the crash
        return y.reshape(xt.shape), aux[None]

    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None, None), P(),
                  P("data", None, model_axis),
                  P("data", None, model_axis),
                  P("data", model_axis, None)),
        out_specs=(P(dp, None, None), P(dp)),
        axis_names=set(manual), check_vma=True)
    y, aux = f(x.astype(jnp.float32),
               params["router"]["w"].astype(jnp.float32),
               params["experts"]["w_gate"].astype(jnp.float32),
               params["experts"]["w_up"].astype(jnp.float32),
               params["experts"]["w_down"].astype(jnp.float32))
    y = y.astype(cdt)
    aux = aux.mean()
    if "shared" in params:
        y = y + apply_mlp(params["shared"], x.reshape(b * s, d), cfg
                          ).reshape(b, s, d)
    return y, aux
