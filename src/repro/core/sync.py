"""Gradient synchronization strategies (used inside the shard_map trainer).

All strategies compute the *same value* — the global data-parallel mean of
the gradient pytree — but schedule different collectives:

  flat              one all-reduce over every data-parallel device
                    (paper Alg. 2, CSGD — the baseline bottleneck)
  layered           paper Alg. 3: intra-group reduce (fast fabric) then
                    inter-group all-reduce (slow fabric).  The trainer
                    defers consumption of the result to the next step,
                    which is what lets the scheduler hide the slow phase.
  layered_rsag      beyond-paper: the slow phase as reduce-scatter +
                    all-gather over the slow axis (bucket-parallel links).
  layered_compressed beyond-paper: slow phase payload cast to bf16 with
                    error-feedback residual (breaks bit-exactness; the
                    residual state bounds the drift).

Every function takes/returns a gradient pytree; they must be called inside
``jax.shard_map(..., check_vma=False)`` with the named axes bound.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import sharding
from repro.core.topology import Topology


def _axes_present(topo: Topology, mesh_axis_names: Sequence[str],
                  manual: Sequence[str]):
    fast = topo.fast_axis if topo.fast_axis in mesh_axis_names \
        and topo.fast_axis in manual else None
    slow = topo.slow_axis if topo.slow_axis in mesh_axis_names \
        and topo.slow_axis in manual else None
    return fast, slow


def flat_sync(grads, topo: Topology, mesh_axis_names, manual):
    """CSGD: single flat all-reduce (mean) over all DP devices."""
    fast, slow = _axes_present(topo, mesh_axis_names, manual)
    axes = tuple(a for a in (fast, slow) if a)
    if not axes:
        return grads
    return jax.tree.map(lambda g: jax.lax.pmean(g, axes), grads)


def layered_sync(grads, topo: Topology, mesh_axis_names, manual,
                 data_size: int):
    """LSGD two-phase hierarchical mean (paper Alg. 3 lines 6+8)."""
    fast, slow = _axes_present(topo, mesh_axis_names, manual)
    p1 = topo.phase1_groups(data_size) if fast else None
    p2 = topo.phase2_groups(data_size) if fast else None

    def sync(g):
        # phase 1: reduce to the communicator (intra-group, fast fabric)
        if fast:
            g = jax.lax.pmean(g, fast, axis_index_groups=p1)
        # phase 2: all-reduce among communicators (slow fabric)
        if fast and p2 is not None:
            g = jax.lax.pmean(g, fast, axis_index_groups=p2)
        if slow:
            g = jax.lax.pmean(g, slow)
        return g

    return jax.tree.map(sync, grads)


def layered_rsag_sync(grads, topo: Topology, mesh_axis_names, manual,
                      data_size: int):
    """Beyond-paper: slow phase as reduce-scatter + all-gather.

    psum_scatter splits the payload across the slow-axis members so each
    link carries 1/P of the bytes in each of the two phases (vs the full
    payload in a plain ring all-reduce's single logical op) — XLA can
    pipeline the two halves independently of the fast-phase collectives.
    """
    fast, slow = _axes_present(topo, mesh_axis_names, manual)
    p1 = topo.phase1_groups(data_size) if fast else None
    p2 = topo.phase2_groups(data_size) if fast else None
    def sync(g):
        if fast:
            g = jax.lax.pmean(g, fast, axis_index_groups=p1)
            if p2 is not None:
                g = jax.lax.pmean(g, fast, axis_index_groups=p2)
        if slow:
            orig_shape = g.shape
            n = jax.lax.axis_size(slow)
            flat = g.reshape(-1)
            pad = (-flat.size) % n
            if pad:
                flat = jnp.pad(flat, (0, pad))
            flat = flat.reshape(n, -1)
            shard = jax.lax.psum_scatter(flat, slow, scatter_dimension=0,
                                         tiled=False) / n
            full = jax.lax.all_gather(shard, slow, axis=0)
            g = full.reshape(-1)[:g.size].reshape(orig_shape)
        return g

    return jax.tree.map(sync, grads)


def layered_compressed_sync(grads, residual, topo: Topology,
                            mesh_axis_names, manual, data_size: int):
    """Beyond-paper: bf16 slow-phase payload with error feedback.

    Returns (synced_grads, new_residual).  The residual accumulates the
    local quantization error and is re-injected next step (Karimireddy
    et al.-style EF), keeping long-run drift bounded.
    """
    fast, slow = _axes_present(topo, mesh_axis_names, manual)
    p1 = topo.phase1_groups(data_size) if fast else None
    p2 = topo.phase2_groups(data_size) if fast else None

    def sync(g, r):
        if fast:
            g = jax.lax.pmean(g, fast, axis_index_groups=p1)
            if p2 is not None:
                g = jax.lax.pmean(g, fast, axis_index_groups=p2)
        if slow is None:
            return g, jnp.zeros_like(r)
        g32 = g.astype(jnp.float32) + r.astype(jnp.float32)
        q = g32.astype(jnp.bfloat16)
        new_r = g32 - q.astype(jnp.float32)
        # wire payload is the bf16 quantization; the pmean runs over its
        # f32 re-expansion because bf16 collectives inside shard_map crash
        # this XLA CPU build (numerics identical to a bf16-payload pmean
        # with f32 accumulation, which is what TPU does; wire bytes in the
        # dry-run HLO therefore overstate this mode by 2x)
        out = jax.lax.pmean(q.astype(jnp.float32), slow)
        return out.astype(g.dtype), new_r.astype(r.dtype)

    pairs = jax.tree.map(sync, grads, residual)
    synced = jax.tree.map(lambda t: t[0], pairs,
                          is_leaf=lambda t: isinstance(t, tuple))
    new_res = jax.tree.map(lambda t: t[1], pairs,
                           is_leaf=lambda t: isinstance(t, tuple))
    return synced, new_res


SYNC_MODES = ("csgd", "lsgd", "lsgd_rsag", "lsgd_compressed")
