"""The LSGD / CSGD trainer.

Two execution paths, one algorithm (DESIGN.md §4):

* **shard_map path** (paper-faithful, pure data-parallel over the manual
  (pod, data) axes; tensor parallelism rides the auto `model` axis).  The
  whole train step — deferred pending update, local gradients, two-phase
  hierarchical sync — is one ``jax.shard_map(check_vma=False)`` region, so
  the collectives in the HLO are exactly the ones the paper prescribes.

* **pjit path** (`fsdp=True`, beyond-paper): for the 100B+ configs whose
  optimizer state cannot be replicated, parameters are ZeRO-3 sharded over
  `data` and XLA chooses the collectives; LSGD's *deferral* still applies
  (the pending gradient is consumed only at the top of the next step, so
  the latency-hiding scheduler overlaps the cross-pod phase with the next
  step's early compute — the paper's overlap, generalized to FSDP).

Exact-sequence property: with ``defer_update=True`` the parameter vector
after ``finalize()`` equals CSGD's after the same number of steps (paper
§4.2); ``tests/test_equivalence.py`` asserts it.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import sharding
from repro.core import sync as sync_mod
from repro.core.topology import Topology
from repro.optim.sgd import OptimConfig, apply_update, init_state


@dataclass(frozen=True)
class TrainerConfig:
    sync_mode: str = "lsgd"       # csgd | lsgd | lsgd_eager | lsgd_rsag |
                                  # lsgd_compressed
    optim: OptimConfig = field(default_factory=OptimConfig)
    topology: Topology = field(default_factory=Topology)
    fsdp: bool = False            # pjit path with ZeRO-3 params
    pending_dtype: str = "float32"  # deferred-gradient buffer dtype
    grad_dtype: str = "float32"   # gradient sync dtype (bf16 halves the
                                  # FSDP grad-sync wire bytes; optimizer
                                  # math still upcasts to f32 per leaf)
    # lr_fn is supplied separately (a traced step -> lr callable)

    @property
    def defer_update(self) -> bool:
        return self.sync_mode in ("lsgd", "lsgd_rsag", "lsgd_compressed")

    @property
    def layered(self) -> bool:
        return self.sync_mode != "csgd"


def make_init_state(model, tcfg: TrainerConfig):
    """Returns init_fn(rng) -> state dict."""

    def init_fn(rng):
        params = model.init(rng)
        state = {"params": params,
                 "opt": init_state(params, tcfg.optim),
                 "step": jnp.zeros((), jnp.int32)}
        pdt = jnp.dtype(tcfg.pending_dtype)
        if tcfg.defer_update:
            state["pending"] = jax.tree.map(
                lambda p: jnp.zeros(p.shape, pdt), params)
        if tcfg.sync_mode == "lsgd_compressed":
            state["residual"] = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
        return state

    return init_fn


@jax.named_scope("lsgd.apply_pending")
def _apply_pending(state, lr_fn, ocfg):
    """Deferred update of step t-1 (LSGD Alg. 3 line 10); no-op at step 0."""
    params, opt = state["params"], state["opt"]

    def do(args):
        p, o = args
        return apply_update(p, o, state["pending"], lr_fn(state["step"] - 1),
                            ocfg)

    return jax.lax.cond(state["step"] > 0, do, lambda a: a, (params, opt))


def _algorithm(model, tcfg: TrainerConfig, lr_fn, sync_fn):
    """The step body, shared by both execution paths.  ``sync_fn`` maps the
    raw (local or global) gradient pytree to the fully-averaged one; in the
    pjit path it is identity (autodiff of the global-mean loss already
    averages).

    Each phase is a ``jax.named_scope``, so its ops carry the name in
    their HLO ``op_name`` and in the device trace: ``lsgd.apply_pending``
    (the deferred update), ``lsgd.fwd_bwd`` (loss and gradients),
    ``lsgd.sync`` and ``lsgd.pending_write`` (LSGD) or ``lsgd.update``
    (the eager update of the other modes)."""
    ocfg = tcfg.optim

    def step(state, batch):
        new_state = dict(state)
        if tcfg.defer_update:
            params, opt = _apply_pending(state, lr_fn, ocfg)
        else:
            params, opt = state["params"], state["opt"]

        with jax.named_scope("lsgd.fwd_bwd"):
            (loss, metrics), grads = jax.value_and_grad(
                model.loss, has_aux=True)(params, batch)
            gdt = jnp.dtype(tcfg.grad_dtype)
            grads = jax.tree.map(lambda g: g.astype(gdt), grads)

        with jax.named_scope("lsgd.sync"):
            if tcfg.sync_mode == "lsgd_compressed":
                grads, new_res = sync_fn(grads, state["residual"])
                new_state["residual"] = new_res
            else:
                grads = sync_fn(grads)

        if tcfg.defer_update:
            with jax.named_scope("lsgd.pending_write"):
                new_state["pending"] = jax.tree.map(
                    lambda g, old: g.astype(old.dtype), grads,
                    state["pending"])
        else:
            with jax.named_scope("lsgd.update"):
                params, opt = apply_update(params, opt, grads,
                                           lr_fn(state["step"]), ocfg)
        new_state["params"] = params
        new_state["opt"] = opt
        new_state["step"] = state["step"] + 1
        return new_state, (loss, metrics)

    return step


def make_finalize(model, tcfg: TrainerConfig, lr_fn):
    """Flush the trailing pending update (makes LSGD == CSGD exactly)."""

    def finalize(state):
        if not tcfg.defer_update:
            return state
        params, opt = _apply_pending(state, lr_fn, tcfg.optim)
        out = dict(state)
        out["params"], out["opt"] = params, opt
        out["pending"] = jax.tree.map(jnp.zeros_like, state["pending"])
        return out

    return finalize


# ---------------------------------------------------------------------------
# shard_map path (paper-faithful collectives)
# ---------------------------------------------------------------------------


def _batch_specs(batch_tree, dp_axes):
    return jax.tree.map(
        lambda leaf: P(dp_axes, *([None] * (jnp.ndim(leaf) - 1))), batch_tree)


def make_shardmap_step(model, tcfg: TrainerConfig, lr_fn, mesh):
    """Train step with explicit LSGD collectives.  Params replicated over
    the manual (pod, data) axes, sharded over the auto `model` axis."""
    topo = tcfg.topology
    manual = tuple(a for a in (topo.slow_axis, topo.fast_axis)
                   if a in mesh.axis_names)
    dp_axes = tuple(a for a in (topo.slow_axis, topo.fast_axis)
                    if a in mesh.axis_names)
    data_size = dict(zip(mesh.axis_names, mesh.devices.shape)).get(
        topo.fast_axis, 1)

    if tcfg.sync_mode == "csgd":
        sync_fn = lambda g: sync_mod.flat_sync(g, topo, mesh.axis_names,
                                               manual)
    elif tcfg.sync_mode in ("lsgd", "lsgd_eager"):
        sync_fn = lambda g: sync_mod.layered_sync(g, topo, mesh.axis_names,
                                                  manual, data_size)
    elif tcfg.sync_mode == "lsgd_rsag":
        sync_fn = lambda g: sync_mod.layered_rsag_sync(
            g, topo, mesh.axis_names, manual, data_size)
    elif tcfg.sync_mode == "lsgd_compressed":
        sync_fn = lambda g, r: sync_mod.layered_compressed_sync(
            g, r, topo, mesh.axis_names, manual, data_size)
    else:
        raise ValueError(tcfg.sync_mode)

    body = _algorithm(model, tcfg, lr_fn, sync_fn)

    def wrapped(state, batch):
        new_state, (loss, metrics) = body(state, batch)
        # replicate metrics across DP shards for reporting
        if dp_axes:
            loss = jax.lax.pmean(loss, dp_axes)
            metrics = jax.tree.map(lambda m: jax.lax.pmean(m, dp_axes),
                                   metrics)
        return new_state, (loss, metrics)

    def step_fn(state, batch):
        state_specs = jax.tree.map(lambda _: P(), state)
        bspecs = _batch_specs(batch, dp_axes)
        # metrics tree structure (no collectives in model.loss, so
        # eval_shape is safe outside the shard_map region)
        _, metrics_abs = jax.eval_shape(model.loss, state["params"], batch)
        out_specs = (state_specs,
                     (P(), jax.tree.map(lambda _: P(), metrics_abs)))
        f = jax.shard_map(wrapped, mesh=mesh,
                          in_specs=(state_specs, bspecs),
                          out_specs=out_specs,
                          axis_names=set(manual), check_vma=False)
        return f(state, batch)

    return step_fn


# ---------------------------------------------------------------------------
# pjit path (FSDP / auto collectives; LSGD deferral preserved)
# ---------------------------------------------------------------------------


def make_pjit_step(model, tcfg: TrainerConfig, lr_fn):
    """Global-arrays train step; call under ``jax.jit`` with shardings from
    ``state_shardings``/``batch_shardings``."""
    body = _algorithm(model, tcfg, lr_fn, sync_fn=lambda g: g)

    def step(state, batch):
        new_state, (loss, metrics) = body(state, batch)
        return new_state, (loss, metrics)

    return step


def state_pspecs(abstract_state, *, fsdp: bool):
    """PartitionSpec tree for a trainer state pytree."""
    specs = {}
    pspec = sharding.param_pspecs(abstract_state["params"], fsdp=fsdp)
    specs["params"] = pspec
    # opt/pending/residual mirror the param layout
    opt = {}
    for k, v in abstract_state["opt"].items():
        if k == "t":
            opt[k] = P()
        else:
            opt[k] = pspec
    specs["opt"] = opt
    specs["step"] = P()
    if "pending" in abstract_state:
        specs["pending"] = pspec
    if "residual" in abstract_state:
        specs["residual"] = pspec
    return specs


def batch_pspecs(batch_tree, mesh):
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return jax.tree.map(
        lambda leaf: P(dp, *([None] * (jnp.ndim(leaf) - 1))), batch_tree)
