"""Sharding utilities: partition rules for every param family + activation
sharding hints.

Mesh axes (see launch/mesh.py):
  pod    — slow inter-pod fabric (LSGD's "between communicators" layer)
  data   — fast intra-pod axis used for data parallelism (and FSDP / experts)
  model  — tensor parallelism (heads / ffn hidden / vocab)

Activation hints are no-ops unless a mesh has been activated via
``set_active_mesh`` (the launchers do this; unit tests run without).
"""
from __future__ import annotations

import re
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_ACTIVE_MESH: Optional[Mesh] = None


def set_active_mesh(mesh: Optional[Mesh]) -> None:
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh


def active_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH


def hint(x, *spec):
    """with_sharding_constraint if a mesh is active, else identity.

    Axis names missing from the active mesh are dropped; inside a
    shard_map manual region (where constraints on manual axes are
    illegal) the hint degrades to identity.
    """
    if _ACTIVE_MESH is None:
        return x
    # inside a shard_map manual region constraints on manual axes are
    # illegal — detect bound manual axis names and skip the hint
    try:
        from jax._src import core as _core
        env = _core.get_axis_env()
        if env.axis_sizes:
            return x
    except Exception:
        pass
    axes = _ACTIVE_MESH.axis_names
    # drop axis names not present in the active mesh (e.g. no "pod" axis)
    clean = []
    for s in spec:
        if s is None:
            clean.append(None)
        elif isinstance(s, tuple):
            kept = tuple(a for a in s if a in axes)
            clean.append(kept if kept else None)
        else:
            clean.append(s if s in axes else None)
    try:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(_ACTIVE_MESH, P(*clean)))
    except Exception:
        return x


# ---------------------------------------------------------------------------
# Parameter partition rules
# ---------------------------------------------------------------------------
# Matched against the '/'-joined pytree path of each parameter leaf.  First
# match wins.  Specs are written for the *unstacked* (per-layer) shape; a
# leading scan-stack axis is detected by rank mismatch and padded with None.
#
# fsdp=True additionally shards the largest replicated dim over "data"
# (ZeRO-3 style) — required for the 100B+ configs to fit HBM.

_RULES = [
    # embeddings / unembedding: vocab over model
    (r"embed/embedding$",        ("model", None)),
    (r"lm_head/w$",              (None, "model")),
    (r"pos_embed/embedding$",    (None, None)),
    # attention
    (r"attn/wq$",                (None, "model")),
    (r"attn/wk$",                (None, "model")),
    (r"attn/wv$",                (None, "model")),
    (r"attn/wo$",                ("model", None)),
    (r"attn/[bw]?b[qkv]$",       ("model",)),
    # MLA
    (r"attn/wq_a$",              (None, None)),
    (r"attn/wq_b$",              (None, "model")),
    (r"attn/wkv_a$",             (None, None)),
    (r"attn/wkv_b$",             (None, "model")),
    (r"attn/(q_norm|kv_norm)/scale$", (None,)),
    # dense mlp
    (r"mlp/w_gate$",             (None, "model")),
    (r"mlp/w_up$",               (None, "model")),
    (r"mlp/w_down$",             ("model", None)),
    # MoE: experts over data (expert parallel), hidden over model
    (r"moe/router/w$",           (None, None)),
    (r"moe/experts/w_gate$",     ("data", None, "model")),
    (r"moe/experts/w_up$",       ("data", None, "model")),
    (r"moe/experts/w_down$",     ("data", "model", None)),
    (r"moe/shared/w_gate$",      (None, "model")),
    (r"moe/shared/w_up$",        (None, "model")),
    (r"moe/shared/w_down$",      ("model", None)),
    # mamba2 / SSD
    (r"ssm/in_proj$",            (None, "model")),
    (r"ssm/conv_w$",             (None, "model")),
    (r"ssm/conv_b$",             ("model",)),
    (r"ssm/(A_log|D|dt_bias)$",  ("model",)),
    (r"ssm/norm/scale$",         ("model",)),
    (r"ssm/out_proj$",           ("model", None)),
    # RG-LRU
    (r"rglru/w_x$",              (None, "model")),
    (r"rglru/w_gate$",           (None, "model")),
    (r"rglru/conv_w$",           (None, "model")),
    (r"rglru/conv_b$",           ("model",)),
    (r"rglru/(w_r|w_i)$",        (None, "model")),
    (r"rglru/(b_r|b_i|lam)$",    ("model",)),
    (r"rglru/w_out$",            ("model", None)),
    # norms & scalars: replicated
    (r"(norm|ln)[^/]*/(scale|bias)$", None),
    (r"scale$|bias$",            None),
    # resnet convs
    (r"conv[^/]*/w$",            (None, None, None, "model")),
    (r"fc/w$",                   (None, "model")),
]


def _spec_for(path: str, ndim: int, fsdp_axis: Optional[str]) -> P:
    for pat, spec in _RULES:
        if re.search(pat, path):
            if spec is None:
                return P()
            spec = list(spec)
            # pad leading stacked-layer axes
            while len(spec) < ndim:
                spec.insert(0, None)
            spec = spec[:ndim] if len(spec) > ndim else spec
            used = {a for s in spec if s
                    for a in (s if isinstance(s, tuple) else (s,))}
            if fsdp_axis and fsdp_axis not in used:
                # shard the first large replicated dim over the fsdp axis
                for i, s in enumerate(spec):
                    if s is None and ndim - i <= len(spec):
                        # skip stacked-layer axis (i==0 with ndim>len rule)
                        if i == 0 and ndim > 2:
                            continue
                        spec[i] = fsdp_axis
                        break
            return P(*spec)
    return P()  # default: replicate


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_pspecs(abstract_params: Any, *, fsdp: bool = False) -> Any:
    """PartitionSpec pytree matching ``abstract_params`` (from eval_shape)."""
    fsdp_axis = "data" if fsdp else None

    def f(path, leaf):
        return _spec_for(_path_str(path), np.ndim(leaf), fsdp_axis)

    return jax.tree_util.tree_map_with_path(f, abstract_params)


def batch_pspec(kind: str = "train") -> P:
    """Batch dims shard over (pod, data)."""
    return P(("pod", "data"))


def filter_spec_for_mesh(spec_tree: Any, mesh: Mesh) -> Any:
    """Drop axis names that don't exist in ``mesh`` (e.g. single-pod)."""
    axes = set(mesh.axis_names)

    def clean(spec):
        if not isinstance(spec, P):
            return spec
        out = []
        for s in spec:
            if s is None:
                out.append(None)
            elif isinstance(s, tuple):
                kept = tuple(a for a in s if a in axes)
                out.append(kept if kept else None)
            else:
                out.append(s if s in axes else None)
        return P(*out)

    return jax.tree.map(clean, spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def legalize_pspecs(abstract_tree: Any, spec_tree: Any, mesh: Mesh) -> Any:
    """Drop sharded axes whose dimension doesn't divide evenly on ``mesh``
    (XLA input shardings require exact tiling; e.g. vocab 50280 % 16 != 0
    stays replicated)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def fix(leaf, spec):
        if not isinstance(spec, P):
            return spec
        out = []
        for i, s in enumerate(spec):
            if s is None or i >= len(leaf.shape):
                out.append(None if i >= len(leaf.shape) else s)
                continue
            names = s if isinstance(s, tuple) else (s,)
            n = 1
            for a in names:
                n *= sizes.get(a, 1)
            out.append(s if n and leaf.shape[i] % n == 0 else None)
        return P(*out)

    return jax.tree.map(fix, abstract_tree, spec_tree,
                        is_leaf=lambda x: isinstance(x, P) or
                        isinstance(x, jax.ShapeDtypeStruct))


def named_sharding_tree(spec_tree: Any, mesh: Mesh) -> Any:
    spec_tree = filter_spec_for_mesh(spec_tree, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Serving (tensor-parallel replica) shardings
# ---------------------------------------------------------------------------
# A serve replica's sub-mesh has a single "model" axis spanning its device
# slice (launch.mesh.replica_slices).  Params reuse the training rules with
# one remap: routed experts go *expert-parallel* over "model" (the serving
# mesh has no "data" axis, and splitting d_ff_expert would change psum
# reduction order inside each expert — EP keeps per-expert math bit-exact,
# which the engine==sequential equivalence contract requires).  The paged
# pools shard on the same family axis the params do (heads / channels),
# while everything consulted by control flow — block tables, slot token
# buffers, MLA latent pools (shared across heads by construction) — stays
# replicated, so `paged_step`/`paged_decode_loop` run unchanged under
# GSPMD and every collective is XLA's to place.


def serve_param_pspecs(abstract_params: Any, mesh: Mesh) -> Any:
    """Partition specs for a TP serve replica: training rules with routed
    experts remapped from ("data", …, "model") to pure expert-parallel
    over "model", then legalized against ``mesh`` (non-dividing dims stay
    replicated)."""
    base = param_pspecs(abstract_params)

    def remap(path, leaf, spec):
        if re.search(r"moe/experts/", _path_str(path)):
            return P(*["model" if s == "data" else None for s in spec])
        return spec

    specs = jax.tree_util.tree_map_with_path(remap, abstract_params, base)
    specs = legalize_pspecs(abstract_params, specs, mesh)
    return filter_spec_for_mesh(specs, mesh)


# paged-cache leaf name -> spec for the *unstacked* serving layout.  Keyed
# by basename because init_paged_cache emits one dict per layer family:
#   k/v      (L, num_blocks, block_size, num_kv_heads, head_dim)  heads
#   ckv      (L, num_blocks, block_size, kv_lora_rank)   latent: replicated
#   krope    (L, num_blocks, block_size, qk_rope_head_dim)        replicated
#   state    (L, slots, heads, head_dim, d_state)                 heads
#   conv     (..., channels)                                      channels
#   h        (..., channels)                                      channels
_CACHE_AXES = {"k": 3, "v": 3, "state": 2}        # name -> sharded dim
_CACHE_LAST = {"conv", "h"}                       # shard the last dim


def serve_cache_pspecs(cache: Any, mesh: Mesh) -> Any:
    """Partition specs for a paged cache pytree on a serve replica mesh:
    K/V pools shard on the head axis, ssm/rglru state on the channel/head
    axes, MLA latent pools + block tables + token buffers replicate."""
    def f(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        nd = np.ndim(leaf)
        if name in _CACHE_AXES and nd > _CACHE_AXES[name]:
            # no trailing Nones: XLA hands donated outputs back with the
            # trimmed canonical spec, and spec-identical round-trips are
            # what keep the jit cache at one entry per shape
            return P(*([None] * _CACHE_AXES[name] + ["model"]))
        if name in _CACHE_LAST and nd >= 1:
            return P(*([None] * (nd - 1) + ["model"]))
        return P()

    specs = jax.tree_util.tree_map_with_path(f, cache)
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), getattr(x, "dtype", None)),
        cache)
    specs = legalize_pspecs(abstract, specs, mesh)
    return filter_spec_for_mesh(specs, mesh)
