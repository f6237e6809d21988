"""Seeded weights of a dense Qwen decoder, in the benchmark's own layout.

Both sides of a comparison make their weights here from ``--seed``: the
harness hands them to the program (``adapter.py`` nests them into the
program's parameter tree) and the reference makes them again on its own,
so the reference takes nothing the program has made.

Layout (L layers stacked on the leading axis, D model width, H/KV query
and key-value heads of width hd, F feed-forward width, V vocabulary):

    embed (V, D)  final_norm (D,)  ln1, ln2 (L, D)
    wq (L, D, H*hd)   bq (L, H*hd)   wk, wv (L, D, KV*hd)   bk, bv (L, KV*hd)
    wo (L, H*hd, D)   w_gate, w_up (L, D, F)   w_down (L, F, D)

The output head is tied to ``embed``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LEAVES = ("embed", "final_norm", "ln1", "ln2", "wq", "bq", "wk", "bk",
          "wv", "bv", "wo", "w_gate", "w_up", "w_down")


def shapes(dims: dict) -> dict:
    L, D, H, KV, hd, F, V = (dims[k] for k in (
        "num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
        "d_ff", "vocab_size"))
    return {"embed": (V, D), "final_norm": (D,), "ln1": (L, D),
            "ln2": (L, D), "wq": (L, D, H * hd), "bq": (L, H * hd),
            "wk": (L, D, KV * hd), "bk": (L, KV * hd),
            "wv": (L, D, KV * hd), "bv": (L, KV * hd),
            "wo": (L, H * hd, D), "w_gate": (L, D, F), "w_up": (L, D, F),
            "w_down": (L, F, D)}


def _std(name: str, shape) -> float:
    if name == "embed":
        return 0.02
    if name in ("bq", "bk", "bv"):
        return 0.02
    if name in ("final_norm", "ln1", "ln2"):
        return 0.1
    return 1.0 / math.sqrt(shape[-2])       # fan-in of a (.., in, out) matrix


def seed_key(seed: int):
    """A key for any whole ``seed`` up to 63 bits (two 31-bit halves)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def generate(dims: dict, key, dtype) -> dict:
    """The weights for ``key``; call under ``jax.jit``."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(dims).items())):
        x = jax.random.normal(jax.random.fold_in(key, i), shape, dtype)
        x = x * jnp.asarray(_std(name, shape), dtype)
        if name in ("final_norm", "ln1", "ln2"):
            x = x + jnp.ones((), dtype)
        out[name] = x
    return out


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make(dims_items, key, dtype):
    return generate(dict(dims_items), key, dtype)


def make_weights(dims: dict, seed: int, dtype="bfloat16") -> dict:
    """All weights in one jitted call on the default device."""
    items = tuple(sorted((k, int(dims[k])) for k in (
        "num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
        "d_ff", "vocab_size")))
    return _make(items, seed_key(seed), jnp.dtype(dtype))
