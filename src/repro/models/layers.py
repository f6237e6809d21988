"""Shared building blocks: inits, norms, MLPs, rotary embeddings."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------


def dense_init(key, shape, dtype, scale: Optional[float] = None):
    """Truncated-normal fan-in init (lecun-style)."""
    fan_in = shape[0] if len(shape) >= 2 else shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


def embed_init(key, shape, dtype, scale: float = 0.02):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def init_norm(key, dim, cfg):
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((dim,), cfg.pdtype),
                "bias": jnp.zeros((dim,), cfg.pdtype)}
    return {"scale": jnp.ones((dim,), cfg.pdtype)}


def apply_norm(params, x, cfg):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    if "bias" in params:  # layernorm
        mean = x.mean(-1, keepdims=True)
        var = ((x - mean) ** 2).mean(-1, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    else:  # rmsnorm
        var = (x ** 2).mean(-1, keepdims=True)
        y = x * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * params["scale"].astype(jnp.float32)
    return y.astype(dtype)


# ---------------------------------------------------------------------------
# MLP (dense FFN)
# ---------------------------------------------------------------------------


def init_mlp(key, cfg, d_model: Optional[int] = None, d_ff: Optional[int] = None):
    d = d_model or cfg.d_model
    f = d_ff or cfg.d_ff
    pd = cfg.pdtype
    ks = jax.random.split(key, 3)
    if cfg.activation == "swiglu":
        return {"w_gate": dense_init(ks[0], (d, f), pd),
                "w_up": dense_init(ks[1], (d, f), pd),
                "w_down": dense_init(ks[2], (f, d), pd)}
    return {"w_up": dense_init(ks[0], (d, f), pd),
            "w_down": dense_init(ks[1], (f, d), pd)}


@jax.named_scope("mlp")
def apply_mlp(params, x, cfg):
    if "w_gate" in params:
        g = jnp.einsum("...d,df->...f", x, params["w_gate"].astype(x.dtype))
        u = jnp.einsum("...d,df->...f", x, params["w_up"].astype(x.dtype))
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(
            jnp.einsum("...d,df->...f", x, params["w_up"].astype(x.dtype)))
    return jnp.einsum("...f,fd->...d", h, params["w_down"].astype(x.dtype))


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (...,S,hd/2)
    cos = jnp.cos(angles)[..., None, :]  # (...,S,1,hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos_emb(positions, dim: int, dtype):
    """Classic transformer sinusoidal embeddings; positions (...,S)."""
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / max(half - 1, 1))
    args = positions[..., None].astype(jnp.float32) * freqs
    emb = jnp.concatenate([jnp.sin(args), jnp.cos(args)], axis=-1)
    if dim % 2:
        emb = jnp.pad(emb, [(0, 0)] * (emb.ndim - 1) + [(0, 1)])
    return emb.astype(dtype)


# ---------------------------------------------------------------------------
# depthwise causal conv1d (mamba / rg-lru frontends)
# ---------------------------------------------------------------------------


def init_conv1d(key, channels: int, kernel: int, dtype):
    return {"conv_w": dense_init(key, (kernel, channels), dtype,
                                 scale=1.0 / math.sqrt(kernel)),
            "conv_b": jnp.zeros((channels,), dtype)}


def apply_conv1d(params, x, cache=None):
    """Depthwise causal conv.  x: (B, S, C).  cache: (B, K-1, C) past inputs.

    Returns (y, new_cache) where new_cache holds the last K-1 inputs.
    """
    w = params["conv_w"].astype(x.dtype)         # (K, C)
    b = params["conv_b"].astype(x.dtype)
    k = w.shape[0]
    if cache is None:
        pad = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    else:
        pad = cache.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)       # (B, S+K-1, C)
    # sum_k w[k] * x[t - (K-1) + k]
    y = sum(w[i] * jax.lax.dynamic_slice_in_dim(xp, i, x.shape[1], axis=1)
            for i in range(k))
    y = y + b
    new_cache = xp[:, -(k - 1):, :] if k > 1 else pad
    return y, new_cache


def slot_conv_window(conv0, x_raw, valid_len):
    """Conv cache for a paged state slot: the last K-1 *valid* inputs.

    The window of [conv0 | x_raw] ends just before column ``valid_len``
    (``apply_conv1d``'s own tail window would capture padded columns).
    valid_len None means every column is valid.  Shared by the ssm and
    rglru slot-state paths."""
    b, s = x_raw.shape[:2]
    k1 = conv0.shape[1]
    full = jnp.concatenate([conv0, x_raw], axis=1)      # (B, K-1+S, C)
    vl = (jnp.full((b,), s, jnp.int32) if valid_len is None else valid_len)
    idx = vl[:, None] + jnp.arange(k1)[None]            # (B, K-1)
    return jnp.take_along_axis(full, idx[..., None], axis=1)


def slot_state_scatter(pool, state_slots, valid_len, value):
    """Write each row's recurrent state back to its slot; rows with
    ``valid_len == 0`` (padding/stale) write trash slot 0 instead, so a
    stale engine row can never advance a live slot's state — the
    recurrent analogue of the KV trash block."""
    wslot = (state_slots if valid_len is None
             else jnp.where(valid_len > 0, state_slots, 0))
    return pool.at[wslot].set(value.astype(pool.dtype))


# ---------------------------------------------------------------------------
# cross entropy
# ---------------------------------------------------------------------------


def cross_entropy(logits, labels, mask=None):
    """Mean next-token CE.  logits (..., V) f32-upcast; labels (...) int."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - ll
    if mask is None:
        return nll.mean()
    mask = mask.astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
