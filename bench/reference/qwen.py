"""Plain float32 reference of the dense Qwen decoder block (Qwen1.5, Qwen2).

Pre-norm decoder: RMSNorm, attention with QKV bias, rotary embeddings
(rotate-half, base ``rope_theta``), grouped-query heads, SwiGLU MLP, and
an output head tied to the embedding.  Written from the published model
description in straightforward ``jax.numpy``; it imports nothing of the
system under test.  Every matrix product runs at ``Precision.HIGHEST``.

``prec="fp8"`` is the control: every matrix product's operands are
rounded to float8 e4m3 (one scale per tensor) before the product, the
precision step below the bfloat16 the configurations state.

Sequences are processed one at a time, and the loss in chunks of
positions, so that the full-width models fit next to nothing else.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8_e4m3fn


def _q8(x):
    """x rounded to float8 e4m3 with one scale per tensor.  The gradient
    passes straight through the rounding (JAX differentiates a cast to
    float8 as zero), so the backward pass sees the rounded operands."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec, a, b, prec):
    if prec == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (S, heads, hd), positions 0..S-1."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(h, lw, dims, prec):
    S = h.shape[0]
    H, KV, hd = dims["num_heads"], dims["num_kv_heads"], dims["head_dim"]
    eps = dims["norm_eps"]
    x = _rms(h, lw["ln1"], eps)
    q = (_mm("sd,dk->sk", x, lw["wq"], prec) + lw["bq"]).reshape(S, H, hd)
    k = (_mm("sd,dk->sk", x, lw["wk"], prec) + lw["bk"]).reshape(S, KV, hd)
    v = (_mm("sd,dk->sk", x, lw["wv"], prec) + lw["bv"]).reshape(S, KV, hd)
    q, k = _rope(q, dims["rope_theta"]), _rope(k, dims["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    sc = _mm("qhd,khd->hqk", q, k, prec) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    o = _mm("hqk,khd->qhd", p, v, prec).reshape(S, H * hd)
    h = h + _mm("sk,kd->sd", o, lw["wo"], prec)
    x = _rms(h, lw["ln2"], eps)
    g = _mm("sd,df->sf", x, lw["w_gate"], prec)
    u = _mm("sd,df->sf", x, lw["w_up"], prec)
    return h + _mm("sf,fd->sd", jax.nn.silu(g) * u, lw["w_down"], prec)


_LAYER_KEYS = ("ln1", "ln2", "wq", "bq", "wk", "bk", "wv", "bv", "wo",
               "w_gate", "w_up", "w_down")


def hidden(w, tokens, dims, prec="f32", remat=False):
    """Final-normed hidden states (S, D) of one sequence ``tokens`` (S,)."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    h = jnp.take(w["embed"], tokens, axis=0)
    body = lambda h, lw: (_layer(h, lw, dims, prec), None)
    if remat:
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, h, {k: w[k] for k in _LAYER_KEYS})
    return _rms(h, w["final_norm"], dims["norm_eps"])


def _head(h, w, prec):
    return _mm("sd,vd->sv", h, w["embed"].astype(jnp.float32), prec)


def _dims_key(dims):
    return tuple(sorted(dims.items()))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _logits_at(w, tokens, idx, dims_key, prec):
    dims = dict(dims_key)
    h = hidden(w, tokens, dims, prec)
    return _head(h[idx], w, prec)


def logits_at(w, tokens, idx, dims, prec="f32"):
    """Logits (len(idx), V) at positions ``idx`` of one sequence."""
    return _logits_at(w, tokens, idx, _dims_key(dims), prec)


def _nll_sum(w, tokens, dims, prec, chunk):
    """Summed next-token NLL over positions 0..S-2 of one sequence."""
    h = hidden(w, tokens, dims, prec, remat=True)[:-1]
    labels = tokens[1:]
    n = h.shape[0]
    pad = (-n) % chunk
    h = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, chunk, h.shape[-1])
    lab = jnp.pad(labels, (0, pad)).reshape(-1, chunk)
    msk = (jnp.arange(n + pad) < n).reshape(-1, chunk)

    @jax.checkpoint
    def body(tot, xs):
        hc, lc, mc = xs
        lg = _head(hc, w, prec)
        nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, lc[:, None], -1)[:, 0]
        return tot + jnp.sum(jnp.where(mc, nll, 0.0)), None

    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (h, lab, msk))
    return tot


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _row_grad(w32, tokens, dims_key, prec, chunk):
    return jax.value_and_grad(_nll_sum)(w32, tokens, dict(dims_key), prec,
                                        chunk)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _rows_grad(w32, rows, dims_key, prec, chunk):
    """Summed NLL and gradient of several sequences, one per device."""
    nll, g = jax.vmap(jax.value_and_grad(_nll_sum), (None, 0, None, None,
                                                     None))(
        w32, rows, dict(dims_key), prec, chunk)
    return nll.sum(), jax.tree.map(lambda x: x.sum(0), g)


def loss_and_grad(w32, batch, dims, prec="f32", chunk=512, devices=None):
    """Mean next-token loss over every position of ``batch`` (B, S) and
    its gradient, summed in float32 one sequence at a time on each of
    ``devices`` (default: the first device alone)."""
    key = _dims_key(dims)
    batch = np.asarray(batch)
    n = len(devices) if devices and len(devices) > 1 \
        and batch.shape[0] % len(devices) == 0 else 1
    if n > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.asarray(devices), ("rows",))
        w32 = jax.device_put(w32, NamedSharding(mesh, P()))
        spread = NamedSharding(mesh, P("rows"))
    tot, grad = 0.0, None
    for i in range(0, batch.shape[0], n):
        if n > 1:
            nll, g = _rows_grad(w32, jax.device_put(batch[i:i + n], spread),
                                key, prec, chunk)
        else:
            nll, g = _row_grad(w32, jnp.asarray(batch[i]), key, prec, chunk)
        tot = tot + nll
        grad = g if grad is None else jax.tree.map(jnp.add, grad, g)
    count = batch.shape[0] * (batch.shape[1] - 1)
    return float(tot) / count, jax.tree.map(lambda g: g / count, grad)


@jax.jit
def _sgd(w, m, g, lr, momentum, wd):
    """SGD with momentum, PyTorch convention: m <- mu m + (g + wd w),
    w <- w - lr m; parameters are kept in the dtype ``w`` arrives in."""
    out_w, out_m = {}, {}
    for k in w:
        w32 = w[k].astype(jnp.float32)
        out_m[k] = momentum * m[k] + g[k] + wd * w32
        out_w[k] = (w32 - lr * out_m[k]).astype(w[k].dtype)
    return out_w, out_m


def train_steps(w, batches, dims, optim, prec="f32", devices=None):
    """Run len(batches) SGD steps from weights ``w`` (kept in their own
    dtype between steps, as the configuration stores them).  Returns
    (losses, first gradient, final weights)."""
    m = {k: jnp.zeros(v.shape, jnp.float32) for k, v in w.items()}
    losses, g0 = [], None
    for batch in batches:
        w32 = {k: v.astype(jnp.float32) for k, v in w.items()}
        loss, g = loss_and_grad(w32, batch, dims, prec, devices=devices)
        del w32
        losses.append(loss)
        if g0 is None:
            g0 = g
        w, m = _sgd(w, m, g, optim["lr"], optim["momentum"],
                    optim["weight_decay"])
    return losses, g0, w


def leaf_norms(tree):
    return {k: float(jnp.linalg.norm(v.astype(jnp.float32).reshape(-1)))
            for k, v in tree.items()}
