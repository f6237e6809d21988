"""The dense Qwen family (Qwen1.5, Qwen2): everything the benchmark knows
of it, under the names every family module exports (``bench.program``
lists them in ``INTERFACE``).

- Sizes: ``PUBLISHED`` maps a published ``config.json`` key to the
  program's ``ModelConfig`` field, ``check_published`` refuses a
  program whose sizes differ from the file's, ``dims`` gives the sizes
  the rest of this module takes.
- Benchmark layout: ``shapes``, ``generate``, ``make_weights``.  Both
  sides of a comparison make their weights here from ``--seed``: the
  cells nest them into the program's tree (``to_program``), and the
  reference makes them again on its own, so it takes nothing the
  program has made.
- Program layout: ``to_program``, ``from_program``.
- Reference: ``train_steps``, ``leaf_norms``, ``logits_at``.
- Counts: ``train_flops_per_token``, ``serve_flops``, ``attend_cost``.

The block: pre-norm decoder, RMSNorm, attention with QKV bias, rotary
embeddings (rotate-half, base ``rope_theta``), grouped-query heads,
SwiGLU MLP, an output head tied to the embedding.  The reference is
written from the published model description in straightforward
``jax.numpy``; it imports nothing of the system under test.  Every
matrix product runs at ``Precision.HIGHEST``; ``prec="fp8"`` is the
control (``common.mm``), the precision step below the bfloat16 the
configurations state.  Sequences are processed one at a time, and the
loss in chunks of positions, so that the full-width models fit next to
nothing else.

Layout (L layers stacked on the leading axis, D model width, H/KV query
and key-value heads of width hd, F feed-forward width, V vocabulary):

    embed (V, D)  final_norm (D,)  ln1, ln2 (L, D)
    wq (L, D, H*hd)   bq (L, H*hd)   wk, wv (L, D, KV*hd)   bk, bv (L, KV*hd)
    wo (L, H*hd, D)   w_gate, w_up (L, D, F)   w_down (L, F, D)

Counts are kept with the benchmark so that no change to the program can
move the yardstick.  Multiply-adds count as two operations; bf16
operands are 2 bytes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.common import BF16, mm, rms, seed_key

# ---------------------------------------------------------------- sizes

# published config.json key -> the program's ModelConfig field
PUBLISHED = {"hidden_size": "d_model", "intermediate_size": "d_ff",
             "num_attention_heads": "num_heads",
             "num_key_value_heads": "num_kv_heads",
             "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
             "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
             "tie_word_embeddings": "tie_embeddings"}


def check_published(cfg_file: dict, mc) -> None:
    """Refuse a ModelConfig whose sizes differ from the published
    numbers the configuration file holds."""
    bad = {k: (cfg_file[k], getattr(mc, f)) for k, f in PUBLISHED.items()
           if cfg_file[k] != getattr(mc, f)}
    if mc.head_dim * mc.num_heads != cfg_file["hidden_size"]:
        bad["head_dim"] = (cfg_file["hidden_size"], mc.head_dim)
    if bad:
        raise SystemExit(f"{mc.name}: differs from its source: {bad}")


def dims(mc) -> dict:
    return {"num_layers": mc.num_layers, "d_model": mc.d_model,
            "num_heads": mc.num_heads, "num_kv_heads": mc.num_kv_heads,
            "head_dim": mc.head_dim, "d_ff": mc.d_ff,
            "vocab_size": mc.vocab_size, "rope_theta": mc.rope_theta,
            "norm_eps": mc.norm_eps}


# ---------------------------------------------------- benchmark layout

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


# ------------------------------------------------------ program layout

_ATTN = ("wq", "bq", "wk", "bk", "wv", "bv", "wo")
_MLP = ("w_gate", "w_up", "w_down")


def to_program(w: dict) -> dict:
    """Benchmark layout -> the program's dense-decoder parameter tree."""
    return {"embed": {"embedding": w["embed"]},
            "final_norm": {"scale": w["final_norm"]},
            "layers": {"run_0": {
                "ln1": {"scale": w["ln1"]},
                "attn": {k: w[k] for k in _ATTN},
                "ln2": {"scale": w["ln2"]},
                "mlp": {k: w[k] for k in _MLP}}}}


def from_program(p: dict) -> dict:
    """The program's parameter-shaped tree -> benchmark layout."""
    run = p["layers"]["run_0"]
    out = {"embed": p["embed"]["embedding"],
           "final_norm": p["final_norm"]["scale"],
           "ln1": run["ln1"]["scale"], "ln2": run["ln2"]["scale"]}
    out.update({k: run["attn"][k] for k in _ATTN})
    out.update({k: run["mlp"][k] for k in _MLP})
    return out


# ------------------------------------------------------------ reference

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
    x = rms(h, lw["ln1"], eps)
    q = (mm("sd,dk->sk", x, lw["wq"], prec) + lw["bq"]).reshape(S, H, hd)
    k = (mm("sd,dk->sk", x, lw["wk"], prec) + lw["bk"]).reshape(S, KV, hd)
    v = (mm("sd,dk->sk", x, lw["wv"], prec) + lw["bv"]).reshape(S, KV, hd)
    q, k = _rope(q, dims["rope_theta"]), _rope(k, dims["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    sc = mm("qhd,khd->hqk", q, k, prec) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    o = mm("hqk,khd->qhd", p, v, prec).reshape(S, H * hd)
    h = h + mm("sk,kd->sd", o, lw["wo"], prec)
    x = rms(h, lw["ln2"], eps)
    g = mm("sd,df->sf", x, lw["w_gate"], prec)
    u = mm("sd,df->sf", x, lw["w_up"], prec)
    return h + mm("sf,fd->sd", jax.nn.silu(g) * u, lw["w_down"], prec)


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
    return rms(h, w["final_norm"], dims["norm_eps"])


def _head(h, w, prec):
    return mm("sd,vd->sv", h, w["embed"].astype(jnp.float32), prec)


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


# --------------------------------------------------------------- counts

def layer_matmul_params(d: dict) -> int:
    """Weights of one layer's matrix products (q, k, v, o, gate, up, down)."""
    D, H, KV, hd, F = (d[k] for k in ("d_model", "num_heads", "num_kv_heads",
                                      "head_dim", "d_ff"))
    return D * (H + 2 * KV) * hd + H * hd * D + 3 * D * F


def layer_params(d: dict) -> int:
    """All of one layer's weights: matrices, QKV biases, two norm scales."""
    H, KV, hd, D = (d[k] for k in ("num_heads", "num_kv_heads", "head_dim",
                                   "d_model"))
    return layer_matmul_params(d) + (H + 2 * KV) * hd + 2 * D


def total_params(d: dict) -> int:
    """Every parameter; the tied embedding counted once."""
    return (d["num_layers"] * layer_params(d) + d["d_model"]
            + d["vocab_size"] * d["d_model"])


def train_flops_per_token(d: dict, seq_len: int) -> float:
    """Forward and backward model operations per token (PaLM, arXiv:
    2204.02311 App. B): 6N plus the attention term 12*L*H*hd*S, halved
    because the causal mask leaves half the score matrix to compute.
    N is ``total_params``: the tied head once, the embedding gather not
    at all (it is the same matrix).  Recomputation does not count."""
    attn = 6 * d["num_layers"] * d["num_heads"] * d["head_dim"] * seq_len
    return 6.0 * total_params(d) + attn


def attention_flops(d: dict, context: int) -> float:
    """Scores and weighted sum for one query over ``context`` keys, all
    layers."""
    return 4.0 * d["num_layers"] * d["num_heads"] * d["head_dim"] * context


def serve_flops(d: dict, positions: int, context_sum: int,
                emitted: int) -> float:
    """Model operations of serving: every processed token (prompt or
    output) through the layers' matrix products, attention over the live
    context (``context_sum`` = sum of each token's position + 1), and
    the output head once per emitted token."""
    return (2.0 * d["num_layers"] * layer_matmul_params(d) * positions
            + attention_flops(d, context_sum)
            + 2.0 * d["vocab_size"] * d["d_model"] * emitted)


def kv_bytes_per_token(d: dict) -> int:
    """K and V of one token across all layers, bf16."""
    return d["num_layers"] * 2 * d["num_kv_heads"] * d["head_dim"] * BF16


def attend_cost(d: dict, context_sum: int, rows: int, kv_positions: int):
    """(operations, bytes) that an attention kernel of one query row per
    token needs, summed over all layers: ``rows`` query rows whose
    contexts add up to ``context_sum`` positions, over the K/V of
    ``kv_positions`` positions (each request's K/V up to its furthest
    row, read once), plus the queries and the outputs."""
    L, H, hd = d["num_layers"], d["num_heads"], d["head_dim"]
    flops = attention_flops(d, context_sum)
    q_and_out = 2 * rows * L * H * hd * BF16
    return flops, kv_bytes_per_token(d) * kv_positions + q_and_out
