"""Operations and bytes that the algorithm needs, from shapes alone.

Kept with the benchmark so that no change to the program can move the
yardstick.  ``dims`` is the dict ``program.dims_of`` gives (layers,
widths, heads, vocabulary).  Multiply-adds count as two operations.
bf16 operands are 2 bytes.
"""
from __future__ import annotations

BF16 = 2


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


def roofline_time(flops: float, nbytes: float, peaks: dict):
    """(least seconds, which bound binds)."""
    tc = flops / peaks["flops_bf16"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
