"""One generator for every serving mix; a mix is a data file of parameters.

    {"kind": "serve_open_loop", "rate_per_s": 0.5, "backlog": 4,
     "prompt": {"median": 512, "sigma": 1.0, "min": 16, "max": 3072},
     "output": {"median": 128, "sigma": 0.8, "min": 8, "max": 1024}}

Arrivals are open loop (Poisson gaps), lengths lognormal and clipped.
Gaps and lengths sit at evenly spaced quantiles of their distributions,
in one order that is the same for every seed; the seed draws only the
prompt tokens (and, in the cells, the weights).  So every seed offers
the same requests at the same times, and runs differ by their tokens,
not by how much work comes when.  ``backlog`` requests (default 0) are
due as the window opens, as if it were taken from a run already above
capacity; ``n = rate * seconds`` more arrive inside the window, the last
one at its end.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


@dataclass
class Due:
    rid: int
    due_s: float            # offset from the window's start
    prompt: np.ndarray
    max_new_tokens: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def poisson_offsets(rate: float, seconds: float, rng) -> np.ndarray:
    """Arrival offsets of ``round(rate * seconds)`` requests: exponential
    gaps at evenly spaced quantiles, shuffled, scaled so the last
    request is due at ``seconds``."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-_quantiles(n)) / rate
    gaps = rng.permutation(gaps)
    return np.cumsum(gaps) * (seconds / gaps.sum())


ORDER = 7                # draws the one order of gaps and lengths


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> List[Due]:
    if mix["kind"] != "serve_open_loop":
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    order = np.random.default_rng(ORDER)
    due = np.concatenate([np.zeros(int(mix.get("backlog", 0))),
                          poisson_offsets(float(mix["rate_per_s"]), seconds,
                                          order)])
    n = len(due)
    plen = order.permutation(lognormal_lengths(mix["prompt"], n))
    olen = order.permutation(lognormal_lengths(mix["output"], n))
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
    return [Due(i, float(due[i]),
                rng.integers(0, vocab, int(plen[i]), dtype=np.int32),
                int(olen[i])) for i in range(n)]
