"""A test-only model family: the qwen family with every leaf of the
benchmark layout renamed (``stub_`` in front), so that a harness which
named a qwen leaf anywhere outside the family module would fail on it.
Tests register it as ``bench.reference.stub``."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.reference import qwen  # noqa: E402

PREFIX = "stub_"


def _ours(w: dict) -> dict:
    return {PREFIX + k: v for k, v in w.items()}


def _qwens(w: dict) -> dict:
    return {k[len(PREFIX):]: v for k, v in w.items()}


PUBLISHED = qwen.PUBLISHED
check_published = qwen.check_published
dims = qwen.dims
leaf_norms = qwen.leaf_norms
train_flops_per_token = qwen.train_flops_per_token
serve_flops = qwen.serve_flops
attend_cost = qwen.attend_cost


def shapes(d):
    return _ours(qwen.shapes(d))


def generate(d, key, dtype):
    return _ours(qwen.generate(d, key, dtype))


def make_weights(d, seed, dtype="bfloat16"):
    return _ours(qwen.make_weights(d, seed, dtype))


def to_program(w):
    return qwen.to_program(_qwens(w))


def from_program(p):
    return _ours(qwen.from_program(p))


def train_steps(w, batches, d, optim, prec="f32", devices=None):
    losses, g, w3 = qwen.train_steps(_qwens(w), batches, d, optim, prec,
                                     devices)
    return losses, _ours(g), _ours(w3)


def logits_at(w32, tokens, idx, d, prec="f32"):
    return qwen.logits_at(_qwens(w32), tokens, idx, d, prec)
