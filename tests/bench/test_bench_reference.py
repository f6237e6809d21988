"""The qwen family's reference (reached through ``program.family``, as
the cells reach it) follows the program's dense Qwen block at smoke size.

Same seeded weights on both sides, float32 everywhere, on the CPU: the
reference's logits (serving) and loss and gradient (training) against
the repo's model, so the reference is known to compute the published
block before the chip relies on it.
"""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import program  # noqa: E402

program.import_program()
from repro.configs.base import get_config  # noqa: E402
from repro.models import transformer  # noqa: E402
from repro.models.model import build_model  # noqa: E402

SMALL = {"num_layers": 2, "d_model": 64, "num_heads": 4, "head_dim": 16,
         "d_ff": 128, "vocab_size": 256, "param_dtype": "float32",
         "compute_dtype": "float32", "attn_impl": "naive", "remat": False}
ARCHS = {"qwen1.5-0.5b": 4, "qwen2-1.5b": 2}       # kv heads (MHA, GQA)


def _family(arch):
    cfg = json.loads((ROOT / "bench" / "configs" / f"{arch}.json").read_text())
    return program.family(cfg)


def _small(arch):
    mc = get_config(arch).replace(num_kv_heads=ARCHS[arch], **SMALL)
    return mc, build_model(mc), _family(arch).dims(mc)


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_logits_match_program(arch):
    mc, model, dims = _small(arch)
    ref = _family(arch)
    w = ref.make_weights(dims, 2**31 + 7, "float32")
    params = ref.to_program(w)
    program.check_layout(model, params)
    toks = jax.random.randint(jax.random.key(1), (1, 24), 0, mc.vocab_size)
    with jax.default_matmul_precision("highest"):
        want = transformer.forward(params, {"tokens": toks}, mc)[0][0]
    got = ref.logits_at(w, toks[0], jnp.arange(24), dims)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_loss_and_gradient_match_program(arch):
    mc, model, dims = _small(arch)
    ref = _family(arch)
    w = ref.make_weights(dims, 5, "float32")
    params = ref.to_program(w)
    toks = jax.random.randint(jax.random.key(2), (3, 20), 0, mc.vocab_size)
    with jax.default_matmul_precision("highest"):
        (loss, _), g = jax.value_and_grad(model.loss, has_aux=True)(
            params, {"tokens": toks})
    r_loss, r_g = ref.loss_and_grad(w, np.asarray(toks), dims, chunk=8)
    assert r_loss == pytest.approx(float(loss), rel=1e-5)
    g = ref.from_program(g)
    for k in ref.LEAVES:
        np.testing.assert_allclose(np.asarray(r_g[k]), np.asarray(g[k]),
                                   rtol=2e-3, atol=2e-6, err_msg=k)


def test_control_is_coarser_than_reference():
    mc, model, dims = _small("qwen2-1.5b")
    ref = _family("qwen2-1.5b")
    w = ref.make_weights(dims, 9, "float32")
    toks = jax.random.randint(jax.random.key(3), (32,), 0, mc.vocab_size)
    idx = jnp.arange(32)
    hi = np.asarray(ref.logits_at(w, toks, idx, dims))
    lo = np.asarray(ref.logits_at(w, toks, idx, dims, "fp8"))
    err = np.abs(lo - hi).max() / np.abs(hi).max()
    assert 1e-3 < err < 0.5


def test_weights_depend_on_every_bit_of_a_large_seed():
    dims = _small("qwen2-1.5b")[2]
    ref = _family("qwen2-1.5b")
    a = ref.make_weights(dims, 2**31 + 1)["wq"]
    b = ref.make_weights(dims, 1)["wq"]
    c = ref.make_weights(dims, 2**31 + 1)["wq"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(a), np.asarray(c))
    assert a.dtype == jnp.bfloat16
