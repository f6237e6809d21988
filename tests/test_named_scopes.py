"""Device work carries stable names: the LSGD phases and the model's
parts are ``jax.named_scope``s, which every HLO op keeps in its
``op_name`` metadata (and the device trace with it), and each Pallas
call is named after its kernel (interpret mode here, so the kernel
body's ops carry the name on their path)."""
import re

import jax
import numpy as np
import pytest

from conftest import make_batch
from repro.configs.base import get_config, smoke_variant
from repro.core import TrainerConfig, make_init_state, make_shardmap_step
from repro.launch.mesh import make_mesh
from repro.models.model import build_model
from repro.optim.sgd import OptimConfig


def _op_names(hlo_text):
    return set(re.findall(r'op_name="([^"]*)"', hlo_text))


def _has_scope(paths, scope):
    """Some op's name-stack path holds ``scope`` as a component, bare or
    inside a transformation (``transpose(jvp(attend))``)."""
    rx = re.compile(r"(^|[/(])" + re.escape(scope) + r"($|[/)])")
    return any(rx.search(p) for p in paths)


def _train_step_text(sync_mode, attn_impl, **tcfg_kw):
    cfg = smoke_variant(get_config("qwen1.5-0.5b")).replace(
        attn_impl=attn_impl, num_layers=2, loss_chunk=16)
    model = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    tcfg = TrainerConfig(sync_mode=sync_mode, optim=OptimConfig(),
                         **tcfg_kw)
    state = make_init_state(model, tcfg)(jax.random.key(0))
    step = make_shardmap_step(model, tcfg, lambda t: 0.01, mesh)
    return jax.jit(step).lower(state, make_batch(cfg, seq=32)
                               ).compile().as_text()


@pytest.mark.parametrize("attn_impl", ["blocked", "naive"])
def test_lsgd_step_ops_carry_phase_and_model_scopes(attn_impl):
    # a bf16 pending buffer makes the pending write a cast (an f32 one
    # aliases the synced gradient and leaves no op of its own)
    paths = _op_names(_train_step_text("lsgd", attn_impl,
                                       pending_dtype="bfloat16"))
    for scope in ("lsgd.apply_pending", "lsgd.fwd_bwd", "lsgd.sync",
                  "lsgd.pending_write", "attend", "mlp", "lm_head_loss"):
        assert _has_scope(paths, scope), (scope, sorted(paths)[:20])
    assert not _has_scope(paths, "lsgd.update")
    # the attention's backward keeps its scope under the transformation
    assert any("attend" in p and "transpose" in p for p in paths)


def test_csgd_step_updates_under_lsgd_update():
    paths = _op_names(_train_step_text("csgd", "naive"))
    assert _has_scope(paths, "lsgd.update")
    assert not _has_scope(paths, "lsgd.apply_pending")
    assert not _has_scope(paths, "lsgd.pending_write")


@pytest.fixture(scope="module")
def tiny_engine():
    from repro.serve import Engine, EngineConfig
    cfg = smoke_variant(get_config("qwen2-1.5b")).replace(
        mtp_depth=0, num_layers=2, d_model=64, d_ff=128, vocab_size=256,
        num_heads=2, num_kv_heads=2, head_dim=32, attn_impl="pallas")
    model = build_model(cfg)
    eng = Engine(model, model.init(jax.random.key(0)),
                 EngineConfig(max_batch=2, block_size=8, num_blocks=17,
                              max_seq_len=32, prefill_chunk=8,
                              prefill_token_budget=8,
                              steps_per_dispatch=4))
    return eng


def _engine_paths(eng, fn, rows):
    meta = np.zeros((6, rows), np.int32)
    tables = eng.kv.table_array([None] * rows)
    args = ((eng.params, eng.cache, eng._slot_buf,
             np.zeros((rows, 1), np.int32), tables, meta)
            if fn is eng._step_fn else
            (eng.params, eng.cache, eng._slot_buf, tables, meta))
    return _op_names(fn.lower(*args).compile().as_text())


def test_paged_step_scopes_and_kernel_names(tiny_engine):
    paths = _engine_paths(tiny_engine, tiny_engine._step_fn, 2)
    for scope in ("paged_step", "attend", "mlp", "sample",
                  "flash_decode_paged", "greedy_sample"):
        assert _has_scope(paths, scope), scope
    # the paged attend kernel runs inside the attention scope
    assert any(re.search(r"attend/flash_decode_paged", p) for p in paths)


def test_paged_decode_loop_scopes_and_kernel_names(tiny_engine):
    paths = _engine_paths(tiny_engine, tiny_engine._loop_fn, 2)
    for scope in ("paged_decode_loop", "gather_view", "scatter_view",
                  "attend", "sample", "decode_view_attend",
                  "greedy_sample"):
        assert _has_scope(paths, scope), scope
