"""Every main-path Pallas kernel compiles with Mosaic for a TPU v5e.

Interpret mode (how the kernel tests run on CPU) accepts tilings, VMEM
layouts and 1-D values that the TPU compiler refuses.  These tests
compile each kernel-module entry point with ``interpret=False`` for a
described (not attached) ``v5e:2x2`` chip, at the padded shapes
``repro.kernels.ops`` produces for real model widths, and check that the
executable holds the compiled kernel (``tpu_custom_call``), named after
the kernel as the device trace will show it.  Nothing runs; a compile
that passes here is not a chip run.

The topology is described inside a fixture (never at import), so every
pytest worker collects the same tests and only the worker given this
file loads the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_view as dv
from repro.kernels import flash_attention as fa
from repro.kernels import flash_decode as fd
from repro.kernels import fused_update as fu
from repro.kernels import mla_decode as mla
from repro.kernels import sampling as sp
from repro.kernels import slot_state as ss
from repro.kernels import ssd_chunk as sc

bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
ROWS = 8                      # decode rows
CTX = 2048                    # context per row
BS = 16                       # tokens per KV block
NB = ROWS * CTX // BS + 1     # block pool incl. trash block 0
VIEW = 2176                   # CTX + 1 trash slot, padded to the 128 kv block
VOCAB = 151936                # qwen2 / qwen1.5 vocabulary


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _case(fn, *shapes):
    return fn, shapes


# name -> (kernel-module call, operand (shape, dtype) list)
CASES = {
    # qwen2-1.5b: 12 query / 2 kv heads, head_dim 128, bf16
    "decode_view_attend": _case(
        lambda q, k, v, p: dv.decode_view_attend_bhd(
            q, k, v, p, scale=128 ** -0.5, block_kv=128, interpret=False),
        ((ROWS, 12, 128), bf16), ((ROWS, VIEW, 2, 128), bf16),
        ((ROWS, VIEW, 2, 128), bf16), ((ROWS,), i32)),
    "flash_decode_paged/decode": _case(
        lambda q, k, v, bt, p: fd.flash_decode_paged_bhd(
            q, k, v, bt, p, interpret=False),
        ((ROWS, 1, 12, 128), bf16), ((NB, BS, 2, 128), bf16),
        ((NB, BS, 2, 128), bf16), ((ROWS, CTX // BS), i32), ((ROWS,), i32)),
    "flash_decode_paged/prefill_chunk": _case(
        lambda q, k, v, bt, p: fd.flash_decode_paged_bhd(
            q, k, v, bt, p, interpret=False),
        ((1, 64, 12, 128), bf16), ((NB, BS, 2, 128), bf16),
        ((NB, BS, 2, 128), bf16), ((1, CTX // BS), i32), ((1,), i32)),
    # the serve cell's mixed step: 32 decode rows and 512 prompt tokens
    # as width-1 rows, 256-block tables (max_seq_len 4096), 4097 blocks
    "flash_decode_paged/mixed": _case(
        lambda q, k, v, bt, p: fd.flash_decode_paged_bhd(
            q, k, v, bt, p, interpret=False),
        ((544, 1, 12, 128), bf16), ((4097, BS, 2, 128), bf16),
        ((4097, BS, 2, 128), bf16), ((544, 256), i32), ((544,), i32)),
    # recurrentgemma-2b's local attention (10 query / 1 kv head of 256,
    # window 2048) at a mixed step of 288 width-1 rows
    "flash_decode_paged/window": _case(
        lambda q, k, v, bt, p: fd.flash_decode_paged_bhd(
            q, k, v, bt, p, window=2048, interpret=False),
        ((288, 1, 10, 256), bf16), ((4097, BS, 1, 256), bf16),
        ((4097, BS, 1, 256), bf16), ((288, 256), i32), ((288,), i32)),
    # DeepSeek-V3 absorbed MLA: kv_lora 512, rope 64 (lane-padded to
    # 128), 128 heads
    "mla_decode_views": _case(
        lambda ql, qr, c, k, p: mla.mla_views_attend(
            ql, qr, c, k, p, scale=192 ** -0.5, block=128,
            interpret=False),
        ((ROWS, 1, 128, 512), bf16), ((ROWS, 1, 128, 128), bf16),
        ((ROWS, VIEW, 512), bf16), ((ROWS, VIEW, 128), bf16),
        ((ROWS,), i32)),
    "mla_decode_paged": _case(
        lambda ql, qr, c, k, bt, p: mla.mla_paged_attend(
            ql, qr, c, k, bt, p, scale=192 ** -0.5, interpret=False),
        ((ROWS, 1, 128, 512), bf16), ((ROWS, 1, 128, 128), bf16),
        ((NB, BS, 512), bf16), ((NB, BS, 128), bf16),
        ((ROWS, CTX // BS), i32), ((ROWS,), i32)),
    # mamba2-370m slot pools: SSD state 32 heads x 64 x d_state 128, and
    # the conv tail 3 x (d_inner 2048 + 2 x 128)
    "slot_gather/state": _case(
        lambda pool, s, fr: ss.slot_gather_rows(pool, s, fr,
                                                interpret=False),
        ((ROWS + 3, 32 * 64 * 128), f32), ((ROWS,), i32), ((ROWS,), i32)),
    "slot_scatter/state": _case(
        lambda pool, s, v: ss.slot_scatter_rows(pool, s, v,
                                                interpret=False),
        ((ROWS + 3, 32 * 64 * 128), f32), ((ROWS,), i32),
        ((ROWS, 32 * 64 * 128), f32)),
    "slot_gather/conv": _case(
        lambda pool, s, fr: ss.slot_gather_rows(pool, s, fr,
                                                interpret=False),
        ((ROWS + 3, 3 * 2304), f32), ((ROWS,), i32), ((ROWS,), i32)),
    "sample/greedy": _case(
        lambda lg: sp.greedy_sample(lg, interpret=False),
        ((ROWS, VOCAB), f32)),
    "sample/gumbel": _case(
        lambda lg, g: sp.gumbel_sample(lg, g, temperature=0.8,
                                       interpret=False),
        ((ROWS, VOCAB), f32), ((ROWS, VOCAB), f32)),
    "sample/top_k": _case(
        lambda lg, g: sp.gumbel_sample(lg, g, temperature=0.8, top_k=40,
                                       interpret=False),
        ((ROWS, VOCAB), f32), ((ROWS, VOCAB), f32)),
    # bf16 params with f32 momentum: one (8192, 128) slab
    "fused_sgd_update": _case(
        lambda w, m, g, s: fu.fused_sgd_update_2d(
            w, m, g, s, momentum=0.9, weight_decay=1e-4, nesterov=False,
            interpret=False),
        ((8192, 128), bf16), ((8192, 128), f32), ((8192, 128), f32),
        ((1, 2), f32)),
    # a 2k-token prefill at qwen2-1.5b's heads
    "flash_attention": _case(
        lambda q, k, v: fa.flash_attention_bhsd(q, k, v, causal=True,
                                                interpret=False),
        ((1, 12, CTX, 128), bf16), ((1, 2, CTX, 128), bf16),
        ((1, 2, CTX, 128), bf16)),
    # mamba2-370m SSD chunk: 256-token chunks, 32 heads, p 64 -> 128,
    # d_state 128
    "ssd_chunk": _case(
        lambda x, dt, da, b, c: sc.ssd_chunk_bhcp(x, dt, da, b, c,
                                                  interpret=False),
        ((4, 32, 256, 128), bf16), ((4, 32, 256), f32),
        ((4, 32, 256), f32), ((4, 32, 256, 128), bf16),
        ((4, 32, 256, 128), bf16)),
}


# case -> the kernel name its pallas_call carries
KERNEL_OF = {
    "decode_view_attend": "decode_view_attend",
    "flash_decode_paged/decode": "flash_decode_paged",
    "flash_decode_paged/prefill_chunk": "flash_decode_paged",
    "flash_decode_paged/mixed": "flash_decode_paged",
    "flash_decode_paged/window": "flash_decode_paged",
    "mla_decode_views": "mla_views_attend",
    "mla_decode_paged": "mla_paged_attend",
    "slot_gather/state": "slot_gather_rows",
    "slot_scatter/state": "slot_scatter_rows",
    "slot_gather/conv": "slot_gather_rows",
    "sample/greedy": "greedy_sample",
    "sample/gumbel": "gumbel_sample",
    "sample/top_k": "topk_gumbel_sample",
    "fused_sgd_update": "fused_sgd_update",
    "flash_attention": "flash_attention",
    "ssd_chunk": "ssd_chunk",
}


@pytest.fixture(scope="module")
def compiled_text(one_chip):
    """The v5e-compiled HLO text of a case, compiled once per module."""
    texts = {}

    def get(name):
        if name not in texts:
            fn, shapes = CASES[name]
            args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                    for s, dt in shapes]
            texts[name] = jax.jit(fn).lower(*args).compile().as_text()
        return texts[name]

    return get


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(compiled_text, name):
    assert "tpu_custom_call" in compiled_text(name)


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_carries_its_name(compiled_text, name):
    """The Mosaic custom call is the instruction the device trace names
    the kernel by: it is called after the kernel, and its op_name ends
    in the kernel's name."""
    kernel = KERNEL_OF[name]
    calls = [line for line in compiled_text(name).splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls
    for line in calls:
        assert re.search(r"%" + kernel + r"(\.\d+)? = ", line), line[:120]
        assert f'/{kernel}/pallas_call"' in line
