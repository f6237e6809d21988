"""The qwen family's operation counts (reached through ``program.family``,
as the cells and readers reach them) against numbers worked out by hand,
the roofline's least time, and the peaks table."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import peaks, program  # noqa: E402
from bench.reference.common import roofline_time  # noqa: E402

fam = program.family(json.loads(
    (ROOT / "bench" / "configs" / "qwen2-1.5b.json").read_text()))

QWEN15 = {"num_layers": 24, "d_model": 1024, "num_heads": 16,
          "num_kv_heads": 16, "head_dim": 64, "d_ff": 2816,
          "vocab_size": 151936}
QWEN2 = {"num_layers": 28, "d_model": 1536, "num_heads": 12,
         "num_kv_heads": 2, "head_dim": 128, "d_ff": 8960,
         "vocab_size": 151936}


def test_qwen15_parameters():
    # per layer: 4 x 1024^2 attention + 3 x 1024 x 2816 MLP
    # + 3 x 1024 QKV bias + 2 x 1024 norm scales
    assert fam.layer_params(QWEN15) == 12_850_176
    # 24 layers + final norm + 151936 x 1024 tied embedding
    assert fam.total_params(QWEN15) == 463_987_712


def test_qwen15_train_flops_per_token():
    # 6N = 2,783,926,272; causal attention 6 x 24 x 16 x 64 x 2048
    assert fam.train_flops_per_token(QWEN15, 2048) == \
        6 * 463_987_712 + 301_989_888 == 3_085_916_160


def test_qwen2_parameters_and_kv_bytes():
    # q, o: 1536 x 1536; k, v: 1536 x 256; MLP 3 x 1536 x 8960
    assert fam.layer_matmul_params(QWEN2) == 46_792_704
    assert 28 * fam.layer_matmul_params(QWEN2) == 1_310_195_712
    # K and V of one token over all layers: 28 x 2 x 2 x 128 x 2 bytes
    assert fam.kv_bytes_per_token(QWEN2) == 28_672
    assert fam.total_params(QWEN2) == 1_543_714_304


def test_serve_flops_counts_head_per_emitted_token():
    one = fam.serve_flops(QWEN2, positions=1, context_sum=1, emitted=0)
    assert one == 2 * 1_310_195_712 + 4 * 28 * 12 * 128
    head = fam.serve_flops(QWEN2, 0, 0, 1) - fam.serve_flops(QWEN2, 0, 0, 0)
    assert head == 2 * 151936 * 1536


def test_attend_cost():
    # two rows of one request at positions 99 and 199: 100 + 200 keys,
    # the request's K/V up to position 199 read once
    ops, nbytes = fam.attend_cost(QWEN2, context_sum=300, rows=2,
                                    kv_positions=200)
    assert ops == 4 * 28 * 12 * 128 * 300
    assert nbytes == 28_672 * 200 + 2 * 2 * 28 * 12 * 128 * 2
    p = peaks.peaks_for("TPU v5 lite")
    t, bound = roofline_time(ops, nbytes, p)
    assert bound == "memory"
    assert t == pytest.approx(nbytes / 819e9)


def test_peaks_table():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["flops_bf16"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
