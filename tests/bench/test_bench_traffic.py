"""The traffic generator and the serving cell's kernel accounting, with
no engine and no chip."""
import json
import re
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import program, traffic  # noqa: E402

MIX = json.loads((ROOT / "bench" / "traffic" / "chat-overload.json")
                 .read_text())
FAMILY = program.family(json.loads(
    (ROOT / "bench" / "configs" / "qwen2-1.5b.json").read_text()))
QWEN2 = {"num_layers": 28, "d_model": 1536, "num_heads": 12,
         "num_kv_heads": 2, "head_dim": 128, "d_ff": 8960,
         "vocab_size": 151936}


def test_every_seed_offers_the_same_requests_at_the_same_times():
    a = traffic.schedule(MIX, 2**31 + 7, 51.0, 151936)
    b = traffic.schedule(MIX, 12345, 51.0, 151936)
    assert len(a) == len(b) == MIX["backlog"] + round(MIX["rate_per_s"] * 51.0)
    assert [(d.due_s, len(d.prompt), d.max_new_tokens) for d in a] == \
        [(d.due_s, len(d.prompt), d.max_new_tokens) for d in b]
    # the seed draws the tokens
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    same = traffic.schedule(MIX, 12345, 51.0, 151936)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(b, same))
    # the backlog is due as the window opens, the rest inside it
    k = MIX["backlog"]
    assert all(d.due_s == 0.0 for d in a[:k])
    assert a[-1].due_s == 51.0 and all(d.due_s > 0 for d in a[k:])


def test_tokens_interpolated_at_the_window_end():
    from bench import serve_cell
    marks = [(0.0, 0), (10.0, 0), (20.0, 30), (30.0, 45)]
    assert serve_cell._tokens_at(marks, 25.0) == 37.5
    assert serve_cell._tokens_at(marks, 20.0) == 30
    assert serve_cell._tokens_at(marks, 40.0) == 45


def test_lengths_follow_the_mix():
    lens = traffic.lognormal_lengths(MIX["prompt"], 101)
    assert lens[50] == MIX["prompt"]["median"]
    assert lens.min() >= MIX["prompt"]["min"]
    assert lens.max() == MIX["prompt"]["max"]


def test_flash_decode_paged_found_by_signature():
    from bench import serve_cell
    rx = re.compile(serve_cell.FLASH_DECODE_PAGED)
    paged = ("%checkpoint.9 = bf16[544,1,12,128]{3,2,1,0:T(8,128)(2,1)S(1)}"
             " custom-call(s32[544,256]{1,0:T(8,128)S(1)} %a, s32[544]{0} %b,"
             " bf16[544,1,12,128]{3,2,1,0} %c), custom_call_target="
             "\"tpu_custom_call\"")
    view = ("%custom-call.3 = bf16[32,12,128]{2,1,0} custom-call("
            "s32[32]{0} %p, bf16[32,12,128]{2,1,0} %q)")
    sampler = "%custom-call.5 = s32[288,1,1]{2,1,0} custom-call(f32[288,1,8])"
    assert rx.search(paged)
    assert not rx.search(view) and not rx.search(sampler)


def test_paged_cost_reads_each_request_once():
    from bench import serve_cell
    # request 7: prompt rows at positions 0-3; request 9: one decode row
    # at position 10; one padding row
    pos = np.array([0, 1, 2, 3, 10, 0])
    valid = np.array([1, 1, 1, 1, 1, 0])
    rid = np.array([7, 7, 7, 7, 9, 0])
    ops, nbytes = serve_cell._paged_cost(FAMILY, QWEN2, pos, valid, rid)
    assert (ops, nbytes) == FAMILY.attend_cost(QWEN2, 1 + 2 + 3 + 4 + 11, 5,
                                               4 + 11)
