"""Smoke-size stand-ins for the benchmark's cells, for CPU tests.

The harness runs as it does on the chip, but with ``require_tpu=False``
and the model, engine and traffic cut to a size the CPU holds.
"""
import contextlib
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

# limits for the smoke sizes, set as the cells' are (bench/calibrate.py):
# the bf16 program against the f32 reference reads up to 5e-4 (step-1
# loss), 1e-3 (loss), 4e-3 (gradient norm), 0.018 (gradient difference)
# and 7e-3 (change) here; the float8 control 2.7e-3 on the step-1 loss
# and 0.19 on the gradient difference; a planted fault 0.1 or more;
# served tokens 6e-3
TINY_LIMITS = {"loss1_gap": 0.0015, "loss_gap": 0.002, "grad_gap": 0.008,
               "grad_diff": 0.06, "change_gap": 0.012, "logit_gap": 0.05}


def overrides(cell, faults=None, limits=None, config=None, traffic=None):
    """The smoke-size overrides of ``cell``; ``config`` and ``traffic``
    add to (and win over) the configuration's and the mix's."""
    _, _, cfg, mix, _ = harness.load_cell(cell)
    po = dict(cfg["program_overrides"])
    po.update(num_layers=2, d_model=128, d_ff=256, vocab_size=512)
    ov = {"config": {"check_published": False, "program_overrides": po},
          "limits": {**TINY_LIMITS, **(limits or {})},
          "faults": faults or {}}
    if mix["kind"] == "train":
        po.update(num_heads=4, num_kv_heads=4, head_dim=32)
        ov["traffic"] = {"seq_len": 64, "seqs_per_chip": 2}
    else:
        po.update(num_heads=1, num_kv_heads=1, head_dim=128)
        engine = {"max_batch": 4, "max_seq_len": 256, "block_size": 16,
                  "num_blocks": 65, "steps_per_dispatch": 8,
                  "prefill_chunk": 32, "prefill_token_budget": 64,
                  "temperature": 0.0}
        ov["config"]["engine"] = engine
        ov["traffic"] = {"rate_per_s": 2.0,
                         "prompt": {"median": 40, "sigma": 1.0, "min": 4,
                                    "max": 150},
                         "output": {"median": 12, "sigma": 0.8, "min": 2,
                                    "max": 64}}
    ov["config"].update(config or {})
    ov["traffic"].update(traffic or {})
    return ov


@contextlib.contextmanager
def no_compile_cache():
    """Keep the persistent compile cache off while the harness runs."""
    harness.program.import_program()
    from repro.launch import compile_cache
    keep = compile_cache.enable_compile_cache
    compile_cache.enable_compile_cache = lambda: None
    try:
        yield
    finally:
        compile_cache.enable_compile_cache = keep


def run(cell, seconds=2.0, seed=2**31 + 99, trace=False, **kw):
    with no_compile_cache():
        return harness.run_cell(cell, seed, seconds, trace,
                                time.perf_counter(), require_tpu=False,
                                overrides=overrides(cell, **kw))
