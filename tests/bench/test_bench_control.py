"""The correctness comparison's control, at a size the CPU holds.

The control is the reference put in the program's place at float8 e4m3,
the precision step below the bf16 the configurations state.  Planted
through the harness's own ``run_cell`` (as ``bench/calibrate.py`` plants
it on the chip, against the committed limits), it has to come out as not
correct where the sound program, at the same size and seed, is correct.
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402

SEED = 2**31 + 3


def _control_fails_where_program_passes(cell, seconds):
    sound = bench_tiny.run(cell, seconds=seconds, seed=SEED)
    assert sound["correct"], sound["checks"]
    ctl = bench_tiny.run(cell, seconds=seconds, seed=SEED,
                         faults={"control": True})
    assert not ctl["correct"], ctl["checks"]
    return sound, ctl


def test_training_control_fails_and_program_passes():
    sound, ctl = _control_fails_where_program_passes(
        "train-qwen1.5-0.5b-1chip", 1.0)
    # the control fails the gradient's difference, as on the chip
    assert ctl["checks"]["grad_diff"]["value"] > \
        ctl["checks"]["grad_diff"]["limit"], ctl["checks"]


def test_serving_control_fails_and_program_passes():
    sound, _ = _control_fails_where_program_passes(
        "serve-qwen2-1.5b-chat-overload", 3.0)
    assert sound["failed"] == 0
