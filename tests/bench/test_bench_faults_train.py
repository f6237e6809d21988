"""The training cell's ``correct`` on the CPU at smoke size: a sound run
passes, and each fault a one-chip training cell can have, planted under
the timed path, fails it."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402

CELL = "train-qwen1.5-0.5b-1chip"


def test_sound_run_is_correct():
    out = bench_tiny.run(CELL)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(out)[-2:] == ["checks", "_info"]


@pytest.mark.parametrize("fault", ["frozen", "half_batch"])
def test_fault_is_not_correct(fault):
    out = bench_tiny.run(CELL, faults={"step": fault})
    assert not out["correct"], out["checks"]
