"""A ``--trace 1`` run goes through on the CPU at smoke size: host spans,
the traced window and the reduction, with the device metrics left out
(no chip, no device planes) and the host's per-layer metrics reported."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402


@pytest.mark.parametrize("cell,host_metric", [
    ("train-qwen1.5-0.5b-1chip", "input_wait_ms.train"),
    ("serve-qwen2-1.5b-chat-overload", "decode_occupancy.overload")])
def test_traced_run(cell, host_metric):
    out = bench_tiny.run(cell, seconds=4.0, trace=True)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {host_metric}
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
