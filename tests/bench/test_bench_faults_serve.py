"""The serving cell's harness on the CPU at smoke size: a sound run is
correct, a served token altered where it is produced is not, a compile
inside the window ends the run, and no TPU means no result."""
import subprocess
import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402

CELL = "serve-qwen2-1.5b-chat-overload"


def test_sound_run_is_correct():
    out = bench_tiny.run(CELL, seconds=3.0)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-2:] == ["checks", "_info"]


def test_overload_counts_tokens_in_window():
    out = bench_tiny.run(CELL, seconds=3.0)
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    info = out["_info"]
    assert out["metrics"]["serve_tokens_per_s"]["value"] == pytest.approx(
        info["tokens_in_window"] / 3.0)
    assert info["tokens_in_window"] <= info["tokens_to_last_fetch"]
    # unfinished requests are checked on the tokens they were served
    assert info["served_requests"] >= info["finished"]
    assert info["sample_served_tokens"] > 0


def test_altered_token_is_not_correct():
    out = bench_tiny.run(CELL, seconds=3.0, faults={"alter_token": True})
    assert not out["correct"], out["checks"]


def test_compile_inside_window_ends_the_run():
    from bench import harness, program, serve_cell, traffic
    ov = bench_tiny.overrides(CELL)
    _, _, cfg, mix, _ = harness.load_cell(CELL)
    cfg = {**cfg, **ov["config"]}
    mix = {**mix, **ov["traffic"]}
    program.import_program()
    from repro.models.model import build_model
    from repro.serve import Engine, EngineConfig
    mc = program.model_config(cfg)
    fam = program.family(cfg)
    params = fam.to_program(fam.make_weights(fam.dims(mc), 1, mc.param_dtype))
    engine = Engine(build_model(mc), params, EngineConfig(**cfg["engine"]))
    plan = traffic.schedule(mix, 1, 1.0, mc.vocab_size)     # no warmup()
    with pytest.raises(SystemExit, match="compiles inside the window"):
        serve_cell.serve(engine, plan, 1.0, harness.Tracer(False),
                         time.perf_counter())


def test_no_tpu_means_no_result():
    root = Path(__file__).resolve().parents[2]
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=root, capture_output=True,
        text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(root)})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
