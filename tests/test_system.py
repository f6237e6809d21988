"""End-to-end system tests: the training driver really trains (loss goes
down), LSGD==CSGD through the whole stack, checkpoints resume exactly, and
the dry-run CLI lowers a production mesh in a subprocess."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SRC = os.path.join(REPO, "src")


def _run_module(args, devices=None, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    # the child never reaches for an accelerator the parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    if devices:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
    out = subprocess.run([sys.executable, "-m"] + args, capture_output=True,
                         text=True, env=env, timeout=timeout, cwd=REPO)
    return out


def test_train_driver_loss_decreases(tmp_path):
    out = _run_module(["repro.launch.train", "--arch", "qwen1.5-0.5b",
                       "--smoke", "--steps", "60", "--batch", "8",
                       "--seq", "64", "--base-lr", "0.1", "--schedule",
                       "const", "--log-every", "10"])
    assert out.returncode == 0, out.stderr[-3000:]
    losses = [float(l.split("loss")[1].split()[0])
              for l in out.stdout.splitlines() if l.startswith("step")]
    assert len(losses) >= 5
    assert losses[-1] < losses[0] - 0.05, \
        f"no learning: {losses[0]} -> {losses[-1]}"


def test_train_driver_lsgd_equals_csgd_run(tmp_path):
    """The whole driver stack, both sync modes, same data: same loss
    trajectory (paper §4.2 equivalence, end to end)."""
    outs = {}
    for mode in ("csgd", "lsgd"):
        r = _run_module(["repro.launch.train", "--arch", "mamba2-370m",
                         "--smoke", "--steps", "25", "--batch", "4",
                         "--seq", "32", "--schedule", "const",
                         "--base-lr", "0.2", "--sync-mode", mode,
                         "--log-every", "5"])
        assert r.returncode == 0, r.stderr[-3000:]
        outs[mode] = [l for l in r.stdout.splitlines()
                      if l.startswith("step")]
    for a, b in zip(outs["csgd"], outs["lsgd"]):
        la = float(a.split("loss")[1].split()[0])
        lb = float(b.split("loss")[1].split()[0])
        assert abs(la - lb) < 2e-3, (a, b)


def test_checkpoint_resume_exact(tmp_path):
    from repro.configs.base import get_config, smoke_variant
    from repro.models.model import build_model
    from repro.core import TrainerConfig, make_init_state, make_shardmap_step
    from repro.checkpoint import checkpoint
    from repro.launch.mesh import make_mesh
    from conftest import make_batch, tree_max_diff

    cfg = smoke_variant(get_config("qwen1.5-0.5b")).replace(
        num_layers=2, d_model=64, d_ff=128, vocab_size=64)
    model = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    tcfg = TrainerConfig(sync_mode="lsgd")
    step = jax.jit(make_shardmap_step(model, tcfg, lambda t: 0.05, mesh))
    batches = [make_batch(cfg, 4, 16, seed=s) for s in range(4)]

    s0 = make_init_state(model, tcfg)(jax.random.key(0))
    s = s0
    for b in batches[:2]:
        s, _ = step(s, b)
    checkpoint.save(str(tmp_path), s, int(s["step"]))
    for b in batches[2:]:
        s, _ = step(s, b)

    r = checkpoint.restore(str(tmp_path), jax.tree.map(jnp.zeros_like, s0))
    for b in batches[2:]:
        r, _ = step(r, b)
    assert tree_max_diff(s["params"], r["params"]) < 1e-7


@pytest.mark.slow
def test_dryrun_cli_single_pair(tmp_path):
    """The real 512-device production-mesh dry-run, one pair (slowish)."""
    out = _run_module(["repro.launch.dryrun", "--arch", "qwen1.5-0.5b",
                       "--shape", "decode_32k", "--mesh", "multi_pod",
                       "--out", str(tmp_path)], timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "[OK]" in out.stdout
    rec = json.load(open(tmp_path /
                         "qwen1.5-0.5b__decode_32k__mp__lsgd.json"))
    assert rec["status"] == "ok"
    assert rec["mesh_axes"] == {"pod": 2, "data": 16, "model": 16}
    assert rec["flops_per_device"] > 0
    assert rec["roofline"]["dominant"] in ("compute", "memory", "collective")


def test_quickstart_example_runs():
    out = _run_module(["examples.quickstart"], timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "equivalence" in out.stdout.lower()
