"""Distributed-trainer integration tests.  These need >1 device, so each
spawns a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count
(the parent process keeps its single-device view)."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    # the child never reaches for an accelerator the parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return out.stdout


COMMON = r"""
import jax, jax.numpy as jnp, numpy as np, json, re
from repro.configs.base import get_config, smoke_variant
from repro.models.model import build_model
from repro.core import (TrainerConfig, Topology, make_init_state,
                        make_shardmap_step, make_finalize)
from repro.core import virtual
from repro.optim.sgd import OptimConfig
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = smoke_variant(get_config("qwen1.5-0.5b")).replace(
    num_layers=2, d_model=64, d_ff=128, vocab_size=64)
m = build_model(cfg)
ocfg = OptimConfig()
lr_fn = lambda t: 0.05
T, B, S = 3, 16, 12
rng = jax.random.key(3)
batches = [{"tokens": jax.random.randint(jax.random.fold_in(rng, t),
                                         (B, S), 0, 64)} for t in range(T)]

def run_mode(mode, intra=None):
    tcfg = TrainerConfig(sync_mode=mode, optim=ocfg,
                         topology=Topology(intra_group_size=intra))
    state = make_init_state(m, tcfg)(jax.random.key(0))
    step = jax.jit(make_shardmap_step(m, tcfg, lr_fn, mesh))
    for t in range(T):
        state, (loss, met) = step(state, batches[t])
    state = jax.jit(make_finalize(m, tcfg, lr_fn))(state)
    return state["params"], float(loss)

def maxdiff(a, b):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
"""


def test_all_sync_modes_match_reference():
    out = _run(COMMON + r"""
p_ref, _ = virtual.csgd(m, m.init(jax.random.key(0)),
                        [virtual.partition_minibatch(b, 4) for b in batches],
                        lr_fn, ocfg)
results = {}
for mode in ["csgd", "lsgd", "lsgd_eager", "lsgd_rsag"]:
    p, loss = run_mode(mode)
    results[mode] = maxdiff(p, p_ref)
# intra-group subdivision (paper's 4-GPU nodes inside the data axis)
p, _ = run_mode("lsgd", intra=1)
results["lsgd_subgroup"] = maxdiff(p, p_ref)
print(json.dumps(results))
""")
    res = json.loads(out.strip().splitlines()[-1])
    for mode, diff in res.items():
        assert diff < 1e-5, f"{mode} diverged from reference: {diff}"


def test_lsgd_compressed_close_but_not_exact():
    out = _run(COMMON + r"""
p_ref, _ = run_mode("csgd")
p_c, _ = run_mode("lsgd_compressed")
print(json.dumps({"diff": maxdiff(p_c, p_ref)}))
""")
    diff = json.loads(out.strip().splitlines()[-1])["diff"]
    assert diff < 1e-2     # bf16 cross-pod payload: bounded drift
    # (not asserting > 0: at these scales bf16 may round-trip exactly)


def test_lsgd_hlo_has_two_phase_collectives():
    """The paper's signature: intra-group all-reduce + inter-group
    all-reduce with disjoint replica groups (vs CSGD's single phase)."""
    out = _run(COMMON + r"""
import collections
def groups_of(mode):
    tcfg = TrainerConfig(sync_mode=mode, optim=ocfg)
    state = make_init_state(m, tcfg)(jax.random.key(0))
    step = make_shardmap_step(m, tcfg, lr_fn, mesh)
    txt = jax.jit(step).lower(state, batches[0]).compile().as_text()
    ars = re.findall(r'all-reduce\([^\n]*replica_groups=(\{\{[0-9,{} ]*\}\})',
                     txt)
    return set(ars)
g_lsgd = groups_of("lsgd")
g_csgd = groups_of("csgd")
print(json.dumps({"lsgd": sorted(g_lsgd), "csgd": sorted(g_csgd)}))
""")
    res = json.loads(out.strip().splitlines()[-1])
    # lsgd must contain an intra-pod (data-axis) group {0,2} style AND an
    # inter-pod group {0,4} style; csgd must have the flat {0,2,4,6}
    lsgd = " ".join(res["lsgd"])
    csgd = " ".join(res["csgd"])
    assert "{{0,2}" in lsgd and "{{0,4}" in lsgd, res["lsgd"]
    assert "{{0,2,4,6}" in csgd, res["csgd"]


def test_pjit_fsdp_path_runs():
    out = _run(COMMON + r"""
from repro.core import make_pjit_step
from repro.core.trainer import state_pspecs
from repro import sharding as shd
from jax.sharding import NamedSharding
tcfg = TrainerConfig(sync_mode="lsgd", fsdp=True)
state = make_init_state(m, tcfg)(jax.random.key(0))
specs = state_pspecs(jax.eval_shape(lambda: state), fsdp=True)
specs = shd.filter_spec_for_mesh(specs, mesh)
specs = shd.legalize_pspecs(state, specs, mesh)
shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                         is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
state = jax.device_put(state, shardings)
step = jax.jit(make_pjit_step(m, tcfg, lr_fn))
for t in range(T):
    state, (loss, metrics) = step(state, batches[t])
state = jax.jit(make_finalize(m, tcfg, lr_fn))(state)
p_ref, _ = virtual.csgd(m, m.init(jax.random.key(0)),
                        [virtual.partition_minibatch(b, 4) for b in batches],
                        lr_fn, ocfg)
print(json.dumps({"diff": maxdiff(state["params"], p_ref),
                  "loss": float(loss)}))
""")
    res = json.loads(out.strip().splitlines()[-1])
    assert res["diff"] < 1e-5, res
