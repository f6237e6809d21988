"""Expert-parallel shard_map MoE vs the portable scatter path (the §Perf B
optimization): forward and gradients must agree when capacity is ample."""
import json
import os
import subprocess
import sys

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


def test_ep_matches_scatter_forward_and_grad():
    out = _run(r"""
import dataclasses, json, jax, jax.numpy as jnp
from repro import sharding
from repro.configs.base import get_config, smoke_variant
from repro.models import moe
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = smoke_variant(get_config("dbrx-132b"))
cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, num_experts=4,
                                          capacity_factor=16.0))
p = moe.init_moe(jax.random.key(0), cfg)
x = jax.random.normal(jax.random.key(1), (8, 16, cfg.d_model))

def run_ep(p_, x_):
    sharding.set_active_mesh(mesh)
    try:
        return moe.apply_moe(p_, x_, cfg)
    finally:
        sharding.set_active_mesh(None)

y0, _ = moe.apply_moe_scatter(p, x, cfg)
y1, _ = jax.jit(run_ep)(p, x)
g0 = jax.grad(lambda a, b: moe.apply_moe_scatter(a, b, cfg)[0].sum())(p, x)
g1 = jax.jit(jax.grad(lambda a, b: run_ep(a, b)[0].sum()))(p, x)
rel = max(float(jnp.max(jnp.abs(u - v)) / (jnp.max(jnp.abs(u)) + 1e-9))
          for u, v in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)))
print(json.dumps({"fwd": float(jnp.max(jnp.abs(y0 - y1))), "grad": rel}))
""")
    res = json.loads(out.strip().splitlines()[-1])
    assert res["fwd"] < 2e-3, res
    assert res["grad"] < 1e-5, res


def test_ep_deepseek_family_with_shared_expert():
    out = _run(r"""
import dataclasses, json, jax, jax.numpy as jnp
from repro import sharding
from repro.configs.base import get_config, smoke_variant
from repro.models import moe
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = smoke_variant(get_config("deepseek-v3-671b"))
cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, num_experts=4,
                                          num_experts_per_tok=2,
                                          capacity_factor=16.0,
                                          first_k_dense=0))
p = moe.init_moe(jax.random.key(0), cfg)
x = jax.random.normal(jax.random.key(1), (4, 8, cfg.d_model))
y0, _ = moe.apply_moe_scatter(p, x, cfg)
sharding.set_active_mesh(mesh)
try:
    y1, _ = jax.jit(lambda a, b: moe.apply_moe(a, b, cfg))(p, x)
finally:
    sharding.set_active_mesh(None)
print(json.dumps({"fwd": float(jnp.max(jnp.abs(y0 - y1)))}))
""")
    res = json.loads(out.strip().splitlines()[-1])
    assert res["fwd"] < 2e-3, res


def test_ep_fallback_without_mesh():
    """No active mesh -> portable scatter path, single device."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.configs.base import get_config, smoke_variant
    from repro.models import moe
    cfg = smoke_variant(get_config("dbrx-132b"))
    p = moe.init_moe(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 8, cfg.d_model))
    y, aux = moe.apply_moe(p, x, cfg)
    assert y.shape == x.shape
