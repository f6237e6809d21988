"""Tensor-parallel engine replicas: a multi-device slice serves ONE
sharded engine, and its output must be token-for-token identical to the
single-device engine — which the rest of the suite pins to sequential
dense decode.

Equivalence is exercised per family axis (qwen2 kv-head sharding,
deepseek MLA latent + expert-parallel MoE, mamba2 channel sharding) at
dispatch depths {1, 8}, greedy and seeded temperature, including forced
pool-starvation preemption — on 8 virtual CPU devices, so every test
here runs in a subprocess with XLA_FLAGS forcing the device count (the
parent process already initialized JAX single-device).

Also here: the jit-cache placement regression (two differently-placed
engines must not share or evict each other's executables) and the
width-weighted router semantics (a 4-device TP replica draws
proportionally more traffic and saturates at width x capacity).
"""
import os
import subprocess
import sys

import pytest

from repro.core.topology import Topology
from repro.serve import ReplicaRouter

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")
TESTS = os.path.join(ROOT, "tests")


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    # the child never reaches for an accelerator the parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (SRC + os.pathsep + TESTS + os.pathsep
                         + env.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, (f"stdout:\n{out.stdout[-2000:]}\n"
                                 f"stderr:\n{out.stderr[-6000:]}")
    return out.stdout


# ---------------------------------------------------------------------------
# engine == sequential, tp {1, 2} x depths {1, 8} x greedy/temperature
# ---------------------------------------------------------------------------

_EQUIV = """
import numpy as np, jax
from repro.models.model import build_model
from repro.serve import Engine, EngineConfig, Request
from test_serve import _family_config, _sequential_greedy
from test_serve_decode_loop import _tiny_qwen2, _sequential_sample

family = {family!r}
impl = {impl!r}
cfg = _tiny_qwen2() if family == "qwen2" else _family_config(family)
cfg = cfg.replace(attn_impl=impl)
model = build_model(cfg)
params = model.init(jax.random.key(0))
rng = np.random.default_rng(0)
reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, (int(p),)),
                max_new_tokens=int(g), rid=51000 + i)
        for i, (p, g) in enumerate(zip(rng.integers(3, 24, 3),
                                       rng.integers(4, 10, 3)))]
refs = dict()
refs[0.0] = [_sequential_greedy(model, params, r.prompt, r.max_new_tokens)
             for r in reqs]
refs[0.8] = [_sequential_sample(model, params, r.prompt, r.max_new_tokens,
                                rid=r.rid, temperature=0.8) for r in reqs]
assert refs[0.0] != refs[0.8]          # sampling actually stochastic
# the Pallas kernels' tp=1 equivalence is pinned in
# test_serve_decode_loop; here they must survive GSPMD sharding (the
# interpret-mode kernels lower to plain HLO and partition like any op)
tps = (2,) if impl == "pallas" else (1, 2)
for tp in tps:
    devs = tuple(jax.devices()[:tp])
    for spd in (1, 8):
        for temp in (0.0, 0.8):
            eng = Engine(model, params, EngineConfig(
                max_batch=3, block_size=8, num_blocks=65, max_seq_len=64,
                prefill_chunk=16, prefill_token_budget=24,
                steps_per_dispatch=spd, temperature=temp), devices=devs)
            assert eng.tp_degree == tp
            assert (eng.mesh is not None) == (tp > 1)
            res = eng.run([Request(prompt=r.prompt.copy(),
                                   max_new_tokens=r.max_new_tokens,
                                   rid=r.rid) for r in reqs])
            for r, ref in zip(reqs, refs[temp]):
                assert res[r.rid].tokens == ref, (family, impl, tp, spd,
                                                  temp, r.rid)
print("OK", family, impl)
"""


@pytest.mark.parametrize("attn_impl", ["jnp", "pallas"])
@pytest.mark.parametrize("family", ["qwen2", "deepseek", "mamba"])
def test_tp_engine_matches_sequential(family, attn_impl):
    impl = "naive" if attn_impl == "jnp" else attn_impl
    out = _run(_EQUIV.format(family=family, impl=impl))
    assert f"OK {family} {impl}" in out


_PREEMPT = """
import numpy as np, jax
from repro.models.model import build_model
from repro.serve import Engine, EngineConfig, Request
from test_serve import _sequential_greedy
from test_serve_decode_loop import _tiny_qwen2

cfg = _tiny_qwen2()
model = build_model(cfg)
params = model.init(jax.random.key(0))
rng = np.random.default_rng(2)
reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, (12,)),
                max_new_tokens=14, rid=52000 + i) for i in range(3)]
# pool too small for every row's full reservation: partial grants + full
# starvation, reconciled on host — while the state lives SHARDED
eng = Engine(model, params, EngineConfig(
    max_batch=3, block_size=4, num_blocks=10, max_seq_len=32,
    prefill_chunk=8, prefill_token_budget=16, steps_per_dispatch=8),
    devices=tuple(jax.devices()[:2]))
res = eng.run([Request(prompt=r.prompt.copy(),
                       max_new_tokens=r.max_new_tokens, rid=r.rid)
               for r in reqs])
c = eng.metrics_snapshot()["counters"]
assert c["preemptions"] > 0, c
for r in reqs:
    ref = _sequential_greedy(model, params, r.prompt, r.max_new_tokens)
    assert res[r.rid].tokens == ref
print("OK preempt", c["preemptions"], c["loop_truncations"])
"""


def test_tp_engine_preemption_keeps_equivalence():
    assert "OK preempt" in _run(_PREEMPT)


# ---------------------------------------------------------------------------
# cluster: 2 replicas x tp=2, heterogeneous slice widths
# ---------------------------------------------------------------------------

_CLUSTER = """
import numpy as np, jax
from repro.models.model import build_model
from repro.serve import EngineConfig, Request, ServeCluster
from test_serve import _cluster_ecfg, _sequential_greedy
from test_serve_decode_loop import _tiny_qwen2

cfg = _tiny_qwen2()
model = build_model(cfg)
params = model.init(jax.random.key(0))
rng = np.random.default_rng(9)
protos = [(rng.integers(0, cfg.vocab_size, (int(p),)), int(g))
          for p, g in zip(rng.integers(3, 30, 6), rng.integers(2, 12, 6))]
subs = [Request(prompt=np.asarray(p).copy(), max_new_tokens=g)
        for p, g in protos]
# 4 devices / 2 replicas -> two disjoint tp=2 slices
cluster = ServeCluster.for_replicas(model, params, _cluster_ecfg(),
                                    num_replicas=2,
                                    devices=jax.devices()[:4])
assert [e.tp_degree for e in cluster.engines] == [2, 2]
assert not set(cluster.slices[0]) & set(cluster.slices[1])
assert cluster.router.width(0) == cluster.router.width(1) == 2
results = cluster.run(subs)
assert len(results) == len(subs)
assert all(v == 0 for v in cluster.loads().values())
assert all(e.metrics_snapshot()["counters"]["generated_tokens"] > 0
           for e in cluster.engines)
for (p, g), sub in zip(protos, subs):
    ref = _sequential_greedy(model, params, np.asarray(p), g)
    assert results[sub.rid].tokens == ref

# heterogeneous explicit slices: router capacity/load scale by width
devs = jax.devices()
het = ServeCluster(model, params, _cluster_ecfg(),
                   slices=[tuple(devs[:3]), (devs[3],)])
assert [e.tp_degree for e in het.engines] == [3, 1]
assert het.router.width(0) == 3 and het.router.width(1) == 1
r = het.run([Request(prompt=np.asarray(p).copy(), max_new_tokens=g)
             for p, g in protos[:3]])
assert len(r) == 3
print("OK cluster")
"""


def test_cluster_tp_replicas_match_sequential():
    assert "OK cluster" in _run(_CLUSTER)


# ---------------------------------------------------------------------------
# jit-cache placement keying (the executable-eviction/churn regression)
# ---------------------------------------------------------------------------

_PLACEMENT = """
import numpy as np, jax
from repro.models.model import build_model
from repro.serve import Engine, EngineConfig, Request
from test_serve_decode_loop import _tiny_qwen2

cfg = _tiny_qwen2()
model = build_model(cfg)
params = model.init(jax.random.key(0))
ecfg = EngineConfig(max_batch=2, block_size=8, num_blocks=33,
                    max_seq_len=64, prefill_chunk=8,
                    prefill_token_budget=16)
devs = jax.devices()
a = Engine(model, params, ecfg, devices=(devs[0],))
b = Engine(model, params, ecfg, devices=(devs[1],))
t = Engine(model, params, ecfg, devices=tuple(devs[2:4]))
# differently-placed engines get their OWN jit wrappers through the
# shared Model.jit_cache (key carries device/mesh identity) ...
assert a._step_fn is not b._step_fn
assert a._step_fn is not t._step_fn
# ... while same-placed engines still share compiled executables
assert Engine(model, params, ecfg, devices=(devs[0],))._step_fn \
    is a._step_fn
a.warmup()
b.warmup()   # would previously grow a's watermarked wrapper cache
t.warmup()
rng = np.random.default_rng(0)
for eng, base in ((a, 53000), (b, 53100), (t, 53200)):
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, (9,)),
                    max_new_tokens=6, rid=base + i) for i in range(2)]
    eng.run(reqs)
for name, eng in (("a", a), ("b", b), ("t", t)):
    c = eng.metrics_snapshot()["counters"]
    assert c["jit_compiles"] == 0, (name, c)
print("OK placement")
"""


def test_jit_cache_keys_on_placement_no_cross_engine_churn():
    assert "OK placement" in _run(_PLACEMENT)


# ---------------------------------------------------------------------------
# width-weighted routing (host-only: no devices involved)
# ---------------------------------------------------------------------------


def test_router_width_normalized_load_balancing():
    """A width-4 replica absorbs ~4x the traffic of a width-1 replica:
    routing compares load PER SLICE DEVICE, not raw outstanding
    tokens."""
    r = ReplicaRouter(Topology(), num_pods=2, data_size=1,
                      widths={0: 4, 1: 1})
    assert r.width(0) == 4 and r.width(1) == 1
    for rid in range(10):
        assert r.route(rid, tokens=4) is not None
    loads = r.loads()
    assert loads[0] == 32 and loads[1] == 8      # 4:1, matching widths
    for rid in range(10):
        r.release(rid)
    assert all(v == 0 for v in r.loads().values())


def test_router_width_scales_capacity_threshold():
    """Backpressure saturates at capacity_tokens x width: the load that
    chokes a 1-device replica fits a 4-device one."""
    wide = ReplicaRouter(Topology(), num_pods=1, data_size=1,
                         capacity_tokens=16, widths={0: 4})
    narrow = ReplicaRouter(Topology(), num_pods=1, data_size=1,
                           capacity_tokens=16)
    assert wide.route(1, tokens=20) is not None   # idle: always accepts
    assert narrow.route(1, tokens=20) is not None
    # loaded: width-4 still has headroom (20+20 <= 64), width-1 refuses
    assert wide.route(2, tokens=20) is not None
    assert narrow.route(2, tokens=20) is None
    wide.release(1)
    wide.release(2)
    narrow.release(1)


def test_router_widths_default_to_topology_slices():
    """Without an override, width comes from the fast-group size the
    topology implies — the same slices ``replica_slices`` hands the
    engines."""
    r = ReplicaRouter(Topology(intra_group_size=4), num_pods=1,
                      data_size=8)
    assert r.num_replicas == 2
    assert r.width(0) == r.width(1) == 4
