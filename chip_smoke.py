#!/usr/bin/env python3
"""Drive both halves of the system once on a TPU at published widths.

    python chip_smoke.py             # one chip: train, serve, compare
    python chip_smoke.py --chips 4   # the paths that exist only across chips

One chip:
  train    ``repro.launch.train`` on qwen1.5-0.5b (24 layers, d_model
           1024, vocab 151936, bf16 params, f32 momentum and pending
           buffers), LSGD, a few steps on the 1x1 mesh; losses finite.
  serve    ``serve.Engine`` on qwen2-1.5b (28 layers, d_model 1536, 12/2
           heads, vocab 151936, bf16) with the Pallas kernels compiled:
           mixed-length greedy requests plus one seeded-temperature
           request, single steps and the 8-step decode loop; every
           request finishes with its token count, and nothing compiles
           after warm-up.
  compare  prefill and decode logits of the paged step, Pallas kernels
           against the jnp path, on the same cache and tokens.

Four chips (``--chips 4``):
  train    LSGD and CSGD on a pod=2 x data=2 mesh; parameters after
           ``finalize`` agree (the paper's equivalence).
  serve    two tp=2 replicas (``ServeCluster.for_replicas``) with Pallas
           kernels; their prefill logits match a tp=1 engine on one
           chip, and every request finishes with no fault or failover.

Weights are random, made from ``--seed``.  Every check raises on
failure, so a failed phase exits nonzero and prints no result line.
The last line of stdout is ``{"ok": true, "device": {...}}``.  Timings
printed on the way are host wall times of this run, compilation
included where labelled; none is a device metric.

``--tiny`` swaps in the smoke-width configs and allows the CPU: it is
the CPU rehearsal (``JAX_PLATFORMS=cpu python chip_smoke.py --tiny``;
add ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` for
``--chips 4``).  Without it, a backend other than TPU is an error.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

TRAIN_ARCH = "qwen1.5-0.5b"
SERVE_ARCH = "qwen2-1.5b"
# Logit agreement bound, as a fraction of the reference's largest |logit|.
# Both sides run bf16 weights and activations; the Pallas attends keep
# scores, probabilities and the accumulator in f32 while the jnp path
# rounds the probabilities to bf16 before the PV product, and tensor
# parallelism reorders the row-parallel sums.  Each layer then differs
# by bf16 rounding (2^-8 relative) in a different order, and the
# difference compounds through the residual stream of every layer.
LOGIT_RTOL = 0.05
# LSGD and CSGD reduce the same f32 gradients in a different order
# (two-phase vs flat all-reduce), so a bf16 parameter may round one way
# or the other at every step: allow a few bf16 ulps, counted at the
# largest magnitude of each parameter tensor (an element near zero has
# a tiny ulp of its own that says nothing about the update's scale).
PARAM_ULPS = 4


def _log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


def _train(args, mesh: str, sync_mode: str, steps: int) -> dict:
    from repro.launch import train
    argv = ["--arch", TRAIN_ARCH, "--sync-mode", sync_mode,
            "--steps", str(steps), "--log-every", "1",
            "--seed", str(args.seed), "--schedule", "const",
            "--base-lr", "0.05"]
    if mesh:
        argv += ["--mesh", mesh]
    if args.tiny:
        argv += ["--smoke", "--batch", "8", "--seq", "32"]
    else:
        # 4096 tokens a step; the (tokens, 151936) f32 logits are cut
        # into 128-position chunks (peak ~8.4 GB of the chip's 16 GB,
        # from the compiled step's memory analysis)
        argv += ["--batch", "8", "--seq", "512", "--loss-chunk", "128"]
    t0 = time.perf_counter()
    out = train.main(argv)
    wall = time.perf_counter() - t0
    losses = out["losses"]
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"non-finite training loss: {losses}")
    _log("train", f"{TRAIN_ARCH} {sync_mode} mesh={mesh or '1,1'}: "
         f"compile {out['compile_s']:.1f}s (wall), losses first "
         f"{losses[0]:.4f} last {losses[-1]:.4f}, phase {wall:.1f}s (wall)")
    return out


def phase_train(args) -> None:
    import jax
    import jax.numpy as jnp
    state = _train(args, "", "lsgd", 5)["state"]
    dtypes = {name: sorted({str(x.dtype) for x in jax.tree.leaves(state[k])})
              for name, k in (("params", "params"), ("momentum", "opt"),
                              ("pending", "pending"))}
    _log("train", f"state dtypes {dtypes}")
    if not args.tiny:
        want = {"params": ["bfloat16"], "momentum": ["float32"],
                "pending": ["float32"]}
        if dtypes != want:
            raise AssertionError(f"state dtypes {dtypes}, want {want}")
    del state
    jnp.zeros(()).block_until_ready()


def _bf16_ulp(x: float) -> float:
    return 2.0 ** (math.floor(math.log2(max(x, 1e-30))) - 7)


def phase_train_equivalence(args) -> None:
    import jax
    import numpy as np
    params = {}
    for mode in ("lsgd", "csgd"):
        params[mode] = jax.tree.map(
            lambda x: np.asarray(x, np.float32),
            _train(args, "2,2,1", mode, 3)["state"]["params"])
    worst, diff = 0.0, 0
    for a, b in zip(jax.tree.leaves(params["lsgd"]),
                    jax.tree.leaves(params["csgd"])):
        d = np.abs(a - b)
        diff += int((d > 0).sum())
        scale = float(max(np.abs(a).max(), np.abs(b).max()))
        worst = max(worst, float(d.max()) / _bf16_ulp(scale))
    n = sum(x.size for x in jax.tree.leaves(params["lsgd"]))
    _log("train", f"lsgd vs csgd after finalize: {diff}/{n} params "
         f"differ, max {worst:.3g} bf16 ulps (bound {PARAM_ULPS})")
    if worst > PARAM_ULPS:
        raise AssertionError(f"LSGD and CSGD parameters differ by "
                             f"{worst:.3g} bf16 ulps")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def _serve_model(args):
    import jax
    from repro.configs.base import get_config, smoke_variant
    from repro.models.model import build_model
    cfg = get_config(SERVE_ARCH)
    if args.tiny:
        cfg = smoke_variant(cfg)
    model = build_model(cfg.replace(attn_impl="pallas", mtp_depth=0))
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(model.init)(
        jax.random.key(args.seed)))
    _log("serve", f"{model.cfg.name}: layers={model.cfg.num_layers} "
         f"d_model={model.cfg.d_model} heads={model.cfg.num_heads}/"
         f"{model.cfg.num_kv_heads} vocab={model.cfg.vocab_size} "
         f"dtype={model.cfg.param_dtype}; init {time.perf_counter() - t0:.1f}s "
         f"(wall)")
    return model, params


def _engine_config(args, **kw):
    from repro.serve import EngineConfig
    # one prefill chunk per step keeps the warm-up set to one mixed
    # shape; max_batch 4 gives decode buckets 4/2/1 (2/1 in the CPU
    # rehearsal, whose interpreted kernels compile slowly)
    chunk, rows = (16, 2) if args.tiny else (64, 4)
    base = EngineConfig(max_batch=rows, block_size=16, num_blocks=257,
                        max_seq_len=256, prefill_chunk=chunk,
                        prefill_token_budget=chunk, steps_per_dispatch=8)
    return dataclasses.replace(base, **kw)


def _requests(args, vocab: int, n: int, rid0: int):
    """``n`` requests of mixed prompt and output lengths (several
    prefill chunks down to a fraction of one; one to five decode-loop
    dispatches)."""
    import numpy as np
    from repro.serve import Request
    rng = np.random.default_rng(args.seed + rid0)
    plen, glen = (40, 17) if args.tiny else (130, 41)
    return [Request(prompt=rng.integers(0, vocab, (int(p),)),
                    max_new_tokens=int(g), rid=rid0 + i)
            for i, (p, g) in enumerate(zip(rng.integers(5, plen, n),
                                           rng.integers(4, glen, n)))]


def _check_results(phase, reqs, results) -> int:
    tokens = 0
    for r in reqs:
        res = results.get(r.rid)
        if res is None or res.fault is not None:
            raise AssertionError(f"request {r.rid}: {res}")
        if len(res.tokens) != r.max_new_tokens:
            raise AssertionError(f"request {r.rid}: {len(res.tokens)} "
                                 f"tokens, asked {r.max_new_tokens}")
        tokens += len(res.tokens)
        _log(phase, f"  req {r.rid}: prompt {len(r.prompt)} -> "
             f"{len(res.tokens)} tokens {res.tokens[:6]}")
    return tokens


def _mosaic_calls(fn, *args) -> int:
    """Compiled Pallas kernels in ``fn``'s lowering (0 when interpreted)."""
    import jax
    return jax.jit(fn).lower(*args).as_text().count("tpu_custom_call")


def phase_serve(args, model, params) -> None:
    import functools
    import jax
    import numpy as np
    from repro.serve import Engine
    engines = {"greedy": Engine(model, params, _engine_config(args)),
               "sampled": Engine(model, params, _engine_config(
                   args, max_batch=1, temperature=0.8, seed=args.seed))}
    for name, eng in engines.items():
        t0 = time.perf_counter()
        eng.warmup()
        _log("serve", f"{name} engine warm-up (compiles) "
             f"{time.perf_counter() - t0:.1f}s (wall)")

    eng = engines["greedy"]
    rows, nbs = eng.cfg.max_batch, eng.cfg.blocks_per_seq
    meta = np.zeros((6, rows), np.int32)
    meta[5] = -1
    calls = _mosaic_calls(
        functools.partial(model.paged_decode_loop, num_steps=8),
        eng.params, eng.cache, jax.numpy.zeros((eng.cfg.num_slots + 1,),
                                               jax.numpy.int32),
        np.zeros((rows, nbs), np.int32), meta)
    _log("serve", f"compiled Pallas kernels in the decode loop: {calls}")
    if (calls > 0) != (jax.default_backend() == "tpu"):
        raise AssertionError(f"{calls} Mosaic kernels on backend "
                             f"{jax.default_backend()}")

    vocab = model.cfg.vocab_size
    batches = {"greedy": _requests(args, vocab, 7, 1000),
               "sampled": _requests(args, vocab, 1, 2000)}
    for name, reqs in batches.items():
        e = engines[name]
        t0 = time.perf_counter()
        results = e.run(reqs)
        wall = time.perf_counter() - t0
        tokens = _check_results("serve", reqs, results)
        counters = e.metrics_snapshot()["counters"]
        _log("serve", f"{name}: {len(reqs)} requests, {tokens} tokens in "
             f"{wall:.2f}s (wall); loop dispatches "
             f"{counters['loop_dispatches']}, steps {counters['steps']}, "
             f"jit compiles after warm-up {counters['jit_compiles']}")
        if counters["jit_compiles"]:
            raise AssertionError(f"{name}: {counters['jit_compiles']} "
                                 f"compiles after warm-up")
    if not engines["greedy"].metrics_snapshot()["counters"]["loop_dispatches"]:
        raise AssertionError("the N-step decode loop never ran")


def _rel_err(got, ref) -> float:
    import numpy as np
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def phase_compare(args, model, params) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import transformer
    from repro.models.model import build_model
    models = {"pallas": model,
              "jnp": build_model(model.cfg.replace(attn_impl="naive"))}
    nbs, bs, plen = 16, 16, 100
    cache = model.init_paged_cache(nbs + 1, bs, 1, nbs)
    tables = jnp.arange(1, nbs + 1, dtype=jnp.int32)[None]
    rng = np.random.default_rng(args.seed)
    prompt = rng.integers(0, model.cfg.vocab_size, (1, plen)).astype(np.int32)
    nxt = rng.integers(0, model.cfg.vocab_size, (1, 1)).astype(np.int32)

    def prefill_then_decode(m, params, cache, prompt, nxt):
        c = transformer.with_block_tables(cache, tables)
        lp, c = m.paged_step_logits(params, c, prompt,
                                    jnp.zeros((1,), jnp.int32))
        ld, _ = m.paged_step_logits(params, c, nxt,
                                    jnp.full((1,), plen, jnp.int32))
        return lp.astype(jnp.float32), ld.astype(jnp.float32)

    out = {}
    for name, m in models.items():
        t0 = time.perf_counter()
        fn = jax.jit(lambda *a, m=m: prefill_then_decode(m, *a))
        out[name] = [np.asarray(x) for x in fn(params, cache, prompt, nxt)]
        _log("compare", f"{name}: prefill+decode logits "
             f"{time.perf_counter() - t0:.1f}s (wall, incl. compile)")
    for i, step in enumerate(("prefill", "decode")):
        got, ref = out["pallas"][i], out["jnp"][i]
        if not np.isfinite(got).all():
            raise AssertionError(f"{step}: non-finite Pallas logits")
        err = _rel_err(got, ref)
        agree = float((got.argmax(-1) == ref.argmax(-1)).mean())
        _log("compare", f"{step} logits {got.shape}: max|pallas-jnp| / "
             f"max|jnp| = {err:.3e} (bound {LOGIT_RTOL}); greedy token "
             f"agreement {agree:.3f} (reported only)")
        if err > LOGIT_RTOL:
            raise AssertionError(f"{step}: pallas vs jnp logits differ by "
                                 f"{err:.3e} of the largest logit")


def _prefill_logits(engine, prompt):
    """Frontier-free prefill logits through ``engine``'s own placement:
    its (possibly sharded) params, a zeroed cache laid out like its
    cache, and its mesh in context for the Pallas wrappers."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import transformer
    from repro.serve.engine import traced_on_mesh
    model = engine.model
    nbs = engine.cfg.blocks_per_seq
    cache = jax.tree.map(
        lambda x: jax.device_put(jnp.zeros(x.shape, x.dtype), x.sharding),
        engine.cache)

    def f(params, cache, prompt):
        tables = jnp.arange(1, nbs + 1, dtype=jnp.int32)[None]
        c = transformer.with_block_tables(cache, tables)
        lg, _ = model.paged_step_logits(params, c, prompt,
                                        jnp.zeros((1,), jnp.int32))
        return lg.astype(jnp.float32)

    return np.asarray(jax.jit(traced_on_mesh(engine.mesh, f))(
        engine.params, cache, prompt))


def phase_serve_tp(args, model, params) -> None:
    import jax
    import numpy as np
    from repro.serve import Engine, ServeCluster
    ecfg = _engine_config(args)
    cluster = ServeCluster.for_replicas(model, params, ecfg, num_replicas=2)
    tps = [e.tp_degree for e in cluster.engines]
    if tps != [2, 2]:
        raise AssertionError(f"replica widths {tps}, want [2, 2]")
    ref = Engine(model, params, ecfg, devices=(jax.devices()[0],))
    rng = np.random.default_rng(args.seed)
    prompt = rng.integers(0, model.cfg.vocab_size, (1, 100)).astype(np.int32)
    want = _prefill_logits(ref, prompt)
    for i, e in enumerate(cluster.engines):
        got = _prefill_logits(e, prompt)
        err = _rel_err(got, want)
        agree = float((got.argmax(-1) == want.argmax(-1)).mean())
        _log("serve-tp", f"replica {i} (tp={e.tp_degree}, devices "
             f"{[d.id for d in e.devices]}) prefill logits vs tp=1: "
             f"max|diff| / max|ref| = {err:.3e} (bound {LOGIT_RTOL}); "
             f"greedy token agreement {agree:.3f} (reported only)")
        if err > LOGIT_RTOL:
            raise AssertionError(f"replica {i}: tp=2 logits differ by "
                                 f"{err:.3e} of the largest logit")
    del ref
    t0 = time.perf_counter()
    cluster.warmup()
    _log("serve-tp", f"warm-up (compiles) {time.perf_counter() - t0:.1f}s "
         f"(wall)")
    reqs = _requests(args, model.cfg.vocab_size, 8, 3000)
    t0 = time.perf_counter()
    results = cluster.run(reqs)
    wall = time.perf_counter() - t0
    tokens = _check_results("serve-tp", reqs, results)
    m = cluster.metrics()
    per = [m["per_replica"][i]["counters"] for i in range(2)]
    gauges = cluster.telemetry.registry.snapshot()["gauges"]
    coll = [v for k, v in sorted(gauges.items())
            if k.startswith("engine_tp_collective_ops")]
    _log("serve-tp", f"{tokens} tokens in {wall:.2f}s (wall); per-replica "
         f"tokens {[c['generated_tokens'] for c in per]}, collectives in "
         f"the compiled decode step {coll}, failovers "
         f"{m['failover']['failovers']}, jit compiles after warm-up "
         f"{[c['jit_compiles'] for c in per]}")
    if m["failover"]["failovers"] or any(c["jit_compiles"] for c in per):
        raise AssertionError(f"failover {m['failover']}, compiles "
                             f"{[c['jit_compiles'] for c in per]}")


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: train, serve and compare on one chip; 4: "
                         "only the multi-chip paths and their references")
    ap.add_argument("--tiny", action="store_true",
                    help="smoke-width configs; allows the CPU (rehearsal)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"chip_smoke.py needs the repository's sources "
                         f"at {SRC}")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.tiny:
        raise SystemExit(f"no TPU: JAX's backend is {platform!r} "
                         f"(--tiny rehearses on the CPU)")
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} "
                         f"devices, JAX sees {len(devices)}")
    print(f"devices: {len(devices)} x {devices[0].device_kind} "
          f"({platform}); compile cache {cache_dir}", flush=True)

    t0 = time.perf_counter()
    if args.chips == 1:
        phase_train(args)
        model, params = _serve_model(args)
        phase_serve(args, model, params)
        phase_compare(args, model, params)
    else:
        phase_train_equivalence(args)
        model, params = _serve_model(args)
        phase_serve_tp(args, model, params)
    print(f"total {time.perf_counter() - t0:.1f}s (wall)", flush=True)
    result = {"ok": True, "device": {"platform": platform,
                                     "kind": devices[0].device_kind,
                                     "count": len(devices)}}
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
