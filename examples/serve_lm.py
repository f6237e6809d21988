"""Serving example — a thin client of the repro.serve engine.

Requests stream in through a thread-safe RequestQueue (host-side
"tokenization" overlapped with device decode, HostLoader-style); the
continuous-batching engine admits them mid-flight, interleaves budgeted
prefill chunks with batched decode over the paged KV cache, and evicts
finished sequences as their slots free.  With ``--replicas N`` the
requests fan out token-weighted over N engines, one per fast-fabric
device slice (ServeCluster); a multi-device slice serves
tensor-parallel across its devices (8 virtual devices / 2 replicas
below = two tp=4 engines).

    PYTHONPATH=src python -m examples.serve_lm [--arch qwen2-1.5b]
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python -m examples.serve_lm --replicas 2
"""
import argparse
import contextlib
import dataclasses
import threading
import time

import jax
import numpy as np

from repro.configs.base import get_config, smoke_variant
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model
from repro.serve import (Engine, EngineConfig, FaultPlan, Request,
                         RequestQueue, ServeCluster, Telemetry)


def _print_metrics(snapshot):
    """Render a registry snapshot as an aligned table."""
    print("\n-- metrics ------------------------------------------------")
    for section in ("counters", "gauges"):
        for name, v in snapshot[section].items():
            print(f"  {name:<58} {v}")
    for name, h in snapshot["histograms"].items():
        if not h["count"]:
            continue
        print(f"  {name:<58} n={h['count']:<5} "
              f"p50={h['p50']*1e3:8.2f}ms p95={h['p95']*1e3:8.2f}ms "
              f"p99={h['p99']*1e3:8.2f}ms")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4,
                    help="concurrent decode rows")
    ap.add_argument("--prompt-len", type=int, default=48,
                    help="max prompt length (lengths are mixed)")
    ap.add_argument("--gen-len", type=int, default=32,
                    help="max new tokens (lengths are mixed)")
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k restriction for temperature sampling "
                    "(0 = full vocab); sampled on device")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps-per-dispatch", type=int, default=1,
                    help="decode steps per device dispatch: N > 1 runs "
                    "the on-device decode loop (one host dispatch per N "
                    "tokens)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas, one per device slice")
    ap.add_argument("--chaos-kill", default=None, metavar="R:K",
                    help="inject a replica kill at replica R's K-th "
                    "dispatch (needs --replicas >= 2): the dispatcher "
                    "detects the death, reclaims the replica's "
                    "in-flight requests, and re-decodes them on the "
                    "survivors — same tokens, fold_in(rid, position) "
                    "keys make re-decode replica-independent")
    ap.add_argument("--deadline", type=float, default=None, metavar="S",
                    help="per-request end-to-end deadline budget in "
                    "seconds; overrunning requests finish with a "
                    "'deadline' fault result instead of blocking the "
                    "batch")
    ap.add_argument("--metrics", action="store_true",
                    help="print the telemetry snapshot table on exit "
                    "(counters, gauges, TTFT/TPOT/e2e percentiles)")
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="profile the serving run into DIR: a JAX "
                    "profiler trace with the engine's repro:* host spans "
                    "and the device ops on one clock (open DIR's "
                    "perfetto_trace.json.gz in Perfetto)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = smoke_variant(get_config(args.arch)).replace(mtp_depth=0)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))

    max_seq = args.prompt_len + args.gen_len
    ecfg = EngineConfig(
        max_batch=args.batch, block_size=16, max_seq_len=max_seq,
        prefill_chunk=min(32, args.prompt_len),
        prefill_token_budget=2 * min(32, args.prompt_len),
        temperature=args.temperature, top_k=args.top_k, seed=args.seed,
        steps_per_dispatch=args.steps_per_dispatch)
    # pool sized so every admissible sequence can reach max_seq_len
    ecfg = dataclasses.replace(
        ecfg, num_blocks=(ecfg.max_batch + ecfg.admission_lookahead)
        * ecfg.blocks_per_seq + 1)
    telemetry = Telemetry()
    plan = None
    if args.chaos_kill is not None:
        if args.replicas < 2:
            ap.error("--chaos-kill needs --replicas >= 2 (a survivor "
                     "must exist to fail over to)")
        rep, k = (int(x) for x in args.chaos_kill.split(":"))
        plan = FaultPlan.kill_at(replica=rep, dispatch=k)
    if args.replicas > 1:
        server = ServeCluster.for_replicas(model, params, ecfg,
                                           num_replicas=args.replicas,
                                           telemetry=telemetry, faults=plan)
    else:
        server = Engine(model, params, ecfg, telemetry=telemetry)
    server.warmup()
    print(f"serving {cfg.name}: {args.requests} requests, "
          f"{args.replicas} replica(s) x {args.batch} decode rows, "
          f"paged KV ({ecfg.num_blocks} x {ecfg.block_size}-token blocks)")

    rng = np.random.default_rng(args.seed)
    queue = RequestQueue(maxsize=args.requests)

    def client():
        # mixed prompt/generation lengths, trickling in
        for _ in range(args.requests):
            p = int(rng.integers(args.prompt_len // 4, args.prompt_len + 1))
            g = int(rng.integers(args.gen_len // 4, args.gen_len + 1))
            queue.submit(Request(
                prompt=rng.integers(0, cfg.vocab_size, (p,)),
                max_new_tokens=g, deadline_s=args.deadline))
            time.sleep(0.002)
        queue.close()

    producer = threading.Thread(target=client)
    profile = (telemetry.profile(args.trace) if args.trace
               else contextlib.nullcontext())
    t0 = time.perf_counter()
    producer.start()
    with profile, queue:
        results = server.run(request_queue=queue)
    producer.join()
    wall = time.perf_counter() - t0

    for rid in sorted(results):
        r = results[rid]
        tag = f"  FAULT={r.fault}" if r.fault else ""
        print(f"  req {rid}: prompt={r.prompt_len:3d} gen={len(r.tokens):3d}"
              f"  first-token={(r.first_token_time - t0)*1e3:6.1f} ms"
              f"  tokens={r.tokens[:8]}{'...' if len(r.tokens) > 8 else ''}"
              f"{tag}")
    tokens = sum(len(r.tokens) for r in results.values())
    if args.replicas > 1:
        m = server.metrics()
        stats = m["aggregate"]["counters"]
        per_rep = ("  per-replica tokens=" + str(
            [m["per_replica"][i]["counters"]["generated_tokens"]
             for i in range(server.num_replicas)])
            + "  tp=" + str([e.tp_degree for e in server.engines]))
    else:
        stats = server.metrics_snapshot()["counters"]
        per_rep = ""
    occ = (stats["decode_active_slot_steps"]
           / max(stats["decode_slot_steps"], 1))
    print(f"{tokens} tokens in {wall*1e3:.0f} ms "
          f"({tokens / wall:,.0f} tok/s), decode occupancy {occ:.2f}, "
          f"{stats['preemptions']} preemptions{per_rep}")
    if plan is not None:
        fo = server.metrics()["failover"]
        print(f"  chaos: fired={[(a.replica, a.dispatch, a.kind) for a in plan.fired()]}  "
              f"failovers={fo['failovers']} redispatched={fo['redispatched']}")
    if args.metrics:
        _print_metrics(telemetry.registry.snapshot())
    if args.trace:
        print(f"wrote a profiler trace under {args.trace}")


if __name__ == "__main__":
    main()
