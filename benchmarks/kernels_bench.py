"""Kernel micro-bench: one row per PUBLIC op in ``repro.kernels.ops``.

Each row times the jnp oracle path and the Pallas path (interpret mode —
this container is CPU-only) on the same inputs and reports the speedup
plus the kernel's analytic TPU-v5e roofline (memory-bound bytes /
819 GB/s or MXU FLOPs / 197 TF/s — what the BlockSpec tiling targets).
Interpret-mode wall time is a Python interpreter walking the grid, so
the speedup column is a wrapper-overhead regression canary, NOT kernel
perf; the roofline column is the perf claim.

Coverage is enforced: every public op gets a row.  Ops without a Pallas
path are reported as ``skipped`` rows with a printed notice instead of
crashing, so adding an op to ops.py before its kernel lands degrades the
bench gracefully — but silently dropping an op from the table fails the
run (exit 1).

Steady-state jit-compile gate (same contract as serve_bench --tp-sweep):
after the warmup call, the timed iterations must not trigger any new XLA
compilation; churn fails the run with exit 1.

    PYTHONPATH=src python benchmarks/kernels_bench.py
    python benchmarks/kernels_bench.py --json    # writes out/kernels_bench.json
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref

HBM = 819e9        # v5e HBM bandwidth, bytes/s
MXU = 197e12       # v5e bf16 matmul, FLOP/s

# artifacts land under benchmarks/out/ (gitignored) so a local --json
# run can never leave a stray report at the repo root of the bench dir
JSON_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "out", "kernels_bench.json")


def _timed(fn, fargs, iters):
    """Jit, warm up once, then time; returns (us_per_call, new_compiles
    observed DURING the timed iterations — steady-state churn)."""
    f = jax.jit(fn)
    out = jax.block_until_ready(f(*fargs))
    cache0 = f._cache_size()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(*fargs)
    jax.block_until_ready(out)
    us = (time.perf_counter() - t0) / iters * 1e6
    return us, f._cache_size() - cache0


def _build_specs():
    """Per-op bench setups: CPU-interpret-friendly shapes (the Pallas
    side walks the grid in Python here), oracle and Pallas closures over
    identical logical inputs, and the v5e roofline at the SAME shape so
    the derived column stays comparable run-over-run."""
    from repro.models import attention as mattn
    from repro.models.layers import slot_state_scatter
    ks = jax.random.split(jax.random.key(0), 10)
    specs = {}

    # --- fused optimizer update (train hot path) ------------------------
    w = jax.random.normal(ks[0], (512, 128), jnp.bfloat16)
    m = jnp.zeros(w.shape, jnp.float32)
    g = jax.random.normal(ks[1], w.shape, jnp.float32)
    kw = dict(lr=0.1, momentum=0.9, weight_decay=1e-4)
    specs["fused_sgd_update"] = dict(
        family="update",
        oracle=(lambda w, m, g: ref.fused_sgd_update(w, m, g, **kw),
                (w, m, g)),
        pallas=(lambda w, m, g: ops.fused_sgd_update(w, m, g, **kw),
                (w, m, g)),
        roofline_us=w.size * (2 + 4 + 4 + 2 + 4) / HBM * 1e6)

    # --- flash attention (prefill/train fwd) ----------------------------
    b, s, h, kv, hd = 1, 256, 4, 2, 64
    qb = jax.random.normal(ks[2], (b, h, s, hd), jnp.bfloat16)
    kb = jax.random.normal(ks[3], (b, kv, s, hd), jnp.bfloat16)
    vb = jax.random.normal(ks[4], (b, kv, s, hd), jnp.bfloat16)
    specs["flash_attention"] = dict(
        family="attend-view",
        oracle=(lambda q, k, v: ref.flash_attention_bhsd(q, k, v), (qb, kb, vb)),
        pallas=(lambda q, k, v: ops.flash_attention(
            jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2),
            jnp.moveaxis(v, 1, 2)), (qb, kb, vb)),
        roofline_us=2 * 2 * b * h * s * s * hd / 2 / MXU * 1e6)

    # --- flash decode (one token vs contiguous KV cache) ----------------
    b, h, kv, hd, s = 4, 4, 2, 64, 1024
    q1 = jax.random.normal(ks[2], (b, h, hd), jnp.bfloat16)
    k1 = jax.random.normal(ks[3], (b, s, kv, hd), jnp.bfloat16)
    v1 = jax.random.normal(ks[4], (b, s, kv, hd), jnp.bfloat16)
    specs["flash_decode"] = dict(
        family="attend-view",
        oracle=(lambda q, k, v, s=s: ref.flash_decode(
            q, jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2), s),
            (q1, k1, v1)),
        pallas=(lambda q, k, v, s=s: ops.flash_decode(q, k, v, s),
                (q1, k1, v1)),
        roofline_us=k1.size * 2 * 2 / HBM * 1e6)

    # --- paged decode attention (engine fused step) ---------------------
    nb, bs, b, c, nbs = 16, 16, 2, 1, 4
    qp = jax.random.normal(ks[2], (b, c, h, hd), jnp.bfloat16)
    kpool = jax.random.normal(ks[3], (nb, bs, kv, hd), jnp.bfloat16)
    vpool = jax.random.normal(ks[4], (nb, bs, kv, hd), jnp.bfloat16)
    bt = jnp.arange(1, 1 + b * nbs, dtype=jnp.int32).reshape(b, nbs)
    pos = jnp.asarray([nbs * bs - c, nbs * bs // 2], jnp.int32)
    specs["flash_decode_paged"] = dict(
        family="attend-view",
        oracle=(lambda q, kp, vp: ref.flash_decode_paged(q, kp, vp, bt, pos),
                (qp, kpool, vpool)),
        pallas=(lambda q, kp, vp: ops.flash_decode_paged(q, kp, vp, bt, pos),
                (qp, kpool, vpool)),
        roofline_us=b * nbs * bs * kv * hd * 2 * 2 / HBM * 1e6)

    # --- view-resident decode attend (N-step loop body) -----------------
    b, s, kv, grp, hd = 4, 160, 2, 2, 64
    h2 = kv * grp
    qv = jax.random.normal(ks[2], (b, h2, hd), jnp.bfloat16)
    kvw = jax.random.normal(ks[3], (b, s, kv, hd), jnp.bfloat16)
    vvw = jax.random.normal(ks[4], (b, s, kv, hd), jnp.bfloat16)
    vpos = jnp.asarray([s - 2, s // 2, 7, 0], jnp.int32)
    specs["decode_view_attend"] = dict(
        family="attend-view",
        oracle=(lambda q, k, v, b=b, kv=kv, grp=grp, hd=hd, h2=h2:
                mattn.paged_decode_attention(
                    q.reshape(b, 1, kv, grp, hd), k, v, vpos[:, None]
                ).reshape(b, h2, hd), (qv, kvw, vvw)),
        pallas=(lambda q, k, v: ops.decode_view_attend(q, k, v, vpos),
                (qv, kvw, vvw)),
        roofline_us=b * s * kv * hd * 2 * 2 / HBM * 1e6)

    # --- MLA absorbed latent attends (views + paged pools) --------------
    b, c, hm, r, rd, s = 2, 1, 4, 64, 32, 96
    scale = 1.0 / (r + rd) ** 0.5
    ql = jax.random.normal(ks[2], (b, c, hm, r), jnp.float32)
    qr = jax.random.normal(ks[3], (b, c, hm, rd), jnp.float32)
    ckv = jax.random.normal(ks[4], (b, s, r), jnp.float32)
    kr = jax.random.normal(ks[5], (b, s, rd), jnp.float32)
    mpos = jnp.asarray([s - 2, 11], jnp.int32)
    specs["mla_decode_views"] = dict(
        family="mla-latent",
        oracle=(lambda a, b_, c_, d: ref.mla_decode_views(
            a, b_, c_, d, mpos, scale=scale), (ql, qr, ckv, kr)),
        pallas=(lambda a, b_, c_, d: ops.mla_decode_views(
            a, b_, c_, d, mpos, scale=scale), (ql, qr, ckv, kr)),
        roofline_us=b * s * (r + rd) * 4 / HBM * 1e6)

    nb2, bs2, nbs2 = 12, 16, 3
    ckv_pool = jax.random.normal(ks[4], (nb2, bs2, r), jnp.float32)
    kr_pool = jax.random.normal(ks[5], (nb2, bs2, rd), jnp.float32)
    bt2 = jnp.arange(1, 1 + b * nbs2, dtype=jnp.int32).reshape(b, nbs2)
    mpos2 = jnp.asarray([nbs2 * bs2 - 1, 9], jnp.int32)
    specs["mla_decode_paged"] = dict(
        family="mla-latent",
        oracle=(lambda a, b_, cp, kp: ref.mla_decode_paged(
            a, b_, cp, kp, bt2, mpos2, scale=scale), (ql, qr, ckv_pool,
                                                      kr_pool)),
        pallas=(lambda a, b_, cp, kp: ops.mla_decode_paged(
            a, b_, cp, kp, bt2, mpos2, scale=scale), (ql, qr, ckv_pool,
                                                      kr_pool)),
        roofline_us=b * nbs2 * bs2 * (r + rd) * 4 / HBM * 1e6)

    # --- slot-state gather/scatter (ssm/rglru recurrent pools) ----------
    spool = jax.random.normal(ks[6], (33, 4, 64), jnp.float32)
    slots = jnp.asarray([3, 17, 32, 1, 9, 25, 12, 6], jnp.int32)
    fresh = jnp.asarray([0, 1, 0, 0, 1, 0, 0, 0], jnp.int32)
    specs["slot_gather"] = dict(
        family="slot-state",
        oracle=(lambda p: jnp.where(fresh[:, None, None] != 0, 0.0,
                                    p[slots]), (spool,)),
        pallas=(lambda p: ops.slot_gather(p, slots, fresh), (spool,)),
        roofline_us=slots.size * 4 * 64 * 4 * 2 / HBM * 1e6)

    sval = jax.random.normal(ks[7], (8, 4, 64), jnp.float32)
    svalid = jnp.asarray([1, 2, 0, 1, 4, 1, 0, 3], jnp.int32)
    specs["slot_scatter"] = dict(
        family="slot-state",
        oracle=(lambda p, v: slot_state_scatter(p, slots, svalid, v),
                (spool, sval)),
        pallas=(lambda p, v: ops.slot_scatter(p, slots, svalid, v),
                (spool, sval)),
        roofline_us=spool.size * 4 * 2 / HBM * 1e6)

    # --- device-side serving sampler ------------------------------------
    bsamp, vocab = 8, 1024
    logits = jax.random.normal(ks[8], (bsamp, vocab), jnp.float32) * 3.0
    keys = ref.sample_keys(0, jnp.arange(100, 100 + bsamp, dtype=jnp.int32),
                           jnp.arange(7, 7 + bsamp, dtype=jnp.int32))
    skw = dict(temperature=0.8, top_k=32)
    specs["sample_tokens"] = dict(
        family="sampling",
        oracle=(lambda lg, k: ops.sample_tokens(lg, k, impl="jnp", **skw),
                (logits, keys)),
        pallas=(lambda lg, k: ops.sample_tokens(lg, k, impl="pallas", **skw),
                (logits, keys)),
        roofline_us=bsamp * vocab * 4 * 3 / HBM * 1e6)

    # --- SSD intra-chunk (Mamba-2 train/prefill) ------------------------
    bc, l, hs, p, n = 2, 16, 2, 64, 64
    x = jax.random.normal(ks[9], (bc, l, hs, p), jnp.float32)
    dts = jax.nn.softplus(jax.random.normal(ks[0], (bc, l, hs), jnp.float32))
    dacum = jnp.cumsum(-dts * 0.1, axis=1)
    Bm = jax.random.normal(ks[1], (bc, l, hs, n), jnp.float32)
    Cm = jax.random.normal(ks[2], (bc, l, hs, n), jnp.float32)
    specs["ssd_chunk"] = dict(
        family="slot-state",
        oracle=(lambda *a: ref.ssd_chunk_bchp(*a), (x, dts, dacum, Bm, Cm)),
        pallas=(lambda *a: ops.ssd_chunk(*a), (x, dts, dacum, Bm, Cm)),
        roofline_us=2 * bc * hs * (l * l * n + l * l * p + l * n * p)
        / MXU * 1e6)

    return specs


def main(argv=(), print_fn=print):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", action="store_true",
                    help=f"write rows to {os.path.basename(JSON_PATH)}")
    ap.add_argument("--iters", type=int, default=3)
    args = ap.parse_args(list(argv))

    public = sorted(
        name for name, f in inspect.getmembers(ops, inspect.isfunction)
        if f.__module__ == "repro.kernels.ops"
        and not name.startswith("_"))
    specs = _build_specs()

    rows = []
    churn = []
    for name in public:
        spec = specs.get(name)
        if spec is None:
            print_fn(f"NOTICE: {name} has no Pallas bench path yet — "
                     f"skipped (row recorded, not a failure)")
            rows.append(dict(name=name, family="-", status="skipped",
                             oracle_us=None, pallas_interpret_us=None,
                             speedup=None, v5e_roofline_us=None))
            continue
        o_us, o_new = _timed(*spec["oracle"], iters=args.iters)
        p_us, p_new = _timed(*spec["pallas"], iters=args.iters)
        if o_new or p_new:
            churn.append((name, o_new + p_new))
        rows.append(dict(name=name, family=spec["family"], status="ok",
                         oracle_us=round(o_us, 1),
                         pallas_interpret_us=round(p_us, 1),
                         speedup=round(o_us / p_us, 4),
                         v5e_roofline_us=round(spec["roofline_us"], 4)))

    print_fn("# kernels: jnp oracle vs Pallas(interpret) on this host; "
             "v5e roofline is the perf target")
    print_fn("name,family,status,oracle_us,pallas_interpret_us,speedup,"
             "v5e_roofline_us")
    for r in rows:
        print_fn(",".join("" if r[k] is None else str(r[k])
                          for k in ("name", "family", "status", "oracle_us",
                                    "pallas_interpret_us", "speedup",
                                    "v5e_roofline_us")))

    missing = sorted(set(public) - {r["name"] for r in rows})
    if missing:
        print_fn(f"FAIL: public kernels ops without a bench row: {missing}")
        sys.exit(1)
    if args.json:
        os.makedirs(os.path.dirname(JSON_PATH), exist_ok=True)
        with open(JSON_PATH, "w") as f:
            json.dump({"rows": rows, "iters": args.iters,
                       "interpret": True}, f, indent=2)
        print_fn(f"wrote {JSON_PATH}")
    if churn:
        print_fn(f"FAIL: steady-state jit_compiles after warmup: {churn}")
        sys.exit(1)
    return rows


if __name__ == "__main__":
    main(sys.argv[1:])
