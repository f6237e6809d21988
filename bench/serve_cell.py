"""Serving cell: ``serve.Engine`` under open-loop traffic above the rate
it sustains.

Set-up makes the weights on the device from the seed, builds the engine
with the configuration's settings and runs its own ``warmup()``, which
compiles every shape it can emit.  A generator thread submits the mix's
requests into a ``RequestQueue`` at their due times; the main thread
drives the engine as ``Engine.run`` does.  Output tokens that reach the
host by the window's end count, the step in flight at the end in
proportion to the share of its time inside the window (tokens arrive a
step at a time, and a step of the mixed prefill path takes seconds).
The engine then stops and hands back what it had not finished with the
tokens it had served (``Engine.reclaim_requests``); that work is neither
failed nor counted.

After the window the engine is freed and the family's reference
(``bench.program.family``) recomputes, in float32, the logits at every
served position of a sample of the requests that were served tokens,
drawn from the seed, the longest among them; a served token's gap is how
far its reference logit lies below the reference's best.

Faults (tests and ``bench/calibrate.py``): ``alter_token`` changes the
last served token of the first request as the engine hands it over;
``control`` puts the reference in the program's place at float8 e4m3
(the precision step below the configurations' bf16): the gap compared
is that of the token the float8 reference ranks first.
"""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

from bench import program, traffic

SAMPLE = 16              # requests the reference recomputes
TRACE_S = 10.0           # length of the profiled stretch


class _Recorder:
    """Wraps the engine's compiled step and decode loop to keep each
    dispatch's packed per-row positions (host arrays it already built),
    for the operation counts of the traced stretch."""

    def __init__(self, fn, kind, log):
        self.fn, self.kind, self.log = fn, kind, log

    def __call__(self, *args):
        meta = np.array(args[-1])
        self.log.append((time.perf_counter(), self.kind, meta))
        return self.fn(*args)

    def __getattr__(self, name):
        return getattr(self.fn, name)


def build(cfg, seed, model=None, phases=None):
    """The engine over the seed's weights, warmed up (every shape it can
    emit compiled).  Pass ``model`` to reuse its compiled programs;
    ``phases`` gets the seconds of each set-up step."""
    from repro.models.model import build_model
    from repro.serve import Engine, EngineConfig
    phases = {} if phases is None else phases
    t = time.perf_counter()
    fam = program.family(cfg)
    mc = program.model_config(cfg)
    model = model or build_model(mc)
    params = fam.to_program(
        fam.make_weights(fam.dims(mc), seed, mc.param_dtype))
    program.check_layout(model, params)
    phases["weights_s"] = time.perf_counter() - t
    t = time.perf_counter()
    engine = Engine(model, params, EngineConfig(**cfg["engine"]))
    del params
    phases["engine_s"] = time.perf_counter() - t
    t = time.perf_counter()
    engine.warmup()
    phases["warmup_s"] = time.perf_counter() - t
    return engine


def serve(engine, plan, seconds, tracer, t_start, log=None):
    """Drive ``engine`` through one window of ``plan``; returns what was
    measured, and every request's served tokens (``served``)."""
    from repro.serve import Request, RequestQueue
    jit0 = engine.metrics_snapshot()["counters"]["jit_compiles"]
    q = RequestQueue()
    late = []
    stop = threading.Event()

    def generate(t0):
        for d in plan:
            while not stop.is_set():
                dt = t0 + d.due_s - time.perf_counter()
                if dt <= 0:
                    break
                time.sleep(min(dt, 0.05))
            if stop.is_set():
                return
            late.append(time.perf_counter() - (t0 + d.due_s))
            q.submit(Request(prompt=d.prompt, max_new_tokens=d.max_new_tokens,
                             rid=d.rid))

    if log is not None:
        engine._step_fn = _Recorder(engine._step_fn, "step", log)
        if engine._loop_fn is not None:
            engine._loop_fn = _Recorder(engine._loop_fn, "loop", log)
    results = {}
    trace_s = min(TRACE_S, seconds / 2)
    c0 = engine.metrics_snapshot()["counters"]
    tokens = lambda: engine.metrics_snapshot()["counters"]["generated_tokens"]
    t0 = time.perf_counter()
    marks = [(t0, c0["generated_tokens"])]      # (time, tokens on the host)
    t_end = t0 + seconds
    gen = threading.Thread(target=generate, args=(t0,), daemon=True)
    gen.start()
    t_tr = [None, None]
    trace_state = 0 if tracer.enabled else 2
    try:
        while True:
            now = time.perf_counter()
            if trace_state == 0 and now >= t0 + (seconds - trace_s) / 2:
                tracer.start()
                t_tr[0], trace_state = time.perf_counter(), 1
            elif trace_state == 1 and now >= t_tr[0] + trace_s:
                tracer.stop()
                t_tr[1], trace_state = time.perf_counter(), 2
            if now >= t_end:
                break
            for r in q.drain():
                engine.submit(r)
            if not engine.has_work:
                with tracer.span("wait_for_requests"):
                    time.sleep(0.0005)
                continue
            with tracer.span("engine_step"):
                done = engine.step()
            marks.append((time.perf_counter(), tokens()))
            for res in done:
                results[res.rid] = res
    finally:
        stop.set()
        gen.join()
        if trace_state == 1:
            tracer.stop()
            t_tr[1] = time.perf_counter()
    ran_s = time.perf_counter() - t0
    snap = engine.metrics_snapshot()
    c1 = snap["counters"]
    marks.append((t0 + ran_s, c1["generated_tokens"]))
    if c1["jit_compiles"] - jit0:
        raise SystemExit(f"{c1['jit_compiles'] - jit0} compiles inside the "
                         "window")
    # what the engine had not finished, with the tokens it had served
    unfinished, finished = engine.reclaim_requests()
    for res in finished:
        results[res.rid] = res
    served = {rid: [int(t) for t in res.tokens]
              for rid, res in results.items()
              if res.fault is None and len(res.tokens)}
    for req in unfinished:
        toks = [int(t) for t in req.prompt[req.orig_prompt_len:]]
        if toks:
            served[req.rid] = toks
    return {"t0": t0, "setup_s": t0 - t_start, "window_s": seconds,
            "ran_s": ran_s,
            "results": results, "served": served, "late": late,
            "tokens_in_window": _tokens_at(marks, t_end) - c0["generated_tokens"],
            "tokens_to_last_fetch": c1["generated_tokens"] - c0["generated_tokens"],
            "c0": c0, "c1": c1, "traced": tuple(t_tr)}


def _tokens_at(marks, t):
    """Tokens on the host at time ``t``, interpolated between the step
    that ended last before ``t`` and the first that ended after it."""
    (ta, ca), (tb, cb) = marks[0], marks[-1]
    for tm, cm in marks:
        if tm <= t:
            ta, ca = tm, cm
        else:
            tb, cb = tm, cm
            break
    return ca if tb <= ta else ca + (cb - ca) * (t - ta) / (tb - ta)


def run(*, cfg, mix, seed, seconds, tracer, devs, t_start, faults):
    from bench.harness import memory_peak
    fam = program.family(cfg)
    mc = program.model_config(cfg)
    dims = fam.dims(mc)
    phases = {}
    engine = build(cfg, seed, phases=phases)
    max_len = engine.cfg.max_seq_len
    plan = traffic.schedule(mix, seed, seconds, mc.vocab_size)
    by_rid = {d.rid: d for d in plan}
    log = [] if tracer.enabled else None
    out = serve(engine, plan, seconds, tracer, t_start, log)
    memory = memory_peak(devs)
    del engine
    gc.collect()
    served = out["served"]
    if faults.get("alter_token") and served:
        toks = served[min(served)]
        toks[-1] = (toks[-1] + 1) % mc.vocab_size
    control = bool(faults.get("control"))
    window_s = out["window_s"]
    due = sum(1 for d in plan if d.due_s <= window_s)
    failed = sum(1 for r in out["results"].values() if r.fault is not None)
    sample = _sample(served, by_rid, seed)
    gaps = _gaps(fam, dims, seed, mc.param_dtype, sample, by_rid, served,
                 max_len, "fp8" if control else None)
    c0, c1 = out["c0"], out["c1"]
    late = out["late"]
    rec = {"kind": "serve", "setup_s": out["setup_s"], "window_s": window_s,
           "attempted": due, "failed": failed,
           "tokens_in_window": out["tokens_in_window"],
           "decode_slot_steps": c1["decode_slot_steps"] - c0["decode_slot_steps"],
           "decode_active_slot_steps": (c1["decode_active_slot_steps"]
                                        - c0["decode_active_slot_steps"]),
           "sound": bool(sample),
           "checks": {"logit_gap": gaps["control" if control else "program"]},
           "memory_peak": memory, "family": cfg["family"], "dims": dims,
           "info": {"requests_due": len(plan), "due_in_window": due,
                    "finished": len(out["results"]), "failed": failed,
                    "served_requests": len(served),
                    "window_s": window_s, "ran_s": out["ran_s"],
                    "tokens_to_last_fetch": out["tokens_to_last_fetch"],
                    "setup_s": out["setup_s"],
                    "setup_phases_s": phases,
                    "generator_late_ms_p50_max": [
                        1e3 * float(np.median(late)) if late else None,
                        1e3 * max(late) if late else None],
                    "tokens_in_window": out["tokens_in_window"],
                    "counters": c1, "sample": sample,
                    "sample_served_tokens": sum(len(served[r])
                                                for r in sample),
                    "logit_gap_program": gaps["program"],
                    "memory_peak_bytes": memory}}
    if tracer.enabled:
        _reduce(rec, tracer.reduce(), log, fam, dims, *out["traced"])
    return rec


def _sample(served, by_rid, seed):
    """The longest request that was served tokens (prompt and served
    tokens) and SAMPLE - 1 more drawn from the seed."""
    done = sorted(served)
    if not done:
        return []
    longest = max(done, key=lambda r: len(by_rid[r].prompt) + len(served[r]))
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 11]))
    rest = [r for r in done if r != longest]
    pick = rng.choice(len(rest), min(SAMPLE - 1, len(rest)), replace=False)
    return [longest] + sorted(rest[i] for i in pick)


def reference_gaps(fam, w32, dims, prompt, served, max_len, prec="f32"):
    """Per served token: the best logit of the family ``fam``'s
    reference minus its logit for the served token; with ``prec`` other
    than f32, also the gap of the token that precision ranks first.
    Positions are padded to ``max_len`` so that one compiled program
    serves every request."""
    import jax.numpy as jnp
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    n = len(served)
    toks = np.zeros(max_len, np.int32)
    toks[:len(seq)] = seq
    idx = np.full(max_len, len(prompt) - 1, np.int32)
    idx[:n] = len(prompt) - 1 + np.arange(n)
    lg = np.asarray(fam.logits_at(w32, jnp.asarray(toks), jnp.asarray(idx),
                                  dims))[:n]
    best = lg.max(-1)
    own = best - lg[np.arange(n), np.asarray(served)]
    if prec == "f32":
        return own, None
    lo = np.asarray(fam.logits_at(w32, jnp.asarray(toks), jnp.asarray(idx),
                                  dims, prec))[:n]
    return own, best - lg[np.arange(n), lo.argmax(-1)]


def _gaps(fam, dims, seed, dtype, sample, by_rid, served, max_len,
          control=None):
    import jax.numpy as jnp
    w = fam.make_weights(dims, seed, dtype)
    w32 = {k: v.astype(jnp.float32) for k, v in w.items()}
    del w
    prog, ctrl = [], []
    for rid in sample:
        own, lo = reference_gaps(fam, w32, dims, by_rid[rid].prompt,
                                 served[rid], max_len, control or "f32")
        prog.append(float(own.max()))
        if lo is not None:
            ctrl.append(float(lo.max()))
    del w32
    return {"program": max(prog) if prog else float("inf"),
            "control": max(ctrl) if ctrl else float("inf")}


def _reduce(rec, trace, log, fam, dims, t_tr0, t_tr1):
    from bench import trace_reduce as tr
    w0, w1 = tr.window_of(trace)
    busy = tr.busy(trace, w0, w1)
    positions = context = emitted = 0
    paged = []
    for t, kind, meta in log:
        if not t_tr0 <= t <= t_tr1:
            continue
        if kind == "step":
            pos, valid, dst, rid = meta[0], meta[1], meta[3], meta[5]
            positions += int(valid.sum())
            context += int((valid * pos + valid * (valid + 1) // 2).sum())
            emitted += int(((valid > 0) & (dst >= 0)).sum())
            if valid.max() <= 1:
                paged.append(_paged_cost(fam, dims, pos, valid, rid))
        else:
            pos0, steps = meta[0], meta[1]
            positions += int(steps.sum())
            context += int((steps * pos0 + steps * (steps + 1) // 2).sum())
            emitted += int(steps.sum())
    n_exec, paged_ns = tr.op_stats(trace, w0, w1, FLASH_DECODE_PAGED)
    rec.update({
        "traced_s": (w1 - w0) * 1e-9,
        "busy_s": tr.mean(busy) * 1e-9,
        "traced_flops": fam.serve_flops(dims, positions, context, emitted),
        "paged_costs": paged,
        "paged_executions": n_exec, "paged_s": paged_ns * 1e-9,
        "breakdown": {"device_ops": tr.top_ops(trace, w0, w1),
                      "idle_gaps": tr.idle_gaps(trace, w0, w1)}})


def _paged_cost(fam, dims, pos, valid, rid):
    """(operations, bytes) that ``flash_decode_paged`` needs in one step
    of one row per token, by the family ``fam``'s count: each row's
    query against the K/V up to its own position, the K/V of a request
    read once."""
    live = valid > 0
    ctx = {}
    for r, p in zip(rid[live].tolist(), pos[live].tolist()):
        ctx[r] = max(ctx.get(r, 0), p + 1)
    return fam.attend_cost(dims, int((pos[live] + 1).sum()),
                           int(live.sum()), sum(ctx.values()))


# flash_decode_paged in the device trace.  Pallas calls carry no name of
# their own there (a later change is to give them one), so the kernel
# is found by its signature: a custom call with one query row per token,
# (R, 1, H, hd) bf16, whose first operand is the (R, T) int32 block table.
FLASH_DECODE_PAGED = (r"= bf16\[\d+,1,\d+,\d+\]\{[^}]*\} "
                      r"custom-call\(s32\[\d+,\d+\]\{")
