"""The program's own names in a profiler trace: its host spans, its
named scopes and its kernels.

The program names what it does.  ``serve.telemetry.SpanTracer`` writes
host spans ``repro:<layer>.<what>`` as profiler annotations
(``engine.step`` and its ``schedule``/``reserve``/``build``/
``dispatch``/``fetch`` children, ``dispatcher.route``), each on the
thread that ran it and with its arguments (``step``, ``phase``, ...).
``jax.named_scope`` puts each LSGD phase and model part on the
name-stack path (``op_name``) of every HLO op it emits, and a
``name=`` on every Pallas call names the kernel's custom-call
instruction.  The reductions here read those beside
``bench/trace_reduce.py``'s, on a trace that extends its form:

    trace = {"devices": {plane: [(op, start_ns, dur_ns), ...]},
             "host":    [(span, start_ns, dur_ns), ...],        bench:
             "program": [(span, start_ns, dur_ns, thread, args), ...]}

A TPU's device events carry the instruction's HLO text as their name
and no name-stack path among their stats (offset, duration, time
scale), so a scope is found through the executable that ran: its
``as_text()`` maps each instruction to its ``op_name``.  A kernel is
found by its instruction's name, which is the kernel's.

``load`` and ``idle_gaps`` extend ``trace_reduce``'s own; once the
cells report the planned metrics, they belong in ``trace_reduce`` and
the probe below goes.

Run as a script, it profiles one cell's program as the cell runs it and
prints one JSON line of what the per-layer metrics planned on these
names read (PERF.md §3; they are not in BENCHMARK.json):

    python3 bench/program_trace.py --workload <cell> --seed <n> [--out F]
"""
from __future__ import annotations

import argparse
import gc
import glob
import json
import os
import re
import sys
import time
from pathlib import Path
from typing import Dict, Iterable, Optional, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace_reduce as tr  # noqa: E402

SAMPLERS = ("greedy_sample", "gumbel_sample", "topk_gumbel_sample")
LSGD_PHASES = ("lsgd.apply_pending", "lsgd.fwd_bwd", "lsgd.sync",
               "lsgd.pending_write")
MODEL_SCOPES = ("attend", "mlp", "lm_head_loss")


def load(trace_dir: str) -> dict:
    """``trace_reduce.load_xplane``'s trace of ``trace_dir`` with the
    program's ``repro:`` spans under ``"program"``."""
    from jax.profiler import ProfileData
    trace = tr.load_xplane(trace_dir)
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(sorted(paths)[-1])
    trace["program"] = sorted(
        ((e.name, e.start_ns, e.duration_ns, f"{plane.name}/{i}",
          dict(e.stats))
         for plane in pd.planes if plane.name.startswith("/host")
         for i, line in enumerate(plane.lines) for e in line.events
         if e.name.startswith("repro:")),
        key=lambda e: (e[1], -e[2]))
    return trace


def op_key(event_name: str) -> str:
    """"%fusion.12 = bf16[8]{0} fusion(...)" or "fusion.12" -> "fusion.12"."""
    return event_name.lstrip("%").split(" = ")[0].split(" ")[0]


def kernel_of(event_name: str) -> str:
    """The instruction's name without its instance number: a named
    Pallas call's custom call carries its kernel's name."""
    return re.sub(r"\.\d+$", "", op_key(event_name))


def op_paths_from_hlo(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` path, from a compiled executable's
    ``as_text()``.  JAX's persistent compile cache leaves ``op_name``
    out of its key, so read this from an executable compiled by the
    build that is measured, not one loaded from an older build's
    cache."""
    out = {}
    for m in re.finditer(r'^\s*(?:ROOT )?%([\w.-]+) = .*?metadata=\{'
                         r'[^}]*op_name="([^"]*)"', hlo_text, re.M):
        out[m.group(1)] = m.group(2)
    return out


def in_scope(path: str, scope: str) -> bool:
    """``scope`` is a component of the path, bare or inside a
    transformation (``transpose(jvp(attend))``)."""
    return re.search(r"(^|[/(])" + re.escape(scope) + r"($|[/)])",
                     path) is not None


def _first_device_leaves(trace):
    if not trace["devices"]:
        return []
    return tr.leaves(trace["devices"][sorted(trace["devices"])[0]])


def scope_time(trace: dict, w0: float, w1: float, scope: str,
               op_paths: Dict[str, str]) -> float:
    """ns, on the first device, of innermost ops inside [w0, w1] whose
    name-stack path (``op_paths``, keyed by ``op_key``) holds ``scope``."""
    tot = 0.0
    for n, s, d in _first_device_leaves(trace):
        path = op_paths.get(op_key(n))
        ov = min(s + d, w1) - max(s, w0)
        if ov > 0 and path is not None and in_scope(path, scope):
            tot += ov
    return tot


def kernel_time(trace: dict, w0: float, w1: float,
                kernels: Iterable[str]) -> Tuple[int, float]:
    """(count, summed ns) of the first device's executions of the named
    kernels that lie wholly inside [w0, w1]."""
    kernels = set(kernels)
    hits = [d for n, s, d in _first_device_leaves(trace)
            if w0 <= s and s + d <= w1 and kernel_of(n) in kernels]
    return len(hits), float(sum(hits))


def spans_in(trace: dict, w0: float, w1: float, name: str):
    """Program spans named ``name`` wholly inside [w0, w1]."""
    return [p for p in trace["program"]
            if p[0] == name and w0 <= p[1] and p[1] + p[2] <= w1]


def span_self_time(trace: dict, w0: float, w1: float, name: str,
                   children: Optional[Iterable[str]] = None
                   ) -> Tuple[int, float]:
    """(count, mean ns) over the ``name`` spans inside [w0, w1] of their
    self time: duration less the part that the spans inside them on
    their thread cover (only those named in ``children``, if given)."""
    children = None if children is None else set(children)
    selfs = []
    for span in spans_in(trace, w0, w1, name):
        _, s, d, thread, _ = span
        inner = [(c[1], c[1] + c[2]) for c in trace["program"]
                 if c is not span and c[3] == thread and s <= c[1]
                 and c[1] + c[2] <= s + d
                 and (children is None or c[0] in children)]
        selfs.append(d - tr.length(tr.union(inner)))
    return len(selfs), (sum(selfs) / len(selfs) if selfs else 0.0)


def idle_gaps(trace: dict, w0: float, w1: float, top: int = 10):
    """``trace_reduce.idle_gaps`` over the spans of either kind
    (``bench:`` or ``repro:``): each gap carries the innermost."""
    spans = trace["host"] + [p[:3] for p in trace["program"]]
    return tr.idle_gaps(
        {**trace, "host": sorted(spans, key=lambda e: (e[1], -e[2]))},
        w0, w1, top)


# ---------------------------------------------------------------------------
# the probe: one cell's program, profiled as the cell runs it
# ---------------------------------------------------------------------------


def span_cost_ns(n: int = 20000) -> dict:
    """Mean ns of one empty span: disabled, enabled with no profiler,
    enabled under a profiler."""
    import tempfile

    import jax
    from repro.serve.telemetry import SpanTracer

    def per_span(tracer):
        t = time.perf_counter()
        for _ in range(n):
            with tracer.span("engine", "probe", step=1):
                pass
        return (time.perf_counter() - t) / n * 1e9

    out = {"off": per_span(SpanTracer(False)),
           "on_no_profiler": per_span(SpanTracer(True))}
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            out["on_profiled"] = per_span(SpanTracer(True))
    return out


def probe_train(cfg, mix, seed, devs, steps: int = 5) -> dict:
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from bench import harness
    from bench import train_cell as tc
    # JAX's persistent cache leaves op_name metadata out of its key: a
    # step that an older build cached would keep that build's op_names.
    # This compile skips the cache, so the executable's text is current.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        pre = tc.prepare(cfg, mix, seed, devs, {})
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    state, step, feed, loader = (pre["state"], pre["step"], pre["feed"],
                                 pre["loader"])
    tracer = harness.Tracer(True)
    try:
        def run(n):
            # as the cell's window: at most two steps in flight
            nonlocal state
            losses = []
            for _ in range(n):
                b = next(loader)
                if len(losses) >= 2:
                    losses[-2].block_until_ready()
                state, (loss, _) = step(state, feed(b))
                losses.append(loss)
            jax.block_until_ready(state)

        run(2)
        b0, wait0 = loader.batches, loader.wait_s
        t = time.perf_counter()
        run(steps)
        untraced_s = (time.perf_counter() - t) / steps
        wait_ms = 1e3 * (loader.wait_s - wait0) / (loader.batches - b0)
        tracer.start()
        t = time.perf_counter()
        run(tc.TRACE_STEPS)
        traced_s = (time.perf_counter() - t) / tc.TRACE_STEPS
        tracer.stop()
        trace = load(tracer.dir)
        hlo = step.as_text()
    finally:
        loader.close()
        tracer.close()
    del state, step, pre
    gc.collect()
    paths = op_paths_from_hlo(hlo)
    w0, w1 = tr.window_of(trace)
    n = tc.TRACE_STEPS
    busy = tr.mean(tr.busy(trace, w0, w1))
    leaves = [e for e in _first_device_leaves(trace)
              if w0 <= e[1] and e[1] + e[2] <= w1]
    named = sum(d for n_, _, d in leaves if op_key(n_) in paths)
    # op time under no LSGD phase: by op, and split into ops with no
    # op_name (the compiler's own copies), ops under a model scope
    # (hoisted out of the differentiated function) and the rest
    outside: Dict[str, float] = {}
    kinds = {"no_op_name": 0.0, "model_scope": 0.0, "other": 0.0}
    for n_, _, d in leaves:
        path = paths.get(op_key(n_), "")
        if not any(in_scope(path, p) for p in LSGD_PHASES):
            key = f"{op_key(n_)} {path}"
            outside[key] = outside.get(key, 0.0) + d
            kind = ("no_op_name" if not path else "model_scope"
                    if any(in_scope(path, m) for m in MODEL_SCOPES)
                    else "other")
            kinds[kind] += d
    scopes = {s: scope_time(trace, w0, w1, s, paths) / n * 1e-6
              for s in LSGD_PHASES + MODEL_SCOPES}
    return {
        "metrics": {"attention_ms.train": scopes["attend"],
                    "optimizer_ms.train": scopes["lsgd.apply_pending"],
                    "loader_wait_ms.train": wait_ms},
        "scope_ms_per_step": scopes,
        "lsgd_phases_share_of_busy": (
            sum(scopes[s] for s in LSGD_PHASES) * n * 1e6 / busy
            if busy else None),
        "busy_ms_per_step": busy / n * 1e-6,
        "step_s_untraced_traced": [untraced_s, traced_s],
        "share_of_op_time_with_a_path": (named / sum(d for _, _, d in leaves)
                                         if leaves else None),
        "outside_lsgd_phases_share_of_busy": {
            k: v / busy if busy else None for k, v in kinds.items()},
        "outside_lsgd_phases_ms": sorted(
            ((k, v / n * 1e-6) for k, v in outside.items()),
            key=lambda kv: -kv[1])[:10],
        "top_ops": tr.top_ops(trace, w0, w1),
        "idle_gaps": idle_gaps(trace, w0, w1)}


def probe_serve(cfg, mix, seed, seconds: float, t_start: float) -> dict:
    from bench import harness, program, traffic
    from bench import serve_cell as sc
    mc = program.model_config(cfg)
    plan = traffic.schedule(mix, seed, seconds, mc.vocab_size)
    engine, model, tokens_per_s = None, None, {}
    for label, spans in (("off", False), ("spans_on", True)):
        engine = sc.build(cfg, seed, model=model)
        model = engine.model
        engine.telemetry.tracer.enabled = spans
        out = sc.serve(engine, plan, seconds, harness.Tracer(False), t_start)
        tokens_per_s[label] = out["tokens_in_window"] / seconds
        del engine
        gc.collect()
    engine = sc.build(cfg, seed, model=model)
    engine.telemetry.tracer.enabled = True
    tracer = harness.Tracer(True)
    try:
        out = sc.serve(engine, plan, seconds, tracer, t_start)
        tokens_per_s["spans_on_profiled"] = out["tokens_in_window"] / seconds
        trace = load(tracer.dir)
    finally:
        tracer.close()
    del engine, model
    gc.collect()
    w0, w1 = tr.window_of(trace)
    n_disp = len(spans_in(trace, w0, w1, "repro:engine.dispatch"))
    n_steps, host_ns = span_self_time(trace, w0, w1, "repro:engine.step",
                                      children=("repro:engine.fetch",))
    samp_n, samp_ns = kernel_time(trace, w0, w1, SAMPLERS)
    by_regex = tr.op_stats(trace, w0, w1, sc.FLASH_DECODE_PAGED)
    by_name = kernel_time(trace, w0, w1, ("flash_decode_paged",))
    busy = tr.mean(tr.busy(trace, w0, w1))
    return {
        "metrics": {"engine_host_ms.overload": host_ns * 1e-6 if n_steps
                    else None,
                    "sampler_ms.overload": (samp_ns / n_disp * 1e-6
                                            if n_disp else None)},
        "engine_steps_dispatches": [n_steps, n_disp],
        "sampler_executions": samp_n,
        "flash_decode_paged_by_regex_by_name": [list(by_regex),
                                                list(by_name)],
        "span_ms_mean": {
            name: span_self_time(trace, w0, w1, name, children=())[1] * 1e-6
            for name in sorted({p[0] for p in trace["program"]})},
        "spans_per_step": (len([p for p in trace["program"]
                                if w0 <= p[1] <= w1]) / n_steps
                           if n_steps else None),
        "tokens_per_s": tokens_per_s,
        "idle_share": 1.0 - busy / (w1 - w0),
        "top_ops": tr.top_ops(trace, w0, w1),
        "idle_gaps": idle_gaps(trace, w0, w1)}


def probe(cell: str, seed: int, seconds: float, *, require_tpu=True,
          overrides: Optional[dict] = None) -> dict:
    from bench import harness
    t_start = time.perf_counter()
    _, cell_spec, cfg, mix, _ = harness.load_cell(cell)
    overrides = overrides or {}
    cfg = {**cfg, **overrides.get("config", {})}
    mix = {**mix, **overrides.get("traffic", {})}
    harness.program.import_program()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    devs = harness.chips(cell_spec["chips"], require_tpu)
    out = {"workload": cell, "seed": seed,
           "device": {"platform": devs[0].platform,
                      "kind": devs[0].device_kind, "count": len(devs)}}
    if mix["kind"] == "train":
        out.update(probe_train(cfg, mix, seed, devs))
    else:
        out.update(probe_serve(cfg, mix, seed, seconds, t_start))
    out["span_cost_ns"] = span_cost_ns()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0,
                    help="length of each serving window")
    ap.add_argument("--out", default=None, help="also write the line here")
    args = ap.parse_args(argv)
    try:
        out = probe(args.workload, args.seed, args.seconds)
    except SystemExit as e:
        print(f"program_trace: {e}", file=sys.stderr, flush=True)
        return 3
    line = json.dumps(out, default=float)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
