"""Serving telemetry: registry/histogram unit invariants, request
lifecycle trace invariants on real engine runs, the jit-compile
steady-state regression guard, the engine's spans in the profiler's
trace, and the cluster metrics()/stats back-compat contract.

The load-bearing invariants (also property-tested in
test_telemetry_props.py):
  * histogram bucket counts sum to the observation counter;
  * every submitted request reaches exactly ONE terminal event
    (``trace_double_terminals == 0``);
  * TTFT <= e2e (both measured from the same submit stamp);
  * spans are disjoint-or-nested per thread, and each fetch names
    the step that dispatched it;
  * after warmup, steady-state serving triggers zero new jit compiles
    at every dispatch depth and under mixed prefill/decode.
"""
import glob
import json
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.models.model import build_model
from repro.serve import (Engine, EngineConfig, Request, ServeCluster,
                         Telemetry)
from repro.serve.telemetry import (Counter, Gauge, Histogram,
                                   JsonlMetricsWriter, MetricsRegistry)

from test_serve_decode_loop import _tiny_qwen2


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = _tiny_qwen2()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _ecfg(**kw):
    base = dict(max_batch=3, block_size=8, num_blocks=65, max_seq_len=64,
                prefill_chunk=16, prefill_token_budget=24)
    base.update(kw)
    return EngineConfig(**base)


def _requests(cfg, n, rid0, seed=0, pmax=20, gmax=10):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, cfg.vocab_size,
                                        (int(rng.integers(3, pmax)),)),
                    max_new_tokens=int(rng.integers(3, gmax)),
                    rid=rid0 + i)
            for i in range(n)]


# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------


def test_counter_gauge_basics():
    c, g = Counter(), Gauge()
    c.inc()
    c.inc(4)
    assert c.value == 5
    g.set(7)
    g.dec(2)
    g.inc()
    assert g.value == 6


def test_histogram_bucket_counts_sum_to_counter():
    h = Histogram(buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.1, 0.5, 1.0, 5.0, 50.0, 500.0):
        h.observe(v)
    assert sum(h.counts) == h.count == 7
    assert h.min == 0.05 and h.max == 500.0
    assert h.counts[-1] == 2                      # overflow bucket
    snap = h.snapshot()
    assert snap["count"] == 7
    assert h.min <= snap["p50"] <= snap["p95"] <= snap["p99"] <= h.max


def test_histogram_single_observation_reports_itself():
    h = Histogram()
    h.observe(0.42)
    s = h.snapshot()
    assert s["p50"] == pytest.approx(0.42)
    assert s["p99"] == pytest.approx(0.42)
    assert s["mean"] == pytest.approx(0.42)


def test_histogram_merge_requires_same_buckets_and_sums():
    a, b = Histogram(), Histogram()
    for v in (0.001, 0.02, 3.0):
        a.observe(v)
    for v in (0.5, 200.0):
        b.observe(v)
    a.merge(b)
    assert a.count == 5
    assert sum(a.counts) == 5
    assert a.max == 200.0
    with pytest.raises(ValueError):
        a.merge(Histogram(buckets=(1.0, 2.0)))


def test_registry_get_or_create_and_labels():
    reg = MetricsRegistry()
    c1 = reg.counter("x", replica=0)
    c2 = reg.counter("x", replica=0)
    c3 = reg.counter("x", replica=1)
    assert c1 is c2 and c1 is not c3
    c1.inc(3)
    c3.inc(1)
    snap = reg.snapshot()
    assert snap["counters"]["x{replica=0}"] == 3
    assert snap["counters"]["x{replica=1}"] == 1
    reg.histogram("lat", replica=0).observe(0.5)
    reg.histogram("lat", replica=1).observe(2.0)
    merged = reg.merged_histogram("lat")
    assert merged.count == 2 and merged.max == 2.0


def test_jsonl_metrics_writer(tmp_path):
    reg = MetricsRegistry()
    c = reg.counter("ticks")
    path = str(tmp_path / "metrics.jsonl")
    with JsonlMetricsWriter(reg, path, interval_s=0.01) as w:
        c.inc(5)
    rows = [json.loads(line) for line in open(path)]
    assert rows                                   # final snapshot at stop
    assert rows[-1]["counters"]["ticks"] == 5
    assert "time" in rows[-1]


# ---------------------------------------------------------------------------
# engine lifecycle invariants
# ---------------------------------------------------------------------------


def _check_lifecycle(telemetry, rids, tokens_of=None):
    book = telemetry.requests
    assert book.double_terminals.value == 0
    for rid in rids:
        tr = book.get(rid)
        assert tr is not None and tr.terminal == "complete"
        s = tr.stamps
        assert s["submit"] <= s["admit"] <= s["first_token"] <= s["complete"]
        ttft = s["first_token"] - s["submit"]
        e2e = s["complete"] - s["submit"]
        assert 0.0 <= ttft <= e2e
        if tokens_of is not None:
            assert tr.tokens == tokens_of[rid]


@pytest.mark.parametrize("spd", [1, 8])
def test_engine_run_trace_invariants(tiny_lm, spd):
    cfg, model, params = tiny_lm
    tel = Telemetry()
    eng = Engine(model, params, _ecfg(steps_per_dispatch=spd),
                 telemetry=tel)
    reqs = _requests(cfg, 4, 41000, seed=spd)
    res = eng.run([Request(prompt=r.prompt.copy(),
                           max_new_tokens=r.max_new_tokens, rid=r.rid)
                   for r in reqs])
    _check_lifecycle(tel, [r.rid for r in reqs],
                     tokens_of={rid: len(v.tokens)
                                for rid, v in res.items()})
    snap = eng.metrics_snapshot()
    assert snap["latency"]["e2e"]["count"] == len(reqs)
    assert snap["latency"]["ttft"]["count"] == len(reqs)
    # histograms observe at most once per request
    assert snap["latency"]["tpot"]["count"] <= len(reqs)


def test_engine_counters_snapshot_contract(tiny_lm):
    """metrics_snapshot()["counters"] is the flat counter surface:
    plain ints, the full engine key set, values that accumulate across
    a run."""
    cfg, model, params = tiny_lm
    eng = Engine(model, params, _ecfg())
    eng.run([Request(prompt=r.prompt.copy(),
                     max_new_tokens=r.max_new_tokens, rid=r.rid)
             for r in _requests(cfg, 2, 42000)])
    s = eng.metrics_snapshot()["counters"]
    for k in ("steps", "decode_steps", "prefill_tokens",
              "generated_tokens", "preemptions", "model_calls",
              "host_syncs", "loop_dispatches", "loop_truncations",
              "jit_compiles"):
        assert isinstance(s[k], int), k
    assert s["generated_tokens"] > 0
    assert s["steps"] > 0


def _hand_count(tables, pos, width, block_size):
    """Query runs and visited pages of one dispatch, token by token: a
    token continues the run when its row's table is the previous
    token's and its position the next one; a tile ends with its run,
    at a grid block's edge or at ``tile`` tokens, and visits pages 0 ..
    its last // block."""
    from repro.kernels.flash_decode import tile_shape
    block_rows, tile = tile_shape(tables.shape[0], width)
    runs, pages, prev, n, last, t = 0, 0, None, 0, 0, 0
    for r in range(tables.shape[0]):
        for j in range(width):
            cur = (tuple(tables[r]), int(pos[r]) + j)
            same = prev is not None and cur[0] == prev[0] \
                and cur[1] == prev[1] + 1
            if not same or n == tile or t % (block_rows * width) == 0:
                if prev is not None:
                    pages += min(last // block_size, tables.shape[1] - 1) + 1
                runs += not same
                n = 0
            prev, last, n, t = cur, cur[1], n + 1, t + 1
    return runs, pages + min(last // block_size, tables.shape[1] - 1) + 1


def test_paged_run_counters_match_hand_count(tiny_lm):
    """paged_query_runs / paged_kv_pages equal a token-by-token count of
    every fused dispatch's layout (prefill, mixed and decode steps)."""
    cfg, _, _ = tiny_lm
    cfg = cfg.replace(attn_impl="pallas")
    model = build_model(cfg)
    ecfg = _ecfg()
    eng = Engine(model, model.init(jax.random.key(0)), ecfg)
    seen = []

    class Record:
        def __init__(self, fn):
            self.fn = fn

        def __call__(self, *args):
            tokens, tables, meta = (np.array(a) for a in args[3:])
            seen.append(_hand_count(tables, meta[0], tokens.shape[1],
                                    ecfg.block_size) + (tokens.size,))
            return self.fn(*args)

        def __getattr__(self, name):
            return getattr(self.fn, name)

    eng._step_fn = Record(eng._step_fn)
    eng.run(_requests(cfg, 5, 44000, pmax=40))
    c = eng.metrics_snapshot()["counters"]
    runs, pages, tokens = (sum(x) for x in zip(*seen))
    assert len(seen) == c["model_calls"] > 0
    assert (c["paged_query_runs"], c["paged_kv_pages"]) == (runs, pages)
    assert runs < tokens        # prompt tokens share runs


def test_kv_and_scheduler_gauges_settle_to_idle(tiny_lm):
    cfg, model, params = tiny_lm
    tel = Telemetry()
    eng = Engine(model, params, _ecfg(), telemetry=tel)
    eng.run([Request(prompt=r.prompt.copy(),
                     max_new_tokens=r.max_new_tokens, rid=r.rid)
             for r in _requests(cfg, 3, 43000)])
    g = tel.registry.snapshot()["gauges"]
    label = f"{{arch={cfg.name},replica=0}}"
    # everything drained: free-list full again, nothing live or waiting
    assert g["kv_blocks_free" + label] == 64      # num_blocks - trash
    assert g["engine_live_seqs" + label] == 0
    assert g["sched_waiting" + label] == 0
    assert g["sched_prefilling" + label] == 0


def test_preemption_counted_and_single_terminal(tiny_lm):
    """The starvation workload from the decode-loop tests: preempted +
    re-admitted requests must still reach exactly one terminal and keep
    their ORIGINAL submit/admit stamps (first stamp wins)."""
    cfg, model, params = tiny_lm
    tel = Telemetry()
    eng = Engine(model, params, EngineConfig(
        max_batch=3, block_size=4, num_blocks=10, max_seq_len=32,
        prefill_chunk=8, prefill_token_budget=16, steps_per_dispatch=8),
        telemetry=tel)
    rng = np.random.default_rng(2)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, (12,)),
                    max_new_tokens=14, rid=44000 + i) for i in range(3)]
    eng.run([Request(prompt=r.prompt.copy(),
                     max_new_tokens=r.max_new_tokens, rid=r.rid)
             for r in reqs])
    preempts = eng.metrics_snapshot()["counters"]["preemptions"]
    assert preempts > 0
    _check_lifecycle(tel, [r.rid for r in reqs])
    assert sum(t.preemptions for t in tel.requests.traces()) == preempts


# ---------------------------------------------------------------------------
# jit-compile steady-state guard (the PR-5 recompile bug, as a metric)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spd", [1, 8])
def test_zero_new_compiles_in_steady_state(tiny_lm, spd):
    """After warmup, serving mixed prefill/decode traffic at any
    dispatch depth must hit only warm jit caches: a recompile mid-serve
    is a multi-second stall on a real model."""
    cfg, model, params = tiny_lm
    eng = Engine(model, params, _ecfg(steps_per_dispatch=spd))
    if eng._jit_cache_total(eng._jit_fns()) is None:
        pytest.skip("jit cache size introspection unsupported")
    eng.warmup()
    # mixed traffic: staggered arrivals keep prefill chunks interleaving
    # with decode (mixed-phase dispatches), long + short generations
    reqs = _requests(cfg, 5, 45000 + spd, seed=7, pmax=24, gmax=14)
    for i, r in enumerate(reqs):
        r.arrival_time = 0.002 * i
    eng.run([Request(prompt=r.prompt.copy(),
                     max_new_tokens=r.max_new_tokens, rid=r.rid,
                     arrival_time=r.arrival_time) for r in reqs])
    c = eng.metrics_snapshot()["counters"]
    assert c["prefill_tokens"] > 0
    assert c["decode_steps"] > 0
    assert c["jit_compiles"] == 0, \
        "steady-state serving recompiled after warmup"


# ---------------------------------------------------------------------------
# spans in the profiler's trace
# ---------------------------------------------------------------------------


def _profiled_spans(trace_dir):
    """``repro:`` host spans of the profile under ``trace_dir``, per
    thread (one host-plane line each): [(name, start_ns, end_ns,
    stats)] in start order."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for i, line in enumerate(plane.lines):   # names repeat
            spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                      dict(e.stats)) for e in line.events
                     if e.name.startswith("repro:")]
            if spans:
                out[f"{plane.name}/{i}"] = sorted(
                    spans, key=lambda e: (e[1], -e[2]))
    return out


def _assert_nested(spans):
    """Spans of one thread are disjoint or properly nested."""
    stack = []
    for name, t0, t1, _ in spans:
        assert t1 >= t0
        while stack and t0 >= stack[-1]:
            stack.pop()
        if stack:
            assert t1 <= stack[-1], f"{name} overlaps its parent"
        stack.append(t1)


def _children(spans, parent):
    """Spans lying inside ``parent`` (same thread)."""
    _, p0, p1, _ = parent
    return [s for s in spans if s is not parent and p0 <= s[1]
            and s[2] <= p1]


def test_engine_trace_export_well_formed(tiny_lm, tmp_path):
    cfg, model, params = tiny_lm
    tel = Telemetry(trace=True)
    eng = Engine(model, params, _ecfg(steps_per_dispatch=8), telemetry=tel)
    eng.warmup()
    with jax.profiler.trace(str(tmp_path)):
        eng.run([Request(prompt=r.prompt.copy(),
                         max_new_tokens=r.max_new_tokens, rid=r.rid)
                 for r in _requests(cfg, 3, 46000)])
    by_thread = _profiled_spans(str(tmp_path))
    assert len(by_thread) == 1            # the engine ran on this thread
    (spans,) = by_thread.values()
    _assert_nested(spans)
    names = {s[0] for s in spans}
    assert {"repro:engine.step", "repro:engine.schedule",
            "repro:engine.reserve", "repro:engine.build",
            "repro:engine.dispatch", "repro:engine.fetch"} <= names
    steps = [s for s in spans if s[0] == "repro:engine.step"]
    assert [s[3]["step"] for s in steps] == list(range(len(steps)))
    # every span lies inside an engine.step
    assert sum(len(_children(spans, s)) for s in steps) \
        == len(spans) - len(steps)
    # the phases the decode loop and the fused step dispatch under
    phases = {s[3]["phase"] for s in spans
              if s[0] == "repro:engine.dispatch"}
    assert {"loop", "prefill"} <= phases, phases
    # every fetch names the step that dispatched what it fetched
    dispatched = {s[3]["step"] for s in steps
                  if any(c[0] == "repro:engine.dispatch"
                         for c in _children(spans, s))}
    fetched = [s[3]["step"] for s in spans if s[0] == "repro:engine.fetch"]
    assert fetched and set(fetched) <= dispatched
    assert len(fetched) == len(set(fetched))  # each dispatch fetched once
    assert tel.registry.snapshot()["counters"][
        "engine_host_syncs{arch=%s,replica=0}" % cfg.name] == len(fetched)


def test_tracing_off_is_free(tiny_lm, tmp_path, monkeypatch):
    """With tracing off (the default) no annotation is created: every
    span is the one shared null context."""
    cfg, model, params = tiny_lm
    made = []
    real = jax.profiler.TraceAnnotation
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda *a, **k: made.append(a) or real(*a, **k))
    tel = Telemetry()
    eng = Engine(model, params, _ecfg(), telemetry=tel)
    eng.warmup()
    with jax.profiler.trace(str(tmp_path)):
        eng.run([Request(prompt=r.prompt.copy(),
                         max_new_tokens=r.max_new_tokens, rid=r.rid)
                 for r in _requests(cfg, 2, 47000)])
    assert made == []
    assert _profiled_spans(str(tmp_path)) == {}
    assert tel.tracer.span("engine", "step") \
        is tel.tracer.span("engine", "fetch")


def test_profile_writes_spans_and_perfetto(tiny_lm, tmp_path):
    """``Telemetry.profile`` turns the spans on for its block only and
    writes the Perfetto file beside the trace."""
    cfg, model, params = tiny_lm
    tel = Telemetry()
    eng = Engine(model, params, _ecfg(), telemetry=tel)
    eng.warmup()
    with tel.profile(str(tmp_path)):
        assert tel.tracer.enabled
        eng.run([Request(prompt=r.prompt.copy(),
                         max_new_tokens=r.max_new_tokens, rid=r.rid)
                 for r in _requests(cfg, 2, 47500)])
    assert not tel.tracer.enabled
    (spans,) = _profiled_spans(str(tmp_path)).values()
    assert "repro:engine.step" in {s[0] for s in spans}
    assert glob.glob(str(tmp_path / "**" / "perfetto_trace.json.gz"),
                     recursive=True)


# ---------------------------------------------------------------------------
# cluster metrics: aggregate + per-replica, cancel
# ---------------------------------------------------------------------------


def test_cluster_metrics_per_replica_aggregation(tiny_lm, tmp_path):
    cfg, model, params = tiny_lm
    cl = ServeCluster.for_replicas(model, params, _ecfg(),
                                   num_replicas=2, trace=True)
    reqs = _requests(cfg, 6, 48000)
    cl.warmup()
    with jax.profiler.trace(str(tmp_path / "trace")):
        res = cl.run([Request(prompt=r.prompt.copy(),
                              max_new_tokens=r.max_new_tokens, rid=r.rid)
                      for r in reqs])
    assert len(res) == len(reqs)
    m = cl.metrics()
    assert sorted(m["per_replica"]) == [0, 1]
    # aggregate counters are exactly the per-replica sums
    for k, v in m["aggregate"]["counters"].items():
        assert v == sum(m["per_replica"][i]["counters"][k] for i in (0, 1))
    # aggregate latency percentiles cover every request, per replica
    # counts split them
    agg = m["aggregate"]["latency"]
    assert agg["e2e"]["count"] == len(reqs)
    assert agg["ttft"]["p50"] <= agg["e2e"]["p99"] + 1e-9
    split = [m["per_replica"][i]["latency"]["e2e"]["count"] for i in (0, 1)]
    assert sum(split) == len(reqs)
    # lifecycle: dispatcher stamped submit/route, engines the rest
    _check_lifecycle(cl.telemetry, [r.rid for r in reqs])
    for r in reqs:
        tr = cl.telemetry.requests.get(r.rid)
        assert tr.stamps["submit"] <= tr.stamps["route"] \
            <= tr.stamps["admit"]
        assert tr.replica in (0, 1)
    # trace: each replica's engine spans on its own worker thread, the
    # routing spans on the submitting thread
    by_thread = _profiled_spans(str(tmp_path / "trace"))
    names = {t: {s[0] for s in spans} for t, spans in by_thread.items()}
    route = [t for t, n in names.items() if "repro:dispatcher.route" in n]
    workers = [t for t, n in names.items() if "repro:engine.step" in n]
    assert len(route) == 1 and len(workers) == 2
    assert route[0] not in workers
    routed = [s[3]["rid"] for s in by_thread[route[0]]
              if s[0] == "repro:dispatcher.route"]
    assert sorted(routed) == sorted(r.rid for r in reqs)
    for spans in by_thread.values():
        _assert_nested(spans)
    # metrics JSON export round-trips
    cl.write_metrics(str(tmp_path / "metrics.json"))
    exported = json.load(open(tmp_path / "metrics.json"))
    assert exported["metrics"]["aggregate"]["latency"]["e2e"]["count"] \
        == len(reqs)


def test_cluster_cancel_is_the_terminal(tiny_lm):
    cfg, model, params = tiny_lm
    cl = ServeCluster.for_replicas(model, params, _ecfg(), num_replicas=2)
    (req,) = _requests(cfg, 1, 49000)
    cl.submit(req)                    # never started: no worker threads
    assert cl.cancel(req.rid)
    tr = cl.telemetry.requests.get(req.rid)
    assert tr.terminal == "cancel"
    assert cl.telemetry.requests.double_terminals.value == 0
    reg = cl.telemetry.registry.snapshot()["counters"]
    assert reg["requests_cancelled"] == 1
    cl.close()
    cl.join()
    # close() after cancel must not double-terminate the drained rid
    assert cl.telemetry.requests.double_terminals.value == 0
