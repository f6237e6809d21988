"""Run one benchmark cell once and print its result line.

Everything a cell needs is found by name:

    BENCHMARK.json            the cell: configuration, traffic, chips
    bench/configs/<c>.json    model, its family, dtypes, trainer or
                              engine settings
    bench/reference/<f>.py    the family: layouts, reference, counts
    bench/traffic/<t>.json    the job (training) or mix (serving)
    bench/limits/<cell>.json  the limits of the correctness comparison
    bench/metrics/<m>.py      one reader per metric: read(record) -> value

A cell module (``train_cell`` or ``serve_cell``, chosen by the
traffic's ``kind``) sets the cell up, measures its window, checks what
the window produced against the family's reference, and returns a
record; the readers turn the record into the metrics that BENCHMARK.json
lists for the cell.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import program  # noqa: E402


class NoChip(SystemExit):
    pass


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str):
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    limits = BENCH / "limits" / f"{name}.json"
    return (spec, cell, load_json(BENCH / "configs" / f"{cell['config']}.json"),
            load_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
            load_json(limits) if limits.exists() else {})


def metrics_for(spec: dict, cell: str, trace: bool) -> list:
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def read_metric(name: str, record: dict):
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(record)


def chips(n: int, require_tpu: bool = True):
    """The first ``n`` devices; a run without a TPU, or with fewer chips
    than the cell asks for, ends here with no result."""
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < n:
        raise NoChip(f"cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


class Tracer:
    """Profiles one stretch of a window; ``span`` writes host spans that
    ``trace_reduce`` reads on the device trace's clock."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir = tempfile.mkdtemp(prefix="bench-trace-") if enabled else None
        self._window = None

    def span(self, name: str):
        import contextlib
        if not self.enabled:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("bench:" + name)

    def start(self):
        import jax
        jax.profiler.start_trace(self.dir)
        self._window = jax.profiler.TraceAnnotation("bench:traced_window")
        self._window.__enter__()

    def stop(self):
        import jax
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self) -> dict:
        """The trace as ``trace_reduce`` reads it; call after the window."""
        from bench import trace_reduce
        return trace_reduce.load_xplane(self.dir)

    def close(self):
        if self.dir:
            import shutil
            shutil.rmtree(self.dir, ignore_errors=True)


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_start: float, *, require_tpu: bool = True,
             overrides: dict = None) -> dict:
    """Set up, measure and check one cell; returns the result dict.
    ``overrides`` (tests and ``bench/calibrate.py``) replaces parts of
    the configuration, traffic and limits, and may plant faults (see the
    cell modules)."""
    spec, cell, cfg, mix, limits = load_cell(name)
    overrides = overrides or {}
    cfg = {**cfg, **overrides.get("config", {})}
    mix = {**mix, **overrides.get("traffic", {})}
    limits = {**limits, **overrides.get("limits", {})}
    if not limits:
        raise SystemExit(f"no limits for {name}: bench/limits/{name}.json")
    program.import_program()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    devs = chips(cell["chips"], require_tpu)
    t_chip = time.perf_counter()
    if mix["kind"] == "train":
        from bench import train_cell as cell_module
    elif mix["kind"] == "serve_open_loop":
        from bench import serve_cell as cell_module
    else:
        raise SystemExit(f"unknown traffic kind {mix['kind']!r}")
    tracer = Tracer(trace)
    try:
        rec = cell_module.run(cfg=cfg, mix=mix, seed=seed, seconds=seconds,
                         tracer=tracer, devs=devs, t_start=t_start,
                         faults=overrides.get("faults", {}))
    finally:
        tracer.close()
    rec["info"]["start_to_chip_s"] = t_chip - t_start
    rec["peaks"] = _peaks(devs, require_tpu)
    rec["chips"] = len(devs)
    checks = {k: {"value": v, "limit": limits[k]}
              for k, v in rec["checks"].items()}
    correct = bool(rec["sound"]) and all(
        c["value"] <= c["limit"] for c in checks.values())
    metrics = {}
    for m in metrics_for(spec, name, trace):
        v = read_metric(m["name"], rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": rec["memory_peak"]}
    out = {"correct": correct, "attempted": rec["attempted"],
           "failed": rec["failed"], "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = rec["busy_s"]
        device["window_s"] = rec["traced_s"]
        out["breakdown"] = rec["breakdown"]
    out["checks"] = checks
    out["_info"] = rec.get("info", {})
    return out


def _peaks(devs, require_tpu):
    from bench.peaks import peaks_for
    try:
        return peaks_for(devs[0].device_kind)
    except KeyError:
        if require_tpu:
            raise
        return None                   # rehearsal on the CPU: no peaks


def main(argv=None, t_start: float = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 3
    info = out.pop("_info")
    for k, v in info.items():
        print(f"info {k}: {json.dumps(v)}", flush=True)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
