"""Reduce a profiler trace to busy and idle time, collectives and kernels.

The loader turns a ``.xplane.pb`` into plain tuples; every reduction
below works on those, so the tests can hand-build a trace.

    trace = {"devices": {plane: [(op, start_ns, dur_ns), ...]},
             "host":    [(span, start_ns, dur_ns), ...]}

Device ops come from each TPU plane's "XLA Ops" line, host spans from
``jax.profiler.TraceAnnotation`` names starting with ``bench:`` (the
harness's own spans around its calls into the program).
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]
COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather",
               "collective-permute", "all-to-all")
OPS_LINE = "XLA Ops"


def load_xplane(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(sorted(paths)[-1])
    devices: Dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [(e.name, e.start_ns, e.duration_ns)
                                           for e in line.events]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events if e.name.startswith("bench:")]
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, w0, w1) -> List[Interval]:
    return [(max(a, w0), min(b, w1)) for a, b in intervals
            if min(b, w1) > max(a, w0)]


def mean(per_device: Dict[str, float]) -> float:
    """Mean over devices; 0 when the trace holds no device."""
    return sum(per_device.values()) / len(per_device) if per_device else 0.0


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the (disjoint, sorted) intervals ``a`` not covered by ``b``."""
    b = union(b)
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def _iv(events):
    return [(s, s + d) for _, s, d in events]


def leaves(events):
    """Events that contain no other event: a ``while`` or ``conditional``
    op spans the ops of its body on the same line, and only the innermost
    ops are the work itself."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    parent = [False] * len(evs)
    stack = []                        # indices of open events
    for i, (_, s, d) in enumerate(evs):
        # close the open events this one does not lie inside
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] < s + d:
            stack.pop()
        if stack:
            parent[stack[-1]] = True
        stack.append(i)
    return [e for e, p in zip(evs, parent) if not p]


def is_collective(name: str) -> bool:
    n = name.lower()
    return any(c in n for c in COLLECTIVES)


def busy(trace: dict, w0: float, w1: float) -> Dict[str, float]:
    """Per device: ns inside [w0, w1] in which some op runs."""
    return {dev: length(clip(union(_iv(ev)), w0, w1))
            for dev, ev in trace["devices"].items()}


def idle_gaps(trace: dict, w0: float, w1: float, top: int = 10):
    """Longest gaps (start, end) inside the window on the first device,
    each labelled by the host span that covers its midpoint."""
    if not trace["devices"]:
        return []
    dev = sorted(trace["devices"])[0]
    gaps = subtract([(w0, w1)], clip(_iv(trace["devices"][dev]), w0, w1))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:top]:
        mid = (a + b) / 2
        label = "no host span"
        for name, s, d in trace["host"]:
            if s <= mid <= s + d and name != "bench:traced_window":
                label = name          # innermost: spans are start-sorted
        out.append((label, (b - a) * 1e-9))
    return out


def collective_exposed(trace: dict, w0: float, w1: float) -> Dict[str, float]:
    """Per device: ns of collective ops inside the window during which no
    other (non-collective) op runs on that device."""
    out = {}
    for dev, ev in trace["devices"].items():
        ev = leaves(ev)
        coll = union(clip(_iv([e for e in ev if is_collective(e[0])]), w0, w1))
        other = clip(_iv([e for e in ev if not is_collective(e[0])]), w0, w1)
        out[dev] = length(subtract(coll, other))
    return out


def op_stats(trace: dict, w0: float, w1: float, pattern: str):
    """(count, summed ns) of the first device's ops that match
    ``pattern`` and lie wholly inside the window."""
    if not trace["devices"]:
        return 0, 0.0
    rx = re.compile(pattern)
    dev = sorted(trace["devices"])[0]
    hits = [d for n, s, d in trace["devices"][dev]
            if w0 <= s and s + d <= w1 and rx.search(n)]
    return len(hits), float(sum(hits))


def top_ops(trace: dict, w0: float, w1: float, top: int = 10):
    """Innermost ops by summed seconds on the first device, each named by
    its HLO instruction without the numeric suffix of the instance and
    without operands."""
    if not trace["devices"]:
        return []
    dev = sorted(trace["devices"])[0]
    tot: Dict[str, float] = {}
    for n, s, d in leaves(trace["devices"][dev]):
        ov = min(s + d, w1) - max(s, w0)
        if ov > 0:
            key = short_name(n)
            tot[key] = tot.get(key, 0.0) + ov
    return sorted(((k, v * 1e-9) for k, v in tot.items()),
                  key=lambda kv: -kv[1])[:top]


def short_name(hlo: str) -> str:
    """"%fusion.12 = bf16[8]{0} fusion(...), kind=kLoop" -> "fusion fusion
    bf16[8]": the instruction's name without its instance number, its op,
    and its (first) result shape, which tells the Pallas kernels apart
    while they carry no name of their own."""
    head, _, rest = hlo.partition(" = ")
    name = re.sub(r"\.\d+$", "", head.lstrip("%"))
    if not rest:
        return name
    op = re.search(r"[)}\]] ([\w-]+)\(", rest)
    shape = re.match(r"\(?(\w+\[[\d,]*\])", rest)
    return " ".join(x for x in (name, op and op.group(1),
                                shape and shape.group(1)) if x)


def window_of(trace: dict) -> Interval:
    """The harness's ``bench:traced_window`` span."""
    for name, s, d in trace["host"]:
        if name == "bench:traced_window":
            return (s, s + d)
    raise ValueError("trace has no bench:traced_window span")
