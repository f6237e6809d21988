"""Pass 1 — Mosaic-compat kernel checker.

Interpret mode (how every Pallas kernel in this repo is validated on
CPU) is a Python interpreter walking the grid: it accepts layouts,
iota ranks, and memory placements that the real Mosaic TPU lowering
rejects.  This pass closes the gap statically: it traces every public
op in ``repro.kernels.ops`` at representative shapes (coverage is
cross-checked against ``PagedSpec.kernel_spec`` for every seed config,
so a servable family cannot ship an unchecked kernel), finds the
``pallas_call`` equations in the jaxpr, and checks kernel body +
BlockSpecs + scratch + scalar prefetch against the constraints from
the Pallas TPU guide:

  KC000  coverage: kernel_spec / public op with no trace recipe, or a
         recipe whose trace contains no pallas_call
  KC001  1-D iota in the kernel body (TPU needs >=2-D broadcasted_iota)
  KC002  non-scalar 1-D intermediate in the kernel body (TPU vectors
         are >=2-D; a (k,) value has no VREG layout)
  KC003  block minor dim not a multiple of the 128-lane tile (and not
         the full array extent)
  KC004  block second-minor dim not sublane-aligned for the dtype
         (8 f32 / 16 bf16 / 32 int8) nor the full array extent — a
         width-1 block of a taller array is refused too
  KC005  VMEM scratch lane-misaligned (minor % 128) or a size-1 VMEM
         scratch that belongs in SMEM
  KC006  scalar-prefetch operand not SMEM-compatible (non-integer, or
         too large for scalar memory)
  KC007  dynamic/non-affine computation leaking into a grid index map
  KC008  op with no Mosaic lowering in the kernel body (gather/sort/
         argsort/top_k/scatter)

Rules apply to the *kernel* jaxpr, not the host wrapper — ops.py is
free to pad/reshape with whatever it likes outside the kernel.
"""
from __future__ import annotations

import functools
import inspect
from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.common import Finding

# sublane tile minimum by itemsize (second-minor dim of a VREG tile);
# the lane (minor) dim is 128 for every dtype
_SUBLANE = {4: 8, 2: 16, 1: 32}
_LANE = 128

# prims with no Mosaic lowering inside a TPU kernel body
_NO_LOWERING = {"gather", "scatter", "scatter_add", "sort", "top_k",
                "approx_top_k", "argsort"}

# what a grid index map may compute: affine arithmetic + scalar reads
# from prefetch refs.  Anything else (transcendentals, reductions,
# data-dependent shapes) means the block routing is not static enough
# for Mosaic's DMA planner.
_INDEX_MAP_OK = {"add", "sub", "mul", "div", "rem", "floordiv", "max",
                 "min", "neg", "sign", "select_n", "convert_element_type",
                 "squeeze", "reshape", "broadcast_in_dim", "get",
                 "dynamic_slice", "slice", "gather", "concatenate",
                 "lt", "le", "gt", "ge", "eq", "ne", "and", "or", "not",
                 "xor", "stop_gradient", "jit", "clamp"}

# host-side callback prims must never appear inside a kernel either
_CALLBACKS = {"pure_callback", "io_callback", "debug_callback", "callback"}

# reductions drop a dim by construction (keepdims lowers as reduce +
# reshape); Mosaic lowers the pair as a unit, so the transient 1-D
# reduce output is not a constructed vector — exempt from KC002
_REDUCE_PRIMS = {"reduce_max", "reduce_min", "reduce_sum", "reduce_prod",
                 "reduce_and", "reduce_or", "argmax", "argmin"}


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        vals = v if isinstance(v, (list, tuple)) else [v]
        for s in vals:
            inner = getattr(s, "jaxpr", None)
            if inner is not None:
                yield inner
            elif type(s).__name__ == "Jaxpr":
                yield s


def _walk_eqns(jaxpr):
    """All eqns in ``jaxpr`` including nested sub-jaxprs (cond/scan/
    while/pjit branches)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk_eqns(sub)


def find_pallas_calls(fn: Callable, args: Sequence[Any]) -> List[Any]:
    """Trace ``fn(*args)`` shape-only and return every pallas_call eqn
    (top-level or nested)."""
    closed = jax.make_jaxpr(fn)(*args)
    return [e for e in _walk_eqns(closed.jaxpr)
            if e.primitive.name == "pallas_call"]


def _aval(var):
    return var.aval


def _mem_space(var) -> str:
    ms = getattr(var.aval, "memory_space", None)
    return str(ms).lower() if ms is not None else "any"


def _check_body(where: str, kernel_jaxpr) -> List[Finding]:
    out: List[Finding] = []
    seen_rules = set()
    for eqn in _walk_eqns(kernel_jaxpr):
        name = eqn.primitive.name
        if name == "iota":
            for ov in eqn.outvars:
                if len(ov.aval.shape) < 2:
                    key = ("KC001", str(ov.aval.shape))
                    if key not in seen_rules:
                        seen_rules.add(key)
                        out.append(Finding(
                            "KC001", where, f"iota{ov.aval.shape}",
                            f"1-D iota of shape {ov.aval.shape} in kernel "
                            f"body — Mosaic only lowers >=2-D iota",
                            "use jax.lax.broadcasted_iota over a >=2-D "
                            "shape (interpret mode hides this)"))
        if name in _NO_LOWERING:
            key = ("KC008", name)
            if key not in seen_rules:
                seen_rules.add(key)
                out.append(Finding(
                    "KC008", where, name,
                    f"'{name}' in kernel body has no Mosaic lowering",
                    "restructure as streamed max-extractions / masked "
                    "selects, or hoist out of the kernel"))
        if name in _CALLBACKS:
            out.append(Finding(
                "KC008", where, name,
                f"host callback '{name}' inside a kernel body",
                "kernels cannot call back to the host; move it outside "
                "the pallas_call"))
        for ov in eqn.outvars:
            shape = getattr(ov.aval, "shape", ())
            if (len(shape) == 1 and shape[0] > 1
                    and name not in _REDUCE_PRIMS):
                key = ("KC002", name, shape)
                if key not in seen_rules:
                    seen_rules.add(key)
                    out.append(Finding(
                        "KC002", where, f"{name}->{tuple(shape)}",
                        f"non-scalar 1-D intermediate {tuple(shape)} "
                        f"(from '{name}') in kernel body — no VREG "
                        f"layout on TPU",
                        "keep intermediates >=2-D, e.g. build (1, k) "
                        "rows via concatenate instead of stack+reshape"))
    return out


def _block_dim(d) -> int:
    """Extent of one ``BlockMapping.block_shape`` entry: an int, a
    ``Blocked``/``Element`` dim (its ``block_size``), or a squeezed
    dim (1)."""
    if isinstance(d, int):
        return d
    return getattr(d, "block_size", 1)


def _check_blocks(where: str, grid_mapping) -> List[Finding]:
    out: List[Finding] = []
    for i, bm in enumerate(grid_mapping.block_mappings):
        origin = bm.origin
        block = [_block_dim(d) for d in bm.block_shape]
        if len(block) < 2:
            continue
        arr_shape = bm.array_aval.shape
        dt = jnp.dtype(bm.array_aval.dtype)
        minor, arr_minor = block[-1], arr_shape[-1]
        if minor % _LANE != 0 and minor != arr_minor:
            out.append(Finding(
                "KC003", where, f"{origin}:block{tuple(block)}",
                f"block minor dim {minor} of {origin} (array "
                f"{tuple(arr_shape)} {dt.name}) is not a multiple of the "
                f"128-lane tile nor the full extent {arr_minor}",
                "pad the block (and the array) minor dim to 128, or "
                "block the full extent"))
        sub = _SUBLANE.get(dt.itemsize, 8)
        smin, arr_smin = block[-2], arr_shape[-2]
        if smin % sub != 0 and smin != arr_smin:
            out.append(Finding(
                "KC004", where, f"{origin}:block{tuple(block)}",
                f"block second-minor dim {smin} of {origin} (array "
                f"{tuple(arr_shape)} {dt.name}) is not {sub}-sublane "
                f"aligned nor the full extent {arr_smin}",
                f"round the second-minor block dim up to a multiple of "
                f"{sub} for {dt.name}"))
    return out


def _is_semaphore(dtype) -> bool:
    from jax.experimental.pallas import tpu as pltpu
    return any(jnp.issubdtype(dtype, t)
               for t in (pltpu.semaphore, pltpu.dma_semaphore))


def _check_scratch(where: str, kernel_jaxpr, num_scratch: int
                   ) -> List[Finding]:
    out: List[Finding] = []
    if not num_scratch:
        return out
    for j, var in enumerate(kernel_jaxpr.invars[-num_scratch:]):
        aval = var.aval
        shape = getattr(aval, "shape", ())
        if _is_semaphore(aval.dtype):       # holds no tile
            continue
        dt = jnp.dtype(aval.dtype)
        space = _mem_space(var)
        size = 1
        for d in shape:
            size *= d
        name = f"scratch[{j}]:{space}:{dt.name}{tuple(shape)}"
        if space == "smem":
            if size > 1024:
                out.append(Finding(
                    "KC006", where, name,
                    f"SMEM scratch of {size} elements — scalar memory "
                    f"holds control values, not tensors",
                    "move bulk scratch to VMEM; keep SMEM for scalars"))
            continue
        if len(shape) < 2:
            out.append(Finding(
                "KC005", where, name,
                f"{len(shape)}-D VMEM scratch {tuple(shape)} — TPU "
                f"vector memory wants >=2-D (sublane, lane) tiles",
                "shape the scratch (rows, 128) or use SMEM for scalars"))
            continue
        if size == 1:
            out.append(Finding(
                "KC005", where, name,
                "size-1 VMEM scratch burns a full (8, 128) vector tile "
                "and forces scalar<->vector relayouts on every access",
                "declare it pltpu.SMEM((1, 1), dtype) instead"))
        elif shape[-1] % _LANE != 0:
            out.append(Finding(
                "KC005", where, name,
                f"VMEM scratch minor dim {shape[-1]} is not 128-lane "
                f"aligned — Mosaic relayouts every read/write",
                "lane-pad the scratch to (rows, 128) and keep all lanes "
                "equal (broadcast the per-row value)"))
    return out


def _check_prefetch(where: str, kernel_jaxpr, num_prefetch: int
                    ) -> List[Finding]:
    out: List[Finding] = []
    for j, var in enumerate(kernel_jaxpr.invars[:num_prefetch]):
        aval = var.aval
        dt = jnp.dtype(aval.dtype)
        shape = getattr(aval, "shape", ())
        size = 1
        for d in shape:
            size *= d
        name = f"prefetch[{j}]:{dt.name}{tuple(shape)}"
        if not jnp.issubdtype(dt, jnp.integer):
            out.append(Finding(
                "KC006", where, name,
                f"scalar-prefetch operand {j} is {dt.name} — SMEM "
                f"prefetch feeds index maps and must be integer",
                "cast indices to int32 on the host before the call"))
        if size > 4096:
            out.append(Finding(
                "KC006", where, name,
                f"scalar-prefetch operand {j} has {size} elements — too "
                f"large for SMEM",
                "prefetch only the per-grid-step indices (block tables, "
                "positions), stream bulk data through VMEM blocks"))
    return out


def _check_index_maps(where: str, grid_mapping) -> List[Finding]:
    out: List[Finding] = []
    for i, bm in enumerate(grid_mapping.block_mappings):
        imj = getattr(bm, "index_map_jaxpr", None)
        if imj is None:
            continue
        origin = getattr(bm, "origin", f"operand{i}")
        bad = sorted({e.primitive.name for e in _walk_eqns(imj.jaxpr)
                      if e.primitive.name not in _INDEX_MAP_OK})
        if bad:
            out.append(Finding(
                "KC007", where, f"{origin}:index_map",
                f"grid index map of {origin} computes {bad} — block "
                f"routing must stay affine in grid ids + prefetched "
                f"scalars for Mosaic's DMA planner",
                "precompute the routing on the host and pass it through "
                "scalar prefetch"))
    return out


def check_traced(name: str, fn: Callable, args: Sequence[Any]
                 ) -> List[Finding]:
    """Run every KC rule on the pallas_call eqns reached by tracing
    ``fn(*args)``.  ``name`` labels the findings ("op/variant")."""
    findings: List[Finding] = []
    eqns = find_pallas_calls(fn, args)
    if not eqns:
        findings.append(Finding(
            "KC000", name, "no-pallas-call",
            "recipe traced without reaching any pallas_call — the op "
            "is not kernel-backed at this shape",
            "fix the recipe (or the op's dispatch) so the Pallas path "
            "is exercised"))
        return findings
    for k, eqn in enumerate(eqns):
        where = name if len(eqns) == 1 else f"{name}#{k}"
        kj = eqn.params["jaxpr"]
        gm = eqn.params["grid_mapping"]
        findings += _check_body(where, kj)
        findings += _check_blocks(where, gm)
        findings += _check_scratch(where, kj, gm.num_scratch_operands)
        findings += _check_prefetch(where, kj, gm.num_index_operands)
        findings += _check_index_maps(where, gm)
    return findings


# ---------------------------------------------------------------------------
# representative-shape recipes, one per public op (mirrors the shapes
# benchmarks/kernels_bench.py exercises — CPU-tractable, GQA + padding
# + paging all represented).  Inputs are ShapeDtypeStructs: tracing is
# shape-only, nothing is allocated or executed.
# ---------------------------------------------------------------------------


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def recipes() -> Dict[str, Dict[str, Tuple[Callable, Tuple]]]:
    from repro.kernels import ops
    i32, bf16, f32 = jnp.int32, jnp.bfloat16, jnp.float32
    keyt = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), 8))
    r: Dict[str, Dict[str, Tuple[Callable, Tuple]]] = {}

    r["fused_sgd_update"] = {"default": (
        functools.partial(ops.fused_sgd_update, lr=0.1, momentum=0.9,
                          weight_decay=1e-4),
        (_sds((512, 128), bf16), _sds((512, 128), f32),
         _sds((512, 128), f32)))}

    r["flash_attention"] = {"causal": (
        ops.flash_attention,
        (_sds((1, 256, 4, 64), bf16), _sds((1, 256, 2, 64), bf16),
         _sds((1, 256, 2, 64), bf16)))}

    r["flash_decode"] = {"default": (
        functools.partial(ops.flash_decode, length=1024),
        (_sds((4, 4, 64), bf16), _sds((4, 1024, 2, 64), bf16),
         _sds((4, 1024, 2, 64), bf16)))}

    r["flash_decode_paged"] = {"default": (
        ops.flash_decode_paged,
        (_sds((2, 1, 4, 64), bf16), _sds((16, 16, 2, 64), bf16),
         _sds((16, 16, 2, 64), bf16), _sds((2, 4), i32), _sds((2,), i32)))}

    r["decode_view_attend"] = {"default": (
        ops.decode_view_attend,
        (_sds((4, 4, 64), bf16), _sds((4, 160, 2, 64), bf16),
         _sds((4, 160, 2, 64), bf16), _sds((4,), i32)))}

    scale = 1.0 / (64 + 32) ** 0.5
    r["mla_decode_views"] = {"default": (
        functools.partial(ops.mla_decode_views, scale=scale),
        (_sds((2, 1, 4, 64), f32), _sds((2, 1, 4, 32), f32),
         _sds((2, 96, 64), f32), _sds((2, 96, 32), f32),
         _sds((2,), i32)))}

    r["mla_decode_paged"] = {"default": (
        functools.partial(ops.mla_decode_paged, scale=scale),
        (_sds((2, 1, 4, 64), f32), _sds((2, 1, 4, 32), f32),
         _sds((12, 16, 64), f32), _sds((12, 16, 32), f32),
         _sds((2, 3), i32), _sds((2,), i32)))}

    r["slot_gather"] = {"default": (
        ops.slot_gather,
        (_sds((33, 4, 64), f32), _sds((8,), i32), _sds((8,), i32)))}

    r["slot_scatter"] = {"default": (
        ops.slot_scatter,
        (_sds((33, 4, 64), f32), _sds((8,), i32), _sds((8,), i32),
         _sds((8, 4, 64), f32)))}

    lg, ky = _sds((8, 1024), f32), keyt
    r["sample_tokens"] = {
        "greedy": (functools.partial(ops.sample_tokens, impl="pallas",
                                     temperature=0.0), (lg, ky)),
        "gumbel": (functools.partial(ops.sample_tokens, impl="pallas",
                                     temperature=0.8, top_k=0), (lg, ky)),
        "topk": (functools.partial(ops.sample_tokens, impl="pallas",
                                   temperature=0.8, top_k=32), (lg, ky)),
    }

    r["ssd_chunk"] = {"default": (
        ops.ssd_chunk,
        (_sds((2, 16, 2, 64), f32), _sds((2, 16, 2), f32),
         _sds((2, 16, 2), f32), _sds((2, 16, 2, 64), f32),
         _sds((2, 16, 2, 64), f32)))}
    return r


def public_ops() -> List[str]:
    """Public kernel surface (same filter kernels_bench enforces
    coverage against)."""
    from repro.kernels import ops
    return sorted(
        name for name, f in inspect.getmembers(ops, inspect.isfunction)
        if f.__module__ == "repro.kernels.ops"
        and not name.startswith("_"))


def kernel_spec_ops() -> List[str]:
    """Every ops.py entry any seed config's PagedSpec names — the ops a
    servable family actually dispatches."""
    from repro.configs.base import available_archs, get_config, smoke_variant
    from repro.models.model import build_model
    names = set()
    for arch in available_archs():
        model = build_model(smoke_variant(get_config(arch)))
        if model.paged_spec is None:
            continue
        for _kind, entry in model.paged_spec.kernel_spec:
            names.update(n for n in entry.split("/") if n)
    return sorted(names)


def check_coverage(expected_ops: Sequence[str],
                   recipe_table: Dict[str, Dict]) -> List[Finding]:
    """KC000: every expected op must have a trace recipe — a new op (or
    a new kernel_spec entry) without registration fails fast."""
    return [Finding(
        "KC000", op, "no-recipe",
        f"op '{op}' (public in kernels/ops.py or named by a "
        f"kernel_spec) has no Pass-1 trace recipe",
        "add a representative-shape recipe in "
        "repro/analysis/kernel_check.py:recipes()")
        for op in expected_ops if op not in recipe_table]


def run() -> List[Finding]:
    """The full Pass 1: coverage + every rule on every recipe."""
    table = recipes()
    expected = sorted(set(public_ops()) | set(kernel_spec_ops()))
    findings = check_coverage(expected, table)
    for op in expected:
        for variant, (fn, args) in table.get(op, {}).items():
            findings += check_traced(f"{op}/{variant}", fn, args)
    return findings
