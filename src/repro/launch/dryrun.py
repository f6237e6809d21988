import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax-importing module: the dry-run
# builds the production meshes (16x16 single-pod, 2x16x16 multi-pod) out of
# 512 placeholder host devices.  Everything else imports below this line.

import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402

from repro.configs.base import INPUT_SHAPES, get_config, shape_config  # noqa: E402
from repro.launch import analysis, builders, hlo_accounting  # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_axis_sizes  # noqa: E402
from repro.models.model import count_params_analytic  # noqa: E402


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D (train) / 2*N*D (inference), N = active params."""
    n = count_params_analytic(cfg, active_only=True)
    if shape.kind == "train":
        d = shape.global_batch * shape.seq_len
        return 6.0 * n * d
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def _parse_overrides(text: str) -> dict:
    out = {}
    if not text:
        return out
    for kv in text.split(","):
        k, v = kv.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        out[k] = v
    return out


def run_one(arch: str, shape_name: str, *, multi_pod: bool,
            sync_mode: str = "lsgd", print_hlo: bool = False,
            save_hlo: str = "", overrides: str = "", tag_suffix: str = "",
            **kw) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch, **_parse_overrides(overrides))
    shape = shape_config(shape_name)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "multi_pod" if multi_pod else "single_pod",
           "mesh_axes": mesh_axis_sizes(mesh), "sync_mode": sync_mode}
    ok, why = builders.pair_applicable(cfg, shape)
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec

    t0 = time.time()
    try:
        if shape.kind == "train":
            low = builders.make_train_lowerable(cfg, shape, mesh,
                                                sync_mode=sync_mode, **kw)
        else:
            low = builders.make_serve_lowerable(cfg, shape, mesh)
        rec["step_kind"] = low.description
        lowered = low.lower()
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-3000:])
        return rec

    xla_cost = dict(compiled.cost_analysis())
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    n_pods = 2 if multi_pod else 1
    pod_stride = mesh.devices.size // n_pods
    # trip-count-aware accounting (XLA's cost_analysis counts while bodies
    # once on this backend — see launch/hlo_accounting.py)
    acc = hlo_accounting.account(hlo)
    cost = {"flops": acc.flops, "bytes accessed": acc.bytes}
    ops = hlo_accounting.collective_ops(acc, pod_stride=pod_stride)
    coll = analysis.collective_summary(ops)
    mf = model_flops(cfg, shape)
    roof = analysis.roofline_terms(cost, coll, mesh.devices.size,
                                   model_flops=mf)

    rec.update(
        status="ok",
        lower_s=round(t1 - t0, 2), compile_s=round(t2 - t1, 2),
        params=count_params_analytic(cfg),
        params_active=count_params_analytic(cfg, active_only=True),
        flops_per_device=cost.get("flops", 0.0),
        bytes_accessed_per_device=cost.get("bytes accessed", 0.0),
        xla_flops_raw=xla_cost.get("flops", 0.0),
        xla_bytes_raw=xla_cost.get("bytes accessed", 0.0),
        memory={
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes": mem.peak_memory_in_bytes,
        },
        collectives={k: v for k, v in coll.items()},
        model_flops=mf,
        roofline={
            "compute_s": roof.compute_s,
            "memory_s": roof.memory_s,
            "collective_s": roof.collective_s,
            "collective_cross_pod_s": roof.collective_slow_s,
            "dominant": roof.dominant,
            "useful_flops_frac": roof.useful_flops_frac,
        },
    )
    if print_hlo:
        print(hlo[:20000])
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)
    return rec


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all",
                    help="arch id, comma list, or 'all'")
    ap.add_argument("--shape", default="all",
                    help="shape name, comma list, or 'all'")
    ap.add_argument("--mesh", default="both",
                    choices=["single_pod", "multi_pod", "both"])
    ap.add_argument("--sync-mode", default="lsgd",
                    choices=["csgd", "lsgd", "lsgd_eager", "lsgd_rsag",
                             "lsgd_compressed"])
    ap.add_argument("--intra-group-size", type=int, default=None)
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--print-hlo", action="store_true")
    ap.add_argument("--save-hlo", default="")
    ap.add_argument("--override", default="",
                    help="ModelConfig overrides, e.g. loss_chunk=1024")
    ap.add_argument("--tag", default="",
                    help="suffix for the output json filename")
    args = ap.parse_args()

    archs = (builders.ASSIGNED_ARCHS if args.arch == "all"
             else args.arch.split(","))
    shapes = (list(INPUT_SHAPES) if args.shape == "all"
              else args.shape.split(","))
    meshes = ([False, True] if args.mesh == "both"
              else [args.mesh == "multi_pod"])

    os.makedirs(args.out, exist_ok=True)
    n_ok = n_skip = n_err = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'mp' if mp else 'sp'}__{args.sync_mode}"
                if args.tag:
                    tag += f"__{args.tag}"
                rec = run_one(arch, shape, multi_pod=mp,
                              sync_mode=args.sync_mode,
                              intra_group_size=args.intra_group_size,
                              print_hlo=args.print_hlo,
                              save_hlo=args.save_hlo,
                              overrides=args.override)
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(rec, f, indent=1)
                st = rec["status"]
                n_ok += st == "ok"
                n_skip += st == "skipped"
                n_err += st == "error"
                if st == "ok":
                    r = rec["roofline"]
                    print(f"[OK]   {tag:60s} lower={rec['lower_s']:6.1f}s "
                          f"compile={rec['compile_s']:6.1f}s "
                          f"dom={r['dominant']:10s} "
                          f"comp={r['compute_s']*1e3:8.2f}ms "
                          f"mem={r['memory_s']*1e3:8.2f}ms "
                          f"coll={r['collective_s']*1e3:8.2f}ms", flush=True)
                    print(f"       memory/device: "
                          f"{json.dumps(rec['memory'])}", flush=True)
                elif st == "skipped":
                    print(f"[SKIP] {tag:60s} {rec['reason']}", flush=True)
                else:
                    print(f"[ERR]  {tag:60s} {rec['error'][:160]}", flush=True)
    print(f"\ndone: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
