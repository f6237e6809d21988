"""End-to-end training driver (runs for real on whatever devices exist).

Examples:
  # paper-style LSGD vs CSGD on a ~100M LM, few hundred steps:
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --smoke \
      --d-model 512 --layers 8 --steps 300 --batch 16 --seq 256

  # multi-(virtual)-device LSGD with the paper's hierarchy:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.train --arch mamba2-370m --smoke --steps 50 \
      --mesh 2,2,2 --sync-mode lsgd
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding
from repro.checkpoint import checkpoint
from repro.configs.base import get_config, smoke_variant
from repro.core import (TrainerConfig, Topology, make_finalize,
                        make_init_state, make_shardmap_step)
from repro.data.pipeline import DataConfig, HostLoader, data_config_for
from repro.launch import builders
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.model import build_model
from repro.optim.sgd import OptimConfig
from repro.optim import schedules


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family variant (CPU-trainable)")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--d-model", type=int, default=0)
    ap.add_argument("--d-ff", type=int, default=0)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--sync-mode", default="lsgd",
                    choices=["csgd", "lsgd", "lsgd_eager", "lsgd_rsag",
                             "lsgd_compressed"])
    ap.add_argument("--intra-group-size", type=int, default=None)
    ap.add_argument("--mesh", default="",
                    help="comma dims for (pod,data,model) host mesh; "
                         "default single device")
    ap.add_argument("--optimizer", default="sgd",
                    choices=["sgd", "lars", "adamw"])
    ap.add_argument("--base-lr", type=float, default=0.1)
    ap.add_argument("--schedule", default="paper",
                    choices=["paper", "wsd", "cosine", "const"])
    ap.add_argument("--warmup-steps", type=int, default=20)
    ap.add_argument("--io-latency", type=float, default=0.0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--loss-chunk", type=int, default=0,
                    help="cross-entropy over sequence chunks of this "
                         "length (bounds the (B, S, vocab) logits)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    if args.layers:
        cfg = cfg.replace(num_layers=args.layers)
    if args.d_model:
        heads = max(1, cfg.num_heads)
        cfg = cfg.replace(d_model=args.d_model,
                          head_dim=max(args.d_model // heads, 16))
    if args.d_ff:
        cfg = cfg.replace(d_ff=args.d_ff)
    if args.loss_chunk:
        cfg = cfg.replace(loss_chunk=args.loss_chunk)
    model = build_model(cfg)

    # mesh
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split(","))
        axes = ("pod", "data", "model")[-len(dims):]
        mesh = make_mesh(dims, axes)
    else:
        mesh = make_mesh((1, 1), ("data", "model"))

    # lr schedule — the paper's linear scaling rule, applied only upward
    # (the rule calibrates growth beyond the base batch of 256; tiny CPU
    # batches should not scale the lr toward zero)
    peak = schedules.linear_scaled_lr(args.base_lr, max(args.batch, 256))
    if args.schedule == "paper":
        lr_fn = lambda t: schedules.warmup_step_decay(
            t, base_lr=args.base_lr, peak_lr=peak,
            warmup_steps=args.warmup_steps,
            decay_every=max(args.steps // 3, 1))
    elif args.schedule == "wsd":
        lr_fn = lambda t: schedules.wsd(
            t, peak_lr=peak, warmup_steps=args.warmup_steps,
            stable_steps=args.steps // 2, decay_steps=args.steps // 3)
    elif args.schedule == "cosine":
        lr_fn = lambda t: schedules.cosine(
            t, peak_lr=peak, warmup_steps=args.warmup_steps,
            total_steps=args.steps)
    else:
        lr_fn = lambda t: args.base_lr

    tcfg = TrainerConfig(
        sync_mode=args.sync_mode,
        optim=OptimConfig(kind=args.optimizer),
        topology=Topology(intra_group_size=args.intra_group_size))
    state = jax.jit(make_init_state(model, tcfg))(jax.random.key(args.seed))
    n_params = sum(x.size for x in jax.tree.leaves(state["params"]))
    print(f"arch={cfg.name} params={n_params:,} sync={args.sync_mode} "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    if args.ckpt_dir and checkpoint.latest_step(args.ckpt_dir) is not None:
        state = checkpoint.restore(args.ckpt_dir, state)
        print(f"restored checkpoint at step {int(state['step'])}")

    from repro.configs.base import ShapeConfig
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    dcfg = data_config_for(cfg, shape, seed=args.seed)
    loader = HostLoader(dcfg, io_latency_s=args.io_latency)

    finalize = jax.jit(make_finalize(model, tcfg, lr_fn))

    tokens_per_step = args.batch * args.seq
    losses = []
    try:
        batch = {k: jnp.asarray(v) for k, v in next(loader).items()}
        t0 = time.time()
        step_fn = jax.jit(make_shardmap_step(model, tcfg, lr_fn, mesh),
                          donate_argnums=0).lower(state, batch).compile()
        compile_s = time.time() - t0
        print(f"compiled train step in {compile_s:.1f}s (wall)")
        t0 = time.time()
        for i in range(args.steps):
            if i:
                batch = {k: jnp.asarray(v) for k, v in next(loader).items()}
            state, (loss, metrics) = step_fn(state, batch)
            losses.append(loss)
            if (i + 1) % args.log_every == 0 or i == 0:
                loss_v = float(loss)
                dt = time.time() - t0
                tput = tokens_per_step * (i + 1) / dt
                print(f"step {i+1:5d} loss {loss_v:.4f} "
                      f"lr {float(lr_fn(i)):.4f} "
                      f"tok/s {tput:,.0f} (wall)")
            if args.ckpt_dir and args.ckpt_every \
                    and (i + 1) % args.ckpt_every == 0:
                checkpoint.save(args.ckpt_dir, state, int(state["step"]))
    finally:
        loader.close()
    state = finalize(state)
    if args.ckpt_dir:
        checkpoint.save(args.ckpt_dir, state, int(state["step"]))
    losses = [float(x) for x in losses]
    print(f"done in {time.time()-t0:.1f}s (wall); final loss "
          f"{losses[-1]:.4f}")
    return {"state": state, "losses": losses, "compile_s": compile_s}


if __name__ == "__main__":
    main()
