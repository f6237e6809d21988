"""Training cell: the LSGD step, AOT-compiled as ``repro.launch.train``
compiles it, fed by ``HostLoader``.

Set-up makes the state on the device from the seed (the benchmark's
weights in the program's tree), compiles the step and drives it through
its first three steps, reading what the correctness check needs; the
window then runs that same compiled step on that same state.  After the
window the state is freed and the reference repeats the three steps in
float32 on the same batches.  Layouts, reference and counts come from
the family module the configuration names (``bench.program.family``).

Compared, each against its limit (``bench/limits/<cell>.json``):

    loss1_gap   |program loss - reference loss| of step 1 (the forward
                pass alone; steady from seed to seed)
    loss_gap    the largest |program loss - reference loss| of steps 1-3
    grad_gap    the first gradient as the optimizer got it (LSGD's pending
                buffer after step 1): the worst leaf's gap between the
                program's norm and the reference's, over the larger of
                that leaf's reference norm and the median leaf's
    grad_diff   the same gradient: the worst leaf's norm of the
                difference from the reference's, over the same
                denominator.  Norms average rounding away; this number
                sees it element by element, which is where a precision
                below the configuration's shows
    change_gap  the same for the parameters' change after three steps
                and ``make_finalize`` (which applies LSGD's last pending
                update, as step 4 would)

Leaves whose reference gradient is under a thousandth of the median
leaf's move by round-off alone and are left out of both gaps.

Faults (tests and ``bench/calibrate.py``), by name: ``step`` plants
one of ``FAULTS`` under the compiled step (a step that returns its state
unchanged; half of the batch left out, the mean taken over the rest);
``control`` puts the reference in the program's place at float8 e4m3,
the precision step below the configurations' bf16.
"""
from __future__ import annotations

import functools
import gc
import time

import numpy as np

from bench import program
from bench.reference.common import seed_key

SETUP_STEPS = 3          # steps the reference follows
TRACE_STEPS = 3          # steps in the profiled stretch


def _frozen(step):
    """A step that returns its state unchanged."""
    def f(state, batch):
        _, out = step(state, batch)
        return state, out
    return f


def _half_batch(step):
    """Half of the batch left out: its rows replaced by the other half's,
    so the mean is taken over the rest."""
    def f(state, batch):
        t = batch["tokens"]
        h = t.shape[0] // 2
        return step(state, {"tokens": t.at[h:].set(t[:h])})
    return f


FAULTS = {"frozen": _frozen, "half_batch": _half_batch}


def _norms(from_program, tree):
    import jax.numpy as jnp
    return {k: jnp.linalg.norm(v.astype(jnp.float32).reshape(-1))
            for k, v in from_program(tree).items()}


def prepare(cfg, mix, seed, devs, faults):
    """Build the state from the seed, compile the step, run the first
    steps.  Returns the live objects and the program's readings."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import (TrainerConfig, make_finalize, make_init_state,
                            make_shardmap_step)
    from repro.data.pipeline import DataConfig, HostLoader
    from repro.launch.mesh import make_mesh
    from repro.models.model import build_model
    from repro.optim.sgd import OptimConfig

    fam = program.family(cfg)
    mc = program.model_config(cfg)
    model = build_model(mc)
    dims = fam.dims(mc)
    norms = functools.partial(_norms, fam.from_program)
    opt = cfg["optimizer"]
    shape = tuple(mix["mesh"])
    mesh = make_mesh(shape, ("pod", "data", "model")[-len(shape):])
    if mesh.devices.size != len(devs):
        raise SystemExit(f"mesh {shape} wants {mesh.devices.size} chips")
    dp = int(np.prod(shape[:-1]))
    lr = float(opt["lr"])
    lr_fn = lambda t: lr
    tcfg = TrainerConfig(
        sync_mode=mix["sync_mode"],
        optim=OptimConfig(kind="sgd", momentum=opt["momentum"],
                          weight_decay=opt["weight_decay"]))
    init = make_init_state(model, tcfg)

    def build(key):
        st = init(jax.random.key(0))   # its random params are dead code
        st["params"] = fam.to_program(
            fam.generate(dims, key, jnp.dtype(mc.param_dtype)))
        return st

    phases = {}
    t = time.perf_counter()
    state = jax.block_until_ready(jax.jit(build)(seed_key(seed)))
    phases["state_s"] = time.perf_counter() - t
    program.check_layout(model, state["params"])
    if dp > 1:
        state = jax.device_put(state, NamedSharding(mesh, P()))
    seq, rows = int(mix["seq_len"]), int(mix["seqs_per_chip"]) * dp
    loader = HostLoader(DataConfig(kind="lm", vocab_size=mc.vocab_size,
                                   seq_len=seq, global_batch=rows,
                                   seed=seed, distribution="zipf"))
    feed = lambda b: {k: jnp.asarray(v) for k, v in b.items()}
    try:
        first = [next(loader) for _ in range(SETUP_STEPS)]
        wrap = FAULTS[faults["step"]] if faults.get("step") else None
        body = make_shardmap_step(model, tcfg, lr_fn, mesh)
        t = time.perf_counter()
        step = jax.jit(body, donate_argnums=() if wrap else 0
                       ).lower(state, feed(first[0])).compile()
        phases["compile_s"] = time.perf_counter() - t
        t = time.perf_counter()
        if wrap:
            step = wrap(step)
        finalize = make_finalize(model, tcfg, lr_fn)
        wd = float(opt["weight_decay"])

        def first_grad(st, w0):
            # the gradient as the optimizer got it after one step
            if "pending" in st:
                return st["pending"]
            return jax.jit(lambda m, w: jax.tree.map(
                lambda a, b: a - wd * b.astype(jnp.float32), m, w))(
                    st["opt"]["m"], w0)

        @jax.jit
        def change(st, w0):
            fin = finalize(st)["params"]
            return norms(jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                fin, w0))

        w0 = jax.tree.map(jnp.copy, state["params"])
        losses = []
        for i, b in enumerate(first):
            state, (loss, _) = step(state, feed(b))
            losses.append(loss)
            if i == 0:
                # read before the next step donates the state
                g = first_grad(state, w0)
                g_norms = jax.jit(norms)(g)
                g = {k: np.asarray(v, np.float32) for k, v in
                     fam.from_program(jax.device_get(g)).items()}
        d = change(state, w0)
        del w0
        readings = {"losses": [float(x) for x in losses],
                    "grad": {k: float(v) for k, v in g_norms.items()},
                    "grad_tree": g,
                    "change": {k: float(v) for k, v in d.items()}}
        phases["first_steps_s"] = time.perf_counter() - t
    except BaseException:
        loader.close()
        raise
    return {"fam": fam, "mc": mc, "dims": dims, "opt": opt, "state": state,
            "step": step, "feed": feed, "loader": loader,
            "batches": [b["tokens"] for b in first], "readings": readings,
            "tokens_per_step": rows * seq, "seq_len": seq,
            "phases": phases}


def reference(fam, dims, seed, dtype, batches, opt, prec="f32",
              devices=None):
    """The family ``fam``'s reference readings: losses of the three
    steps, per-leaf norms of the first gradient and of the change after
    three steps.  ``devices`` share the sequences of a step."""
    w = fam.make_weights(dims, seed, dtype)
    losses, g, w3 = fam.train_steps(w, batches, dims, opt, prec, devices)
    out = {"losses": losses, "grad": fam.leaf_norms(g),
           "grad_tree": {k: np.asarray(v, np.float32) for k, v in g.items()},
           "change": fam.leaf_norms({k: w3[k].astype("float32")
                                     - w[k].astype("float32") for k in w})}
    del w, g, w3
    gc.collect()
    return out


def kept_leaves(r):
    """Leaves moved by the gradient: reference gradient norm at least a
    thousandth of the median leaf's."""
    med = float(np.median(list(r["grad"].values())))
    return [k for k in r["grad"] if r["grad"][k] >= 1e-3 * med]


def _host_norm(a) -> float:
    return float(np.sqrt(np.square(a, dtype=np.float64).sum()))


def _gap(prog: dict, refn: dict, keep) -> float:
    med = float(np.median([refn[k] for k in keep]))
    return max(abs(prog[k] - refn[k]) / max(refn[k], med) for k in keep)


def _diff(prog: dict, ref_tree: dict, refn: dict, keep) -> float:
    med = float(np.median([refn[k] for k in keep]))
    return max(_host_norm(prog[k] - ref_tree[k]) / max(refn[k], med)
               for k in keep)


def gaps(p, r) -> dict:
    """Program (or control) readings ``p`` against the reference's ``r``."""
    keep = kept_leaves(r)
    return {"loss1_gap": abs(p["losses"][0] - r["losses"][0]),
            "loss_gap": max(abs(a - b) for a, b in zip(p["losses"],
                                                       r["losses"])),
            "grad_gap": _gap(p["grad"], r["grad"], keep),
            "grad_diff": _diff(p["grad_tree"], r["grad_tree"], r["grad"],
                               keep),
            "change_gap": _gap(p["change"], r["change"], keep)}


def run(*, cfg, mix, seed, seconds, tracer, devs, t_start, faults):
    import jax
    from bench.harness import memory_peak
    pre = prepare(cfg, mix, seed, devs, faults)
    state, step, feed, loader = (pre["state"], pre["step"], pre["feed"],
                                 pre["loader"])
    try:
        jax.block_until_ready(state)
        n, wait, wait_max, traced, trace_at = 0, 0.0, 0.0, None, None
        win_losses, dispatched = [], []
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        t_end = t0 + seconds
        while True:
            now = time.perf_counter()
            if tracer.enabled and trace_at is None and now >= t0 + seconds / 2:
                jax.block_until_ready(state)
                tracer.start()
                trace_at = n
            if now >= t_end and (not tracer.enabled or traced is not None):
                break
            with tracer.span("next_batch"):
                a = time.perf_counter()
                b = next(loader)
                a = time.perf_counter() - a
                wait, wait_max = wait + a, max(wait_max, a)
            if len(win_losses) >= 2:
                # at most two steps in flight: the window then ends
                # within a step of its length, not wherever the host got
                with tracer.span("wait_step"):
                    win_losses[-2].block_until_ready()
            with tracer.span("dispatch_step"):
                state, (loss, _) = step(state, feed(b))
            dispatched.append(time.perf_counter() - t0)
            win_losses.append(loss)
            n += 1
            if trace_at is not None and traced is None \
                    and n - trace_at == TRACE_STEPS:
                with tracer.span("block"):
                    jax.block_until_ready(state)
                tracer.stop()
                traced = n - trace_at
        jax.block_until_ready(state)      # the window ends here
        t1 = time.perf_counter()
    finally:
        loader.close()
    win_losses = [float(x) for x in win_losses]
    memory = memory_peak(devs)
    del state, step, pre["state"], pre["step"]
    gc.collect()

    args = (pre["fam"], pre["dims"], seed, pre["mc"].param_dtype,
            pre["batches"], pre["opt"])
    r = reference(*args, devices=devs)
    p = pre["readings"]
    program_gaps = gaps(p, r)
    if faults.get("control"):
        p = reference(*args, prec="fp8", devices=devs)
    keep = kept_leaves(r)
    # the longest stretch between two dispatches in the window, and where
    steps_at = np.diff([0.0] + dispatched + [t1 - t0])
    longest = int(np.argmax(steps_at))
    rec = {"kind": "train", "setup_s": setup_s, "window_s": t1 - t0,
           "steps": n, "tokens": n * pre["tokens_per_step"],
           "attempted": n,
           "failed": int(sum(not np.isfinite(x) for x in win_losses)),
           "sound": bool(keep), "checks": gaps(p, r), "memory_peak": memory,
           "input_wait_s": wait, "family": cfg["family"],
           "dims": pre["dims"],
           "seq_len": pre["seq_len"],
           "info": {"losses_program": p["losses"],
                    "losses_reference": r["losses"],
                    "left_out": sorted(set(r["grad"]) - set(keep)),
                    "grad_norms_program": p["grad"],
                    "grad_norms_reference": r["grad"],
                    "change_norms_program": p["change"],
                    "change_norms_reference": r["change"],
                    "window_loss_first_last": win_losses[:1] + win_losses[-1:],
                    "steps": n, "setup_s": setup_s, "window_s": t1 - t0,
                    "setup_phases_s": pre["phases"],
                    "checks_program": program_gaps,
                    "input_wait_s_sum_max": [wait, wait_max],
                    "longest_gap_between_dispatches_s_at": [
                        float(steps_at[longest]),
                        float(sum(steps_at[:longest]))],
                    "memory_peak_bytes": memory}}
    if tracer.enabled:
        _reduce(rec, tracer.reduce(), traced, pre["tokens_per_step"])
    return rec


def _reduce(rec, trace, steps, tokens_per_step):
    from bench import trace_reduce as tr
    w0, w1 = tr.window_of(trace)
    busy = tr.busy(trace, w0, w1)
    exposed = tr.collective_exposed(trace, w0, w1)
    rec.update({
        "traced_s": (w1 - w0) * 1e-9, "traced_steps": steps,
        "traced_tokens": steps * tokens_per_step,
        "busy_s": tr.mean(busy) * 1e-9,
        "collective_exposed_s": tr.mean(exposed) * 1e-9,
        "breakdown": {"device_ops": tr.top_ops(trace, w0, w1),
                      "idle_gaps": tr.idle_gaps(trace, w0, w1)}})
