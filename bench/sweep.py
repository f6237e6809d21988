"""Find the rate a serving mix saturates the engine at, once, by a sweep
of offered rates on the chip.

    python3 bench/sweep.py --workload <cell> --rates 0.1,0.2,0.4 --seconds 51

For each rate, one window of the cell's mix at that rate, driven as the
cell drives it; prints the output tokens per second inside the window,
the requests due, those that were served tokens, those finished, and
the backlog at the window's end (due but served nothing yet).  Above the
rate the engine sustains, the backlog grows with the rate and tokens per
second stop rising.  The cell's rate is written into its traffic file by
hand, as a number.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness, program, traffic  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    spec, cell, cfg, mix, _ = harness.load_cell(args.workload)
    program.import_program()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    harness.chips(cell["chips"])
    from bench import serve_cell as sc
    from repro.models.model import build_model
    mc = program.model_config(cfg)
    model = build_model(mc)
    for rate in (float(r) for r in args.rates.split(",")):
        m = {**mix, "rate_per_s": rate}
        engine = sc.build(cfg, args.seed, model)
        plan = traffic.schedule(m, args.seed, args.seconds, mc.vocab_size)
        out = sc.serve(engine, plan, args.seconds, harness.Tracer(False),
                       time.perf_counter())
        del engine
        gc.collect()
        due = sum(1 for d in plan if d.due_s <= out["window_s"])
        print(json.dumps({
            "rate_per_s": rate, "due": due, "served": len(out["served"]),
            "finished": len(out["results"]),
            "backlog": due - len(out["served"]),
            "tokens_per_s": out["tokens_in_window"] / out["window_s"],
            "counters": out["c1"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
