"""Readings that the correctness limits are set from (not run by the
benchmark's own runs).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--fault control] [--seconds 51]

Runs the cell once per seed, in one process, through the harness's own
``run_cell`` with the committed limits, and prints one JSON line per
seed: ``correct``, each number compared beside its limit, and the sound
program's numbers where the control replaced them.  With no ``--fault``
the program's own numbers are the lower readings; with ``--fault
control`` (the reference in the program's place at float8 e4m3) they
are the control's, which has to come out not correct.

A training cell's readings need no window (``--seconds 1``); a serving
cell's need the cell's own window, so that as many requests are served.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault", default=None, choices=("control",))
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(args.workload, seed, args.seconds, False,
                               time.perf_counter(),
                               overrides={"faults": {args.fault: True}}
                               if args.fault else {})
        info = out["_info"]
        print(json.dumps({
            "seed": seed, "fault": args.fault, "correct": out["correct"],
            "checks": out["checks"], "metrics": out["metrics"],
            "program": info.get("checks_program",
                                {"logit_gap": info.get("logit_gap_program")}),
            "sample_served_tokens": info.get("sample_served_tokens")}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
