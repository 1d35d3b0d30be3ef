#!/usr/bin/env python3
"""Read what a cell's ``correct`` limit is set from, on many seeds in one
process.

    python bench/limits.py --workload <cell> --seconds <s> --seeds a,b,c \
        [--control]

For each seed, one run as ``run.py`` makes it (set-up, lead-in, a window
of ``--seconds`` at the cell's own load, the comparison with the
reference), with the configuration's control in the program's place
under ``--control``.  Prints one JSON line per seed: the numbers compared
beside their limits, each sampled request's mean gap, and what the run's
earlier lines hold.  The sound runs' largest reading and the control's
smallest are the two readings a limit lies between.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as R  # noqa: E402

KEEP = ("served_gap_mean", "worst_request_gap", "request_gaps",
        "served_gap_max", "top1_agreement", "served_tokens", "requests",
        "finished", "reference_s", "lead_in_s", "weights_s", "pack_s",
        "engine_s", "warm_up_s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    t_start = T_PROCESS
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            res = R.run_cell(cell, seed, args.seconds, False,
                             control=args.control, t_start=t_start)
        except R.NoChip as e:
            print(f"limits: {e}", file=sys.stderr)
            return 2
        ex = res.pop("_extra")
        print(json.dumps({
            "workload": cell.name, "seed": seed, "control": args.control,
            "correct": res["correct"], "checks": res["checks"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            **{k: ex[k] for k in KEEP if k in ex}}), flush=True)
        gc.collect()
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
