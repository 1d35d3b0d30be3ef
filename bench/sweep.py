#!/usr/bin/env python3
"""Find a cell's knee once: offer several fixed rates to one engine.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 0.6,0.9,1.2

One set-up, then for each rate, in the order given, the cell's lead-in and
an open-loop window of its mix as ``run.py`` drives them; the engine keeps
what the previous rate left running.  Prints one JSON line per rate:
requests due and finished, completed requests per second, TTFT and
queue-wait percentiles, the inter-token gap, and how many requests still
waited for a slot at the window's end (a growing backlog).  The knee is
the highest rate whose backlog stays flat; a chat cell then offers about
four fifths of it, written into its cell file as a number.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as R  # noqa: E402
from bench.stats import percentile  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    try:
        R.check_devices(int(cell.spec["chips"]))
    except R.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    served = R.set_up(cell, args.seed)
    print(f"set-up {time.perf_counter() - T_PROCESS:.1f}s", flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        jobs = R.make_jobs(cell, args.seed, args.seconds, rate)
        win = R.drive(served, cell, jobs, args.seconds, None,
                      lead_in=float(cell.spec.get("lead_in_s", 0.0)))
        run = R.Run(cell=cell, window=win, setup_s=0.0, peaks={},
                    cv=served.cv)
        end = win.t1
        done = [r for r in win.recs if r.req is not None and r.req.finished
                and r.tokens and r.tokens[-1] <= end]
        waits = [(r.admitted_at if r.admitted_at is not None else end)
                 - r.due for r in win.recs]
        line = {
            "rate": rate, "due": len(win.recs), "finished": len(done),
            "completed_per_s": len(done) / (end - win.t0),
            "gen_tok_per_s": run.generated_in_window() / (end - win.t0),
            "ttft_p50_ms": percentile(run.ttfts(), 50) * 1e3,
            "ttft_p90_ms": percentile(run.ttfts(), 90) * 1e3,
            "itl_p95_ms": (percentile(run.itls(), 95) or 0) * 1e3,
            "queue_wait_p90_ms": percentile(waits, 90) * 1e3,
            "waiting_at_end": sum(r.admitted_at is None for r in win.recs),
            "steps": len(win.steps),
            "decode_steps": sum(s.kind == "decode" for s in win.steps),
            "compiles_in_window": win.compiles}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
