"""Benchmark harness — one module per paper table/figure + roofline report.

    PYTHONPATH=src python -m benchmarks.run             # all
    PYTHONPATH=src python -m benchmarks.run table1 fig10 ...

Prints one CSV-ish line per row: ``name,us_per_call,derived...``.  A suite
that raises prints an ERROR row, the others still run, and the exit code
is non-zero.
Heavy steps cache under artifacts/ (CNN training, dry-run compiles), so
re-runs are fast and the final tee'd output is reproducible.
"""

from __future__ import annotations

import json
import sys
import time


def _emit(rows: list[dict]) -> None:
    for r in rows:
        name = r.pop("name", "?")
        us = r.pop("us_per_call", "")
        derived = json.dumps(r, sort_keys=True, default=str)
        print(f"{name},{us},{derived}", flush=True)


SUITES = [
    ("table1", "benchmarks.table1_error"),
    ("conv_error", "benchmarks.conv_error_validation"),
    ("tables2_4", "benchmarks.tables2_4_accuracy"),
    ("fig7_9", "benchmarks.fig7_9_power"),
    ("table5", "benchmarks.table5_overhead"),
    ("fig10", "benchmarks.fig10_pareto"),
    ("kernels", "benchmarks.kernel_bench"),
    ("serve", "benchmarks.serve_bench"),
    ("roofline", "benchmarks.roofline_report"),
]


def main() -> int:
    import importlib

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    want = set(sys.argv[1:])
    t0 = time.time()
    failed = []
    for key, modname in SUITES:
        if want and key not in want:
            continue
        print(f"# --- {key} ({modname}) ---", flush=True)
        mod = importlib.import_module(modname)
        try:
            rows = mod.run()
        except Exception as e:  # a failed suite must not hide the others
            rows = [{"name": f"{key}/ERROR", "error": f"{type(e).__name__}: {e}"}]
            failed.append(key)
        _emit(rows)
    print(f"# total {time.time() - t0:.1f}s", flush=True)
    if failed:
        print(f"# FAILED suites: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
