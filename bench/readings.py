"""Per-layer readings that more than one metric file shares.  Each returns
None where the run has nothing to read (no trace, no device, no step of
the kind), never 0 for a share."""

from __future__ import annotations

from bench import counts
from bench import trace as trace_lib


def peak_ops(run) -> float:
    """The chip's peak for the pack's operand type: int8 for the packed
    numerics, bfloat16 for float serving."""
    key = "bf16_flops_per_s" if run.cell.config["numerics"] == "float" \
        else "int8_ops_per_s"
    return run.peaks[key]


def step_ms(run, kind: str) -> float | None:
    """Mean device time of the step program in traced steps of ``kind``."""
    ns = [n for s, _, n in run.traced_steps() if s.kind == kind and n > 0]
    return sum(ns) / len(ns) / 1e6 if ns else None


def dense_roofline(run) -> float | None:
    """The packed dense layers' least time by the roofline (the family's
    ``dense_work`` on the rows each traced step computes) over the device
    time of the dense products in those steps, %."""
    steps = run.traced_steps()
    if not steps:
        return None
    least = sum(counts.least_time(
        *run.family.dense_work(run.model, run.rows(s), run.cv),
        run.peaks["int8_ops_per_s"], run.peaks["hbm_bytes_per_s"])[0]
        for s, _, _ in steps)
    dense_s = trace_lib.dense_ns(run.trace, [sp for _, sp, _ in steps]) / 1e9
    return 100.0 * least / dense_s if dense_s > 0 else None


def step_mfu(run) -> float | None:
    """Nominal model operations of the traced steps (the family's
    ``nominal_ops``) over their step programs' device time times the peak,
    %."""
    steps = [(s, n) for s, _, n in run.traced_steps() if n > 0]
    if not steps:
        return None
    ops = sum(run.family.nominal_ops(
        run.model, s.prompt_rows + s.decode_rows, s.context_sum)
        for s, _ in steps)
    dev_s = sum(n for _, n in steps) / 1e9
    return 100.0 * ops / (dev_s * peak_ops(run))


def idle_share(run) -> float | None:
    """1 - (union of device operation intervals) / window, %, averaged over
    the devices."""
    if not run.trace:
        return None
    got = trace_lib.idle_share(run.trace)
    return None if got is None else 100.0 * got[2]
