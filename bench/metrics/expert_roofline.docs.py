"""Routed experts: the roofline's least time of the held experts' grouped
products (int8 operands; the family's ``expert_work`` on the ``pairs`` and
``groups_hit`` of the engine's ``engine.moe`` spans) over the device time
of the operations scoped ``experts`` in the step programs of the traced
window, %."""

from bench import counts, moe_trace
from bench.readings import peak_ops


def read(run):
    rec = moe_trace.record(run)
    work = getattr(run.family, "expert_work", None)
    if rec is None or work is None or not rec["pairs"]:
        return None
    ns = rec["scope_ns"].get("experts", 0)
    if ns <= 0:
        return None
    least, _ = counts.least_time(
        *work(run.model, rec["pairs"], rec["groups_hit"], run.cv),
        peak_ops(run), run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ns / 1e9)
