"""MoE layer: device time of the operations scoped ``router``, ``experts``
or ``shared_expert`` over the device time of the step programs
(``jit_engine_*_step``) in the traced window, %."""

from bench import moe_trace


def read(run):
    rec = moe_trace.record(run)
    return None if rec is None else moe_trace.share(rec, moe_trace.SCOPES)
