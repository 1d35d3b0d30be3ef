"""Model step, KV cache: device time of the operations scoped ``kv_write``
or ``layer_stack`` with no inner scope (the cache's writes and the layer
scan's slicing and restacking) over the device time of the step programs
(``jit_engine_*_step``) in the traced window, % (profiler trace)."""

from bench import engine_trace


def read(run):
    rec = engine_trace.record(run)
    return None if rec is None else engine_trace.scope_share(
        rec, engine_trace.KV_STACK)
