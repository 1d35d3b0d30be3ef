"""Engine host loop: mean over the traced ``engine.step`` spans of the span
less its ``engine.fetch`` children (the host's work while the device has
nothing queued), ms (profiler trace)."""

from bench import engine_trace


def read(run):
    rec = engine_trace.record(run)
    return None if rec is None else engine_trace.host_ms_per_step(rec)
