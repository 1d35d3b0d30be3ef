"""Model step: device time of one chunk-shaped call of the jitted step, mean
over the traced window, ms (profiler trace)."""

from bench.readings import step_ms


def read(run):
    return step_ms(run, "chunk")
