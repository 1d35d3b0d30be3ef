"""Whole step: nominal model operations of the traced steps over their
device time times the peak of the pack's operand type, %."""

from bench.readings import step_mfu


def read(run):
    return step_mfu(run)
