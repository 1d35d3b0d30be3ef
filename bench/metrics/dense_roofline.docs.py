"""Packed dense layers: the roofline's least time for their work (int8
operands, bench/counts.py) over the device time of their integer products
in the traced steps, %."""

from bench.readings import dense_roofline


def read(run):
    return dense_roofline(run)
