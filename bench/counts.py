"""Operations and bytes of the work a step asks for, from shapes alone.

A packed layer is counted as the paper's layer: an int8 x int8 product
with one byte per weight, the zero-point corrections of an affine int8
layer, and, for an approximate pack with the control variate (CV), its
per-row statistic and per-column correction.  So a layer reads the same
work whatever implements it (an XLA dot or a Pallas kernel, an emulated
approximate product or an exact one): the emulation's extra products are
cost, not work.  Which layers a model has, and its nominal operations, are
its family's (``bench/families/<family>.py``).
"""

from __future__ import annotations


def layer_work(rows: int, k: int, n: int, cv: bool) -> tuple[float, float]:
    """(operations, bytes) of one packed layer of fan-in ``k`` and fan-out
    ``n`` on ``rows`` activation rows.

    Operations: 2·rows·k·n for the product; rows·k for the row sums of the
    activation codes and 3·rows·n for the zero-point corrections; with the
    CV, rows·k more for its row statistic and 2·rows·n for its per-column
    correction.  Bytes: k·n weight codes, rows·k bfloat16 inputs, rows·n
    bfloat16 outputs, and per column the int32 weight-code sum (4 bytes)
    plus, with the CV, its two float32 constants (8 bytes).
    """
    ops = 2.0 * rows * k * n + rows * k + 3.0 * rows * n
    nbytes = k * n + 2.0 * rows * k + 2.0 * rows * n + 4.0 * n
    if cv:
        ops += rows * k + 2.0 * rows * n
        nbytes += 8.0 * n
    return ops, nbytes


def least_time(ops: float, nbytes: float, peak_ops: float,
               peak_bw: float) -> tuple[float, str]:
    """The roofline's least time, and which bound sets it."""
    t_ops, t_mem = ops / peak_ops, nbytes / peak_bw
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
