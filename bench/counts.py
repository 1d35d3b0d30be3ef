"""Operations and bytes of the work a step asks for, from shapes alone.

The packed dense layers are counted as the paper's layer: an int8 x int8
product with one byte per weight, the zero-point corrections of an affine
int8 layer, and, for an approximate pack with the control variate (CV),
its per-row statistic and per-column correction.  So a layer reads the
same work whatever implements it (an XLA dot or a Pallas kernel, an
emulated approximate product or an exact one): the emulation's extra
products are cost, not work.

The model's nominal operations (for ``step_mfu``) are 2 x the matmul
parameters per processed token (every packed projection and the tied
head) plus attention over the live context: 2 x 2 x layers x heads x
head size per context position (scores and weighted values).
"""

from __future__ import annotations


def dense_layers(model: dict) -> list[tuple[str, int, int]]:
    """(name, fan-in K, fan-out N) of one layer's packed projections."""
    d, f = model["d_model"], model["d_ff"]
    qd = model["n_heads"] * model["head_dim"]
    kvd = model["kv_heads"] * model["head_dim"]
    return [("q", d, qd), ("k", d, kvd), ("v", d, kvd), ("o", qd, d),
            ("gate", d, f), ("up", d, f), ("down", f, d)]


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


def dense_work(model: dict, rows: int, cv: bool) -> tuple[float, float]:
    """(operations, bytes) of every packed dense layer of the model for one
    call of ``rows`` activation rows (:func:`layer_work` summed)."""
    ops = nbytes = 0.0
    for _, k, n in dense_layers(model):
        o, b = layer_work(rows, k, n, cv)
        ops += o
        nbytes += b
    return ops * model["n_layers"], nbytes * model["n_layers"]


def least_time(ops: float, nbytes: float, peak_ops: float,
               peak_bw: float) -> tuple[float, str]:
    """The roofline's least time, and which bound sets it."""
    t_ops, t_mem = ops / peak_ops, nbytes / peak_bw
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def matmul_params(model: dict) -> int:
    """Parameters of every matmul a token passes: the packed projections of
    every layer and the tied head."""
    per_layer = sum(k * n for _, k, n in dense_layers(model))
    return per_layer * model["n_layers"] + model["d_model"] * model["vocab"]


def nominal_ops(model: dict, tokens: float, context_sum: float) -> float:
    """Nominal model operations of ``tokens`` processed tokens whose live
    contexts sum to ``context_sum`` positions."""
    attn = 4.0 * model["n_layers"] * model["n_heads"] * model["head_dim"]
    return 2.0 * matmul_params(model) * tokens + attn * context_sum
