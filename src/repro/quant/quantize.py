"""uint8 asymmetric quantization + approximate quantized linear algebra.

Quantization scheme (gemmlowp):  r = S * (q - Z),  q in [0, 255].

For a linear layer  y = A @ W + b  with activation codes qa (za, sa) and
weight codes qw (zw, sw):

    y = sa*sw * [ sum_k qa*qw  - zw*sum_k qa - za*sum_k qw + k*za*zw ] + b

Only the first term runs on the multiplier array; with an approximate
multiplier it becomes ``sum_k AM(qw, qa)`` and the paper's control variate V
is added to it (still inside the sa*sw rescale).  The zero-point corrections
stay exact (adder-side in hardware).
"""

from __future__ import annotations

import dataclasses
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core import control_variate as cv
from repro.core import multipliers as am

QMIN, QMAX = 0, 255


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QuantParams:
    """Affine quantization parameters.  scale/zero_point broadcast against the
    quantized tensor (scalars for per-tensor, vectors for per-channel)."""

    scale: jax.Array  # float32
    zero_point: jax.Array  # int32

    @staticmethod
    def identity() -> "QuantParams":
        return QuantParams(jnp.float32(1.0), jnp.int32(0))


def calibrate_minmax(lo, hi) -> QuantParams:
    """Affine parameters covering [lo, hi] (forced to include 0, per TFLite,
    so that zero pads/ReLU zeros are exactly representable)."""
    lo = jnp.minimum(jnp.asarray(lo, jnp.float32), 0.0)
    hi = jnp.maximum(jnp.asarray(hi, jnp.float32), 0.0)
    scale = jnp.maximum((hi - lo) / (QMAX - QMIN), 1e-12)
    zp = jnp.clip(jnp.round(QMIN - lo / scale), QMIN, QMAX).astype(jnp.int32)
    return QuantParams(scale=scale, zero_point=zp)


def calibrate_tensor(x, axis: int | None = None) -> QuantParams:
    """Min/max calibration over a tensor (per-tensor, or per-channel along
    ``axis`` — the non-reduced axis keeps its extent)."""
    if axis is None:
        return calibrate_minmax(jnp.min(x), jnp.max(x))
    reduce_axes = tuple(i for i in range(x.ndim) if i != axis % x.ndim)
    return calibrate_minmax(
        jnp.min(x, axis=reduce_axes), jnp.max(x, axis=reduce_axes)
    )


def quantize(x, qp: QuantParams) -> jax.Array:
    """Real -> uint8 codes (stored uint8)."""
    return quantize_i32(x, qp).astype(jnp.uint8)


def _as_f32(x) -> jax.Array:
    """``x`` in float32, holding exactly the values of its own dtype.

    Under XLA's excess-precision rule a fused producer of a bfloat16 tensor
    may hand its float32 value straight to the convert back to float32;
    ``reduce_precision`` pins the rounding, so the codes are the same
    whether the activations were materialized (as a kernel operand is) or
    fused away.
    """
    x = jnp.asarray(x)
    xf = x.astype(jnp.float32)
    if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype.itemsize < 4:
        fi = jnp.finfo(x.dtype)
        xf = jax.lax.reduce_precision(xf, exponent_bits=fi.nexp,
                                      mantissa_bits=fi.nmant)
    return xf


def quantize_i32(x, qp: QuantParams) -> jax.Array:
    """Real -> codes held directly in int32 (skips the uint8 round-trip;
    identical code values to :func:`quantize`, one fewer cast on hot paths)."""
    q = jnp.round(_as_f32(x) / qp.scale) + qp.zero_point
    return jnp.clip(q, QMIN, QMAX).astype(jnp.int32)


def dequantize(q, qp: QuantParams) -> jax.Array:
    return (jnp.asarray(q, jnp.int32) - qp.zero_point).astype(jnp.float32) * qp.scale


# ---------------------------------------------------------------------------
# Packed (offline-prepared) approximate linear layers
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PackedLinear:
    """Serving-time parameter pack for one approximate quantized linear.

    Produced offline by :func:`pack_linear` from float weights; consumed by
    :func:`quantized_linear` (and by the fused Pallas kernel path).

    w_q        (k, n) uint8 weight codes
    w_scale/w_zp   weight quant params (per-tensor scalars, or per-column
               (n,) vectors for fan-out-fused packs — see :func:`concat_packs`)
    sum_qw     (n,)  int32   column sums of codes (zero-point correction)
    c, c0      (n,) / (groups, n) float32 control-variate constants
    bias       (n,) float32 (or None)

    The CPU-serving fast path additionally folds the pack (+ activation
    quant params) into dense float matrices at pack time — see
    :func:`build_fold` — stored on the QuantizedDense wrapper, not here.
    """

    w_q: jax.Array
    w_scale: jax.Array
    w_zp: jax.Array
    sum_qw: jax.Array
    c: jax.Array
    c0: jax.Array
    bias: jax.Array | None


def pack_linear(
    w: jax.Array,
    bias: jax.Array | None,
    mode: am.Mode,
    m: int,
    groups: int = 1,
) -> PackedLinear:
    """Quantize float weights (k, n) and precompute CV constants offline."""
    qp = calibrate_tensor(w)
    w_q = quantize(w, qp)
    w_i = jnp.asarray(w_q, jnp.int32)
    if groups == 1:
        const = cv.cv_constants(w_i, mode, m, reduce_axis=0)
    else:
        const = cv.cv_constants_grouped(w_i, mode, m, groups, reduce_axis=0)
    return PackedLinear(
        w_q=w_q,
        w_scale=qp.scale,
        w_zp=qp.zero_point,
        sum_qw=jnp.sum(w_i, axis=0, dtype=jnp.int32),
        c=const.c,
        c0=const.c0,
        bias=None if bias is None else jnp.asarray(bias, jnp.float32),
    )


def concat_packs(packs: list[PackedLinear]) -> PackedLinear:
    """Fan-out fusion: concatenate sibling packs along the output axis.

    The members must share the fan-in ``k`` (they consume the same
    activations).  Per-tensor weight quant params become per-COLUMN vectors,
    so :func:`quantized_linear` on the fused pack computes, column for
    column, exactly the arithmetic of the separate member calls — the fused
    output is bit-identical to concatenating the member outputs (asserted in
    tests/test_serving_fastpath.py).
    """
    widths = [p.w_q.shape[-1] for p in packs]

    def per_col(v, n, dtype):
        v = jnp.asarray(v, dtype)
        # scalar (or per-layer-stacked scalar) -> one value per output column
        return jnp.broadcast_to(v[..., None], v.shape + (n,))

    has_bias = [p.bias is not None for p in packs]
    if any(has_bias) and not all(has_bias):
        raise ValueError("cannot fuse packs with mixed bias presence")
    return PackedLinear(
        w_q=jnp.concatenate([p.w_q for p in packs], axis=-1),
        w_scale=jnp.concatenate(
            [per_col(p.w_scale, n, jnp.float32) for p, n in zip(packs, widths)],
            axis=-1),
        w_zp=jnp.concatenate(
            [per_col(p.w_zp, n, jnp.int32) for p, n in zip(packs, widths)],
            axis=-1),
        sum_qw=jnp.concatenate([p.sum_qw for p in packs], axis=-1),
        c=jnp.concatenate([p.c for p in packs], axis=-1),
        c0=jnp.concatenate([p.c0 for p in packs], axis=-1),
        bias=(jnp.concatenate([p.bias for p in packs], axis=-1)
              if all(has_bias) else None),
    )


# ---------------------------------------------------------------------------
# Offline-blocked serving layout (zero per-call padding / meta assembly)
# ---------------------------------------------------------------------------

#: Serving-layout tile defaults (MXU-aligned; mirrored by the runtime block
#: picker in repro.kernels.ops).
SERVE_BN, SERVE_BK = 128, 512

#: Epilogue-table row indices (the single aligned operand the kernel's
#: epilogue reads): CV constants, zero-point corrections, per-column weight
#: quant params, bias.  Rows padded to 8 for sublane alignment.
EPI_C, EPI_C0, EPI_SUM_QW, EPI_BIAS, EPI_SW, EPI_ZW = range(6)
EPI_ROWS = 8

#: Meta-vector slots (per-tensor scalars the fused kernel needs).
META_SA, META_ZA = range(2)
META_LEN = 8


def shrink_block(size: int, block: int, floor: int) -> int:
    """Halve ``block`` toward ``floor`` while the operand is smaller than it
    — THE block-picking rule, shared by the offline layout (here) and the
    runtime picker (repro.kernels.ops._pick_blocks) so pad granularity and
    tile choice can never silently diverge."""
    while block > floor and size < block:
        block //= 2
    return max(block, floor)


def serving_blocks(k: int, n: int) -> tuple[int, int]:
    """(bn, bk) tile sizes the offline layout pads to, fixed at pack time."""
    return (
        shrink_block(n, SERVE_BN, 128 if n >= 128 else 8),
        shrink_block(k, SERVE_BK, 128 if k >= 128 else 8),
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BlockedPack:
    """Offline-blocked serving layout for one (possibly fused) linear.

    Everything the fused Pallas kernel consumes, already tiled and aligned
    at pack time — the forward pass does zero padding, zero concatenation,
    and zero scalar scatter:

    w_qb      (Kb, Nb) uint8 codes, padded to (bk, bn) multiples
    epilogue  (EPI_ROWS, Nb) f32 table, rows indexed by ``EPI_*``
    meta      (1, META_LEN) f32 per-tensor scalars, slots ``META_*``
    ``k``/``n`` are the true (unpadded) operand extents; ``bk``/``bn`` the
    pad granularity (the runtime may still *merge* K tiles for decode).
    """

    w_qb: jax.Array
    epilogue: jax.Array
    meta: jax.Array
    k: int = dataclasses.field(metadata=dict(static=True))
    n: int = dataclasses.field(metadata=dict(static=True))
    bk: int = dataclasses.field(metadata=dict(static=True))
    bn: int = dataclasses.field(metadata=dict(static=True))


def build_blocked_layout(pack: PackedLinear, a_qp: QuantParams,
                         bn: int | None = None,
                         bk: int | None = None) -> BlockedPack:
    """Pad/assemble a pack into the serving layout, once, offline.

    Only defined for single-CV packs (``c`` of shape (n,)); grouped CV uses
    the jnp path.  ``sum_qw`` is stored as f32 — exact while 255*k < 2^24.
    """
    k, n = pack.w_q.shape[-2:]
    if pack.c.ndim != pack.sum_qw.ndim:
        raise ValueError("blocked layout requires groups == 1 CV constants")
    if 255 * k >= (1 << 24):
        raise ValueError(f"fan-in {k} overflows f32-exact sum_qw storage")
    if bn is None or bk is None:
        bn_d, bk_d = serving_blocks(k, n)
        bn = bn or bn_d
        bk = bk or bk_d
    kb, nb = -(-k // bk) * bk, -(-n // bn) * bn

    w_qb = jnp.pad(pack.w_q, ((0, kb - k), (0, nb - n)))

    def row(v, fill_n=n):
        v = jnp.broadcast_to(jnp.asarray(v, jnp.float32), (fill_n,))
        return jnp.pad(v, (0, nb - fill_n))

    epi = jnp.stack([
        row(pack.c),
        row(pack.c0),
        row(pack.sum_qw),
        row(pack.bias if pack.bias is not None else jnp.zeros((n,), jnp.float32)),
        row(pack.w_scale),
        row(pack.w_zp),
    ] + [jnp.zeros((nb,), jnp.float32)] * (EPI_ROWS - 6))

    meta = jnp.zeros((META_LEN,), jnp.float32)
    meta = meta.at[META_SA].set(jnp.asarray(a_qp.scale, jnp.float32))
    meta = meta.at[META_ZA].set(jnp.asarray(a_qp.zero_point, jnp.float32))
    return BlockedPack(w_qb=w_qb, epilogue=epi, meta=meta.reshape(1, META_LEN),
                       k=k, n=n, bk=bk, bn=bn)


def _f32_dot(a_f: jax.Array, w_f: jax.Array) -> jax.Array:
    # Precision.HIGHEST: true f32 multiplies (TPU's default bf16-pass dot
    # would round the products and void the ulp-agreement contract)
    return jax.lax.dot_general(
        a_f, w_f, (((a_f.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)


def build_fold(pack: PackedLinear, a_qp: QuantParams, mode: am.Mode, m: int,
               use_cv: bool) -> dict | None:
    """Fold the ENTIRE serving epilogue into dense float matrices, offline.

    The quantized-linear identity

        y = sa*sw * [ acc + V - zw*sumqa - za*sum_qw + k*za*zw ] + b

    is linear in the runtime quantities (the code-product accumulator and
    the per-row sums), all of which are themselves linear in the activation
    CODES and their mode transform.  So the whole layer collapses to

        y = codes @ A  (+ op2 @ B)  + delta

    with A/B/delta precomputed here: A carries alpha*W plus the sumqa
    coefficient folded into every column; B carries the mode's subtractive
    slice (perforated: W, recursive: W&mask, truncated: bitplanes) scaled by
    -alpha, with the CV constant C*alpha folded into the same operand (the
    CV statistic sumx is linear in op2 too); delta collects every
    activation-independent term (C0, za corrections, bias).  ``op2`` is
    ``codes mod 2^m`` (perforated/recursive) or the activation bitplanes
    [+ nonzero-low indicator] (truncated) — pure f32 elementwise work at
    run time, no int round-trips.

    This is the jnp/CPU analogue of the Pallas blocked layout: serving
    becomes plain float GEMMs against offline-prepared operands (exact-int8
    is literally ONE dot plus a constant).  Products are no longer integer-
    exact — results agree with the reference integer path to float ulps,
    far below quantization error.  Built only for single-CV packs at
    fan-ins where the f32 staging copy is cheap (k <= 258); deep fan-ins
    are matmul-dominated and keep the exact integer path.
    """
    k, n = pack.w_q.shape[-2:]
    if pack.c.ndim != pack.sum_qw.ndim:  # grouped CV: no fold
        return None
    if k > am._F32_EXACT_K:
        return None

    w_f = jnp.asarray(pack.w_q, jnp.float32)
    sum_qw = pack.sum_qw.astype(jnp.float32)

    def col(v):
        """Align per-tensor / per-layer / per-column values to (..., n)."""
        v = jnp.asarray(v, jnp.float32)
        return v if v.ndim == sum_qw.ndim else v[..., None]

    za = col(a_qp.zero_point)
    zw = col(pack.w_zp)
    alpha = col(a_qp.scale) * col(pack.w_scale)
    beta = -(zw * alpha)  # sumqa coefficient
    delta = (k * za) * zw - za * sum_qw
    has_cv = use_cv and mode != "exact" and m > 0
    if has_cv:
        delta = delta + pack.c0
    delta = delta * alpha
    if pack.bias is not None:
        delta = delta + pack.bias

    def row(v):  # (..., n) -> (..., 1, n) to broadcast over the k axis
        return v[..., None, :] if v.ndim == sum_qw.ndim else v[..., None]

    fold = {
        "sa": jnp.asarray(a_qp.scale, jnp.float32),
        "za": jnp.asarray(a_qp.zero_point, jnp.float32),
        "A": w_f * row(alpha) + row(beta),
        "delta": delta,
    }
    if mode == "exact" or m == 0:
        return fold
    cv_row = row(pack.c * alpha) if has_cv else None
    if mode in ("perforated", "recursive"):
        w_slice = w_f if mode == "perforated" else (
            jnp.asarray(pack.w_q, jnp.int32) & ((1 << m) - 1)
        ).astype(jnp.float32)
        b_mat = -w_slice * row(alpha)
        if has_cv:
            b_mat = b_mat + cv_row
        fold["B"] = b_mat
        return fold
    # truncated: op2 = [bitplanes (m*k) | nonzero-low indicator (k, CV only)]
    planes = jnp.concatenate(
        [am.low_bits(pack.w_q, m - i) for i in range(m)],
        axis=-2).astype(jnp.float32)
    b_mat = -planes * row(alpha)
    if has_cv:
        b_mat = jnp.concatenate(
            [b_mat, jnp.broadcast_to(cv_row, w_f.shape)], axis=-2)
    fold["B"] = b_mat
    return fold


def folded_linear(a: jax.Array, fold: dict, mode: am.Mode, m: int,
                  use_cv: bool) -> jax.Array:
    """Serving fast path: float in -> float out via the folded operands.

    One fused elementwise pass (quantize + mode transform, all f32 —
    mod-by-power-of-two is exact on small integer floats), one or two float
    GEMMs, one constant add.  Semantics match :func:`quantized_linear` to
    float ulps (see :func:`build_fold`).
    """
    codes = jnp.clip(jnp.round(_as_f32(a) / fold["sa"]) + fold["za"],
                     QMIN, QMAX)
    y = _f32_dot(codes, fold["A"])
    if "B" in fold:
        scale = float(1 << m)
        lo = codes - scale * jnp.floor(codes / scale)  # codes mod 2^m
        if mode in ("perforated", "recursive"):
            op2 = lo
        else:  # truncated bitplanes (bit i scaled by 2^i), peeled bottom-up
            planes = []
            rest = codes
            for i in range(m):
                p2 = float(1 << (i + 1))
                b = rest - p2 * jnp.floor(rest / p2)
                planes.append(b)
                rest = rest - b
            if use_cv:
                planes.append(jnp.where(lo != 0, 1.0, 0.0))
            op2 = jnp.concatenate(planes, axis=-1)
        y = y + _f32_dot(op2, fold["B"])
    return y + fold["delta"]


def quantized_linear(
    a: jax.Array,
    pack: PackedLinear,
    a_qp: QuantParams,
    mode: am.Mode,
    m: int,
    use_cv: bool = True,
    groups: int = 1,
) -> jax.Array:
    """Approximate quantized linear: float in -> float out.

    a: (..., k) float activations, quantized on the fly with ``a_qp``
    (calibrated offline, as in TFLite).  The code-product sum uses the
    bit-slice matmul forms of :mod:`repro.core.multipliers`; the control
    variate V is the paper's rank-1 correction.

    ``pack`` may be a fan-out-fused pack (per-column ``w_scale``/``w_zp``
    from :func:`concat_packs`) — every correction broadcasts per column, so
    the math per output column is unchanged.

    This is the exact-integer reference path (and the grouped-CV path);
    serving goes through :func:`folded_linear` when the packed layer
    carries fold operands.
    """
    k = a.shape[-1]
    a_i = quantize_i32(a, a_qp)
    acc = am.approx_matmul(a_i, pack.w_q, mode, m)
    # Exact zero-point corrections (gemmlowp adder-side arithmetic), in
    # int32: every term is an integer and the bracket's true value is
    # bounded by 255^2 * k, so it is exact where an f32 sum would cancel.
    sum_qa = jnp.sum(a_i, axis=-1, dtype=jnp.int32)
    zw = pack.w_zp.astype(jnp.int32)
    za = a_qp.zero_point.astype(jnp.int32)
    acc = acc - zw * sum_qa[..., None] - za * pack.sum_qw + k * za * zw
    y = acc.astype(jnp.float32)
    if use_cv and mode != "exact" and m > 0:
        const = cv.CVConstants(c=pack.c, c0=pack.c0)
        # the scope a device trace reads the CV's share of the step by
        with jax.named_scope("cv"):
            if groups == 1:
                y = y + cv.cv_term(a_i, const, mode, m)
            else:
                y = y + cv.cv_term_grouped(a_i, const, mode, m, groups)
    y = y * (a_qp.scale * pack.w_scale)
    if pack.bias is not None:
        y = y + pack.bias
    return y
