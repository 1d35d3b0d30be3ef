"""Fused approximate-multiplier matmul with control-variate epilogue (Pallas TPU).

This is the TPU realization of the paper's approximate systolic array
(DESIGN.md Sec. 2): one kernel computes, for uint8 activation codes A (M, K)
and weight codes W (K, N),

    acc[m, n]  = sum_k AM(W[k, n], A[m, k])          (bit-slice MXU algebra)
    sumx[m]    = sum_k x(A[m, k])                    (the MAC* side-adder)
    sumqa[m]   = sum_k A[m, k]                       (gemmlowp correction)
    out[m, n]  = sa*sw * ( acc + CV + zero-point corrections ) + bias
       CV      = sumx[m] * C[n] + C0[n]              (the MAC+ column == fused
                                                      rank-1 epilogue)

All integer arithmetic is exact int32; the AM semantics are bit-exact with
the scalar hardware definitions in :mod:`repro.core.multipliers` (asserted
against `ref.py` in tests).  The approximate products are *decompositions
into exact integer matmuls* on int8 operands, the MXU's integer type:

    perforated: dot(A & ~mask, W)
    recursive : dot(A, W) - dot(A & mask, W & mask)
    truncated : dot(A, W) - sum_{i<m} 2^i dot(bit_i(A), W mod 2^{m-i})

(uint8 codes that can reach 128 are shifted by -128; see _am_tile_acc).

Grid: (M/bm, N/bn, K/bk) with the K axis innermost ("arbitrary" semantics);
accumulators live in VMEM scratch across K steps; the epilogue fires on the
final K step.  Block shapes default to MXU-aligned (128, 128, 512).

TPU is the *target*; on the CPU backend ops.py runs the kernels in
interpret mode (the correctness path), and refuses any other backend.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.multipliers import Mode
from repro.quant.quantize import (EPI_BIAS, EPI_C, EPI_C0, EPI_ROWS, EPI_SUM_QW,
                                  EPI_SW, EPI_ZW, META_LEN, META_SA, META_ZA)

# MXU-aligned defaults: int8-friendly tiles, K deep enough to amortize the
# epilogue; A tile (128x512) + W tile (512x128) + int32 acc (128x128) stay
# well under VMEM with double buffering.
DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 512


#: uint8 codes 0..255 reach the MXU as int8: an operand that can reach 2^7
#: is shifted down by this offset, and the shift is folded back exactly.
_OFFSET = 128


def _dot_i8(p, q):
    """Exact int8 x int8 -> int32 tile matmul (the MXU's integer path)."""
    return jax.lax.dot_general(
        p.astype(jnp.int8), q.astype(jnp.int8), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def _am_terms(mode: Mode, m: int):
    """sum_k AM(w, a) as signed bit-slice products (static description).

    Each term ``(sign, shift, a_op, w_op)`` contributes
    ``sign * 2^shift * (a_op(A) @ w_op(W))``; an op is ``(kind, j)``:
    ``all`` (the codes), ``hi`` (codes with the low j bits cleared),
    ``lo`` (the low j bits) or ``bit`` (bit j).
    """
    full = ("all", 8)
    if mode == "exact" or m == 0:
        return [(1, 0, full, full)]
    if mode == "perforated":
        return [(1, 0, ("hi", m), full)]
    if mode == "recursive":
        return [(1, 0, full, full), (-1, 0, ("lo", m), ("lo", m))]
    if mode == "truncated":
        return [(1, 0, full, full)] + [
            (-1, i, ("bit", i), ("lo", m - i)) for i in range(m)]
    raise ValueError(f"unknown mode {mode}")


def _op_bits(op) -> int:
    """Operand values lie in [0, 2^bits)."""
    kind, j = op
    return {"all": 8, "hi": 8, "lo": j, "bit": 1}[kind]


def _apply_op(x, op):
    kind, j = op
    if kind == "all":
        return x
    if kind == "hi":
        return x - (x & ((1 << j) - 1))
    if kind == "lo":
        return x & ((1 << j) - 1)
    return (x >> j) & 1


def _w_offset_coef(mode: Mode, m: int) -> int:
    """Weight of the per-column offset term in the epilogue (see
    :func:`_am_tile_acc`): the signed count of full-range products."""
    return sum(sign << shift for sign, shift, a_op, _ in _am_terms(mode, m)
               if _op_bits(a_op) == 8)


def _am_tile_acc(a, w, mode: Mode, m: int):
    """sum_k AM(w, a) for one (bm, bk) x (bk, bn) tile on int8 operands.

    ``a``/``w`` hold int32 codes 0..255.  Returns ``(acc, row)`` with

        sum_k AM(w, a) == acc + row
                          + coef * (128 * colsum(w) - 128^2 * bk)

    for ``coef = _w_offset_coef(mode, m)``.  An operand that can reach
    2^7 is shifted to x - 128 to fit int8: with p = p' + 128 and
    q = q' + 128, p @ q = p' @ q' + 128 rowsum(p) + 128 colsum(q) - 128^2 bk,
    and with only q shifted, p @ q = p @ q' + 128 rowsum(p).  A full-range
    activation operand only ever meets the full-range weight operand ``w``
    itself, so the colsum terms sum over the K tiles to the pack's sum_qw
    and the kernel adds them once, in the epilogue.  Planes scaled by 2^i
    enter the dot as 0/1 bits; the shift is applied to the int32 result.
    """
    acc = row = None
    for sign, shift, a_op, w_op in _am_terms(mode, m):
        p, q = _apply_op(a, a_op), _apply_op(w, w_op)
        if _op_bits(w_op) == 8:
            r = _OFFSET * jnp.sum(p, axis=1, dtype=jnp.int32, keepdims=True)
            row = _accumulate(row, r, sign, shift)
            q = q - _OFFSET
        if _op_bits(a_op) == 8:
            p = p - _OFFSET
        acc = _accumulate(acc, _dot_i8(p, q), sign, shift)
    return acc, row


def _accumulate(total, v, sign: int, shift: int):
    """total + sign * 2^shift * v, with None as the empty total."""
    v = v << shift if shift else v
    if total is None:
        return v if sign > 0 else -v
    return total + v if sign > 0 else total - v


def _x_tile(a_i32, mode: Mode, m: int):
    """x(A) per element for one tile (the MAC* statistic)."""
    mask = (1 << m) - 1
    if mode in ("perforated", "recursive"):
        return a_i32 & mask
    if mode == "truncated":
        return ((a_i32 & mask) != 0).astype(jnp.int32)
    raise ValueError(f"unknown mode {mode}")


def _accumulate_tile(a, w, acc_ref, row_ref, sumx_ref, sumqa_ref, *,
                     mode: Mode, m: int, use_cv: bool):
    """Add one K tile's products and per-row sums into the scratch."""
    acc, row = _am_tile_acc(a, w, mode, m)
    acc_ref[...] += acc
    row_ref[...] += row
    sumqa_ref[...] += jnp.sum(a, axis=1, dtype=jnp.int32, keepdims=True)
    if use_cv and mode != "exact" and m > 0:
        sumx_ref[...] += jnp.sum(
            _x_tile(a, mode, m), axis=1, dtype=jnp.int32, keepdims=True
        )


def _epilogue(acc_ref, row_ref, sumx_ref, sumqa_ref, *, c, c0, sum_qw, bias,
              scale, za, zw, k: int, k_total: int, mode: Mode, m: int,
              use_cv: bool):
    """Dequantized output, computed as
    :func:`repro.quant.quantize.quantized_linear` computes it, op for op.

    The code-product sum and every zero-point correction are integers, so
    the whole bracket  acc - zw*sumqa - za*sum_qw + k*za*zw  is formed in
    int32 (exact: the true value is bounded by 255^2 * k) and only the CV
    term and the rescale run in float.  ``za``/``zw``/``sum_qw`` are int32;
    ``k`` is the true fan-in, ``k_total`` the padded K the tiles covered.
    """
    acc = acc_ref[...] + row_ref[...]
    coef = _w_offset_coef(mode, m)
    if coef:
        acc = acc + coef * (_OFFSET * sum_qw - _OFFSET * _OFFSET * k_total)
    acc = acc - zw * sumqa_ref[...] - za * sum_qw + k * za * zw
    out = acc.astype(jnp.float32)
    if use_cv and mode != "exact" and m > 0:
        # the paper's MAC+ column: rank-1 update + bias-folded C0
        out = out + (sumx_ref[...].astype(jnp.float32) * c + c0)
    return out * scale + bias


def _scratch(bm: int, bn: int):
    """acc (bm, bn) plus the per-row sums: offset row, sumx, sumqa."""
    return [pltpu.VMEM((bm, bn), jnp.int32)] + [
        pltpu.VMEM((bm, 1), jnp.int32) for _ in range(3)]


def _kernel(
    # inputs
    a_ref,  # (bm, bk) uint8 codes
    w_ref,  # (bk, bn) uint8 codes
    c_ref,  # (1, bn) f32   CV constant C
    c0_ref,  # (1, bn) f32  CV constant C0
    sum_qw_ref,  # (1, bn) i32  column sums of W codes
    bias_ref,  # (1, bn) f32
    meta_ref,  # (1, 8) f32: [sa, sw, za, zw, 0, 0, 0, 0]
    # outputs
    out_ref,  # (bm, bn) f32
    # scratch
    acc_ref,  # (bm, bn) i32
    row_ref,  # (bm, 1) i32
    sumx_ref,  # (bm, 1) i32
    sumqa_ref,  # (bm, 1) i32
    *,
    mode: Mode,
    m: int,
    use_cv: bool,
    nk: int,
    bk: int,
    k: int,
):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        for ref in (acc_ref, row_ref, sumx_ref, sumqa_ref):
            ref[...] = jnp.zeros_like(ref)

    _accumulate_tile(a_ref[...].astype(jnp.int32),
                     w_ref[...].astype(jnp.int32),
                     acc_ref, row_ref, sumx_ref, sumqa_ref,
                     mode=mode, m=m, use_cv=use_cv)

    @pl.when(k_step == nk - 1)
    def _epi():
        out_ref[...] = _epilogue(
            acc_ref, row_ref, sumx_ref, sumqa_ref,
            c=c_ref[...], c0=c0_ref[...], sum_qw=sum_qw_ref[...],
            bias=bias_ref[...], scale=meta_ref[0, 0] * meta_ref[0, 1],
            za=meta_ref[0, 2].astype(jnp.int32),
            zw=meta_ref[0, 3].astype(jnp.int32),
            k=k, k_total=nk * bk, mode=mode, m=m, use_cv=use_cv)


def _compiler_params(nk: int):
    # single K step (decode-specialized tiles): no cross-step accumulator
    # carry, so every grid axis is freely parallel/reorderable
    sem = "parallel" if nk == 1 else "arbitrary"
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", sem))


@functools.partial(
    jax.jit,
    static_argnames=(
        "mode", "m", "k", "use_cv", "bm", "bn", "bk", "interpret",
    ),
)
def approx_matmul_cv(
    a_q: jax.Array,  # (M, K) uint8 codes
    w_q: jax.Array,  # (K, N) uint8 codes
    c: jax.Array,  # (N,) f32
    c0: jax.Array,  # (N,) f32
    sum_qw: jax.Array,  # (N,) i32
    bias: jax.Array,  # (N,) f32 (zeros if no bias)
    sa: jax.Array,  # scalar f32 activation scale
    sw: jax.Array,  # scalar f32 weight scale
    za: jax.Array,  # scalar i32/f32 activation zero point
    zw: jax.Array,  # scalar (an integer zero point)
    *,
    mode: Mode,
    m: int,
    k: int,
    use_cv: bool = True,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    """Fused quantized approximate matmul; returns float32 (M, N).

    Shapes must be pre-padded to block multiples with zero codes (ops.py
    handles padding and arbitrary leading batch dims); ``k`` is the true
    fan-in.
    """
    mm, kk = a_q.shape
    kk2, nn = w_q.shape
    assert kk == kk2, (a_q.shape, w_q.shape)
    assert mm % bm == 0 and nn % bn == 0 and kk % bk == 0, (
        (mm, kk, nn), (bm, bk, bn),
    )
    nk = kk // bk

    meta = jnp.zeros((1, 8), jnp.float32)
    meta = meta.at[0, 0].set(jnp.float32(sa))
    meta = meta.at[0, 1].set(jnp.float32(sw))
    meta = meta.at[0, 2].set(jnp.float32(za))
    meta = meta.at[0, 3].set(jnp.float32(zw))

    kernel = functools.partial(_kernel, mode=mode, m=m, use_cv=use_cv,
                               nk=nk, bk=bk, k=k)
    grid = (mm // bm, nn // bn, nk)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, 8), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mm, nn), jnp.float32),
        scratch_shapes=_scratch(bm, bn),
        compiler_params=_compiler_params(nk),
        interpret=interpret,
    )(
        a_q,
        w_q,
        c.reshape(1, nn).astype(jnp.float32),
        c0.reshape(1, nn).astype(jnp.float32),
        sum_qw.reshape(1, nn).astype(jnp.int32),
        bias.reshape(1, nn).astype(jnp.float32),
        meta,
    )


# ---------------------------------------------------------------------------
# Fused serving kernel: quantize-in-kernel over the offline-blocked layout
# ---------------------------------------------------------------------------
#
# One launch computes  float x -> quantize -> bit-slice AM matmuls ->
# MAC* statistics -> CV + zero-point epilogue -> output dtype cast.  The
# static operands arrive pre-blocked (repro.quant.BlockedPack): weight codes
# padded to tile multiples offline and all per-column epilogue operands in
# one aligned (EPI_ROWS, Nb) table — the forward pass does no padding of
# static parameters and no meta assembly.  Per-COLUMN weight quant params
# (epilogue rows EPI_SW / EPI_ZW) make the same kernel serve fan-out-fused
# multi-projection packs (Q|K|V, gate|up): activations are quantized once
# and sumx/sumqa are computed once for every fused output column.


def _fused_kernel(
    # inputs
    x_ref,  # (bm, bk) float activations
    w_ref,  # (bk, bn) uint8 codes (zero-padded offline)
    epi_ref,  # (EPI_ROWS, bn) f32 epilogue table
    meta_ref,  # (1, META_LEN) f32 scalars
    # outputs
    out_ref,  # (bm, bn) out_dtype
    # scratch
    acc_ref,  # (bm, bn) i32
    row_ref,  # (bm, 1) i32
    sumx_ref,  # (bm, 1) i32
    sumqa_ref,  # (bm, 1) i32
    *,
    mode: Mode,
    m: int,
    use_cv: bool,
    nk: int,
    bk: int,
    k: int,
):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        for ref in (acc_ref, row_ref, sumx_ref, sumqa_ref):
            ref[...] = jnp.zeros_like(ref)

    sa = meta_ref[0, META_SA]
    za = meta_ref[0, META_ZA]

    # quantize in-kernel (identical arithmetic to quant.quantize_i32), then
    # zero the K-padding columns: padded float zeros would quantize to the
    # zero-point code, which must not reach acc/sumx/sumqa
    x = x_ref[...].astype(jnp.float32)
    a = jnp.clip(jnp.round(x / sa) + za, 0.0, 255.0).astype(jnp.int32)
    if k < nk * bk:
        kcol = k_step * bk + jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
        a = jnp.where(kcol < k, a, 0)
    _accumulate_tile(a, w_ref[...].astype(jnp.int32),
                     acc_ref, row_ref, sumx_ref, sumqa_ref,
                     mode=mode, m=m, use_cv=use_cv)

    @pl.when(k_step == nk - 1)
    def _epi():
        epi = epi_ref[...]
        row = lambda r: epi[r : r + 1, :]
        out = _epilogue(
            acc_ref, row_ref, sumx_ref, sumqa_ref,
            c=row(EPI_C), c0=row(EPI_C0),
            sum_qw=row(EPI_SUM_QW).astype(jnp.int32), bias=row(EPI_BIAS),
            scale=sa * row(EPI_SW), za=za.astype(jnp.int32),
            zw=row(EPI_ZW).astype(jnp.int32),
            k=k, k_total=nk * bk, mode=mode, m=m, use_cv=use_cv)
        out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "mode", "m", "use_cv", "k", "bm", "bn", "bk", "out_dtype",
        "interpret",
    ),
)
def approx_matmul_cv_fused(
    x: jax.Array,  # (M, Kb) float activations (M/K pre-padded to blocks)
    w_qb: jax.Array,  # (Kb, Nb) uint8 codes, blocked offline
    epilogue: jax.Array,  # (EPI_ROWS, Nb) f32
    meta: jax.Array,  # (1, META_LEN) f32
    *,
    mode: Mode,
    m: int,
    k: int,
    use_cv: bool = True,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Fused float->float approximate matmul; returns ``out_dtype`` (M, Nb).

    ``k`` is the true fan-in: activation columns at or past it are padding.
    """
    mm, kk = x.shape
    kk2, nn = w_qb.shape
    assert kk == kk2, (x.shape, w_qb.shape)
    assert mm % bm == 0 and nn % bn == 0 and kk % bk == 0, (
        (mm, kk, nn), (bm, bk, bn),
    )
    assert epilogue.shape == (EPI_ROWS, nn), epilogue.shape
    nk = kk // bk

    kernel = functools.partial(
        _fused_kernel, mode=mode, m=m, use_cv=use_cv, nk=nk, bk=bk, k=k
    )
    grid = (mm // bm, nn // bn, nk)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((EPI_ROWS, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, META_LEN), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mm, nn), out_dtype),
        scratch_shapes=_scratch(bm, bn),
        compiler_params=_compiler_params(nk),
        interpret=interpret,
    )(x, w_qb, epilogue, meta)
