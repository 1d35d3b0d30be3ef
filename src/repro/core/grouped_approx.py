"""The paper's technique for MoE expert GEMMs: grouped (ragged) approximate
matmuls with PER-EXPERT quantization scales and control-variate constants.

`pack_params` on a stacked (E, k, n) expert weight leaf already produces
per-expert codes/scales/CV constants (vmapped pack).  This module executes
the expert-sorted token buffer against them:

    rows sorted by expert, group_sizes (E,)
    -> per-row expert id -> per-row activation scale/zero-point
    -> bit-slice approximate ragged_dot (exact int32 algebra, same
       identities as core.multipliers)
    -> rank-1 CV correction with the ROW'S OWN expert's (C, C0)
    -> exact per-row zero-point corrections

This is the `_expert_ffn_sorted` fast path used by repro.nn.moe when the
expert stacks are packed (approximate serving of MoE architectures —
DESIGN.md §Arch-applicability's "per-expert CV constants").
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import control_variate as cvlib
from repro.core import multipliers as am
from repro.core.approx_linear import QuantizedDense


def _row_expert_ids(group_sizes: jax.Array, m_rows: int) -> jax.Array:
    """group_sizes (E,) -> (M,) expert id per sorted row."""
    e = group_sizes.shape[0]
    return jnp.repeat(jnp.arange(e), group_sizes, total_repeat_length=m_rows)


def _ragged_int_dot(a, w, group_sizes) -> jax.Array:
    """Exact grouped integer matmul of codes in [0, 255]:
    (M, k) x (E, k, n) -> (M, n) int32; rows past the groups are 0.

    The operands go in as signed bytes, ``code - 128``, the form the TPU's
    grouped matmul takes (with int32 operands XLA computes every row
    against every expert).  The shift's terms are put back exactly:
    ``sum a w = sum a'w' + 128 (sum a' + sum w') + 128^2 k``.
    """
    m_rows, k = a.shape
    a_s = a.astype(jnp.int32) - 128
    w_s = jnp.asarray(w, jnp.int32) - 128
    acc = jax.lax.ragged_dot(
        a_s.astype(jnp.int8), w_s.astype(jnp.int8), group_sizes,
        preferred_element_type=jnp.int32)
    col = jnp.sum(w_s, axis=1)  # (E, n): sum over k of w' per expert
    ids = _row_expert_ids(group_sizes, m_rows)
    acc = (acc + 128 * (jnp.sum(a_s, axis=-1, keepdims=True) + col[ids])
           + 128 * 128 * k)
    in_group = jnp.arange(m_rows) < jnp.sum(group_sizes)
    return jnp.where(in_group[:, None], acc, 0)


def _approx_ragged(a_i32, w_q, group_sizes, mode: str, m: int) -> jax.Array:
    """sum_k AM(w, a) via the bit-slice identities, ragged over experts."""
    if mode == "exact" or m == 0:
        return _ragged_int_dot(a_i32, w_q, group_sizes)
    mask = (1 << m) - 1
    if mode == "perforated":
        return _ragged_int_dot(a_i32 - (a_i32 & mask), w_q, group_sizes)
    if mode == "recursive":
        return (_ragged_int_dot(a_i32, w_q, group_sizes)
                - _ragged_int_dot(a_i32 & mask,
                                  jnp.asarray(w_q, jnp.int32) & mask, group_sizes))
    if mode == "truncated":
        acc = _ragged_int_dot(a_i32, w_q, group_sizes)
        planes_a = jnp.concatenate(
            [((a_i32 >> i) & 1) << i for i in range(m)], axis=-1)
        planes_w = jnp.concatenate(
            [jnp.asarray(w_q, jnp.int32) & ((1 << (m - i)) - 1) for i in range(m)],
            axis=1)
        return acc - _ragged_int_dot(planes_a, planes_w, group_sizes)
    raise ValueError(mode)


def grouped_quantized_dense(qd: QuantizedDense, xs: jax.Array,
                            group_sizes: jax.Array) -> jax.Array:
    """Approximate quantized grouped linear.  xs: (M, k) sorted by expert;
    qd.pack leaves are stacked (E, ...).  Returns (M, n) float32."""
    pol = qd.policy
    pack = qd.pack
    m_rows, k = xs.shape
    ids = _row_expert_ids(group_sizes, m_rows)

    # per-row activation quantization with the row's expert's parameters
    a_scale = qd.a_qp.scale[ids][:, None]
    a_zp = qd.a_qp.zero_point[ids][:, None].astype(jnp.float32)
    a_q = jnp.clip(jnp.round(xs.astype(jnp.float32) / a_scale)
                   + a_zp, 0, 255).astype(jnp.int32)

    acc = _approx_ragged(a_q, pack.w_q, group_sizes, pol.mode, pol.m
                         ).astype(jnp.float32)
    if pol.use_cv and pol.mode != "exact" and pol.m > 0:
        sx = cvlib.sum_x(a_q, pol.mode, pol.m, axis=-1).astype(jnp.float32)
        acc = acc + sx[:, None] * pack.c[ids] + pack.c0[ids]

    # exact zero-point corrections (per-row expert constants)
    sum_qa = jnp.sum(a_q, axis=-1, dtype=jnp.int32).astype(jnp.float32)
    zw = pack.w_zp[ids][:, None].astype(jnp.float32)
    acc = (acc - zw * sum_qa[:, None]
           - a_zp * pack.sum_qw[ids].astype(jnp.float32)
           + k * a_zp * zw)
    y = acc * (a_scale * pack.w_scale[ids][:, None])
    if pack.bias is not None:
        y = y + pack.bias[ids]
    return y


def grouped_quantized_swiglu(experts: dict, xs: jax.Array,
                             group_sizes: jax.Array) -> jax.Array:
    """swiglu over packed expert stacks: silu(gate(x)) * up(x) -> down."""
    g = grouped_quantized_dense(experts["gate"], xs, group_sizes)
    u = grouped_quantized_dense(experts["up"], xs, group_sizes)
    h = (jax.nn.silu(g) * u).astype(xs.dtype)
    return grouped_quantized_dense(experts["down"], h, group_sizes).astype(xs.dtype)
