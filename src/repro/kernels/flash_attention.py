"""Blocked online-softmax attention (flash) Pallas TPU kernel.

Forward-only fused attention for the serving paths (prefill is the
attention-bound cell in the roofline table).  Supports causal masking,
sliding windows (hymba), and GQA via head-index mapping — one kernel serves
qwen3/granite/deepseek/olmo/hubert (bidirectional) and hymba (windowed).

Grid: (B*Hq, Tq/bq, Tk/bk), K innermost with VMEM scratch carrying the
running max/denominator/accumulator.  Fully-masked K tiles are skipped with
pl.when so the causal lower triangle costs ~half the FLOPs (same trick as
the TPU flash reference).  ref.py's flash_attention_ref is the oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 256
DEFAULT_BK = 256
NEG_INF = -1e30


def _kernel(
    q_ref,  # (1, bq, d)
    k_ref,  # (1, bk, d)
    v_ref,  # (1, bk, d)
    out_ref,  # (1, bq, d)
    m_ref,  # scratch (bq, 1) f32
    l_ref,  # scratch (bq, 1) f32
    acc_ref,  # scratch (bq, d) f32
    *,
    scale: float,
    causal: bool,
    window: int | None,
    bq: int,
    bk: int,
    tq: int,
    tk: int,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # absolute positions; q rows are aligned to the END of the kv axis
    # (tq == tk for prefill; tq < tk for chunked decode paths)
    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + (tk - tq)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # tile-level skip: fully masked K tiles do no work
    first_q = iq * bq + (tk - tq)
    last_q = first_q + bq - 1
    tile_needed = True
    if causal:
        tile_needed = jnp.asarray(ik * bk <= last_q)
    if window is not None:
        tile_needed = jnp.logical_and(
            tile_needed, jnp.asarray((ik + 1) * bk - 1 > first_q - window)
        )

    @pl.when(tile_needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window is not None:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _epilogue():
        denom = jnp.maximum(l_ref[...], 1e-30)
        out_ref[0] = (acc_ref[...] / denom).astype(out_ref.dtype)


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "bq", "bk", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (B, Hq, Tq, D)
    k: jax.Array,  # (B, Hkv, Tk, D)
    v: jax.Array,  # (B, Hkv, Tk, D)
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    bq: int = DEFAULT_BQ,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    b, hq, tq, d = q.shape
    _, hkv, tk, _ = k.shape
    assert hq % hkv == 0
    rep = hq // hkv
    if scale is None:
        scale = d**-0.5
    bq = min(bq, tq)
    bk = min(bk, tk)
    assert tq % bq == 0 and tk % bk == 0, ((tq, bq), (tk, bk))

    qf = q.reshape(b * hq, tq, d)
    grid = (b * hq, tq // bq, tk // bk)

    def kv_map(h_flat, iq, ik):
        # flat q-head -> (batch, kv-head) for GQA
        return (h_flat // hq) * hkv + (h_flat % hq) // rep, ik, 0

    kf = k.reshape(b * hkv, tk, d)
    vf = v.reshape(b * hkv, tk, d)

    out = pl.pallas_call(
        functools.partial(
            _kernel,
            scale=float(scale),
            causal=causal,
            window=window,
            bq=bq,
            bk=bk,
            tq=tq,
            tk=tk,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, iq, ik: (h, iq, 0)),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, iq, ik: (h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, tq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, hq, tq, d)
