"""Jitted public wrappers around the Pallas kernels.

Handles: arbitrary leading batch dims, padding to block multiples, backend
selection (compiled on TPU, interpret mode on CPU), and the bridge from
the framework's packed-parameter representation (QuantizedDense) to raw
kernel operands.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.multipliers import Mode
from repro.kernels import approx_matmul as _amk


def interpret_mode() -> bool:
    """Whether the Pallas kernels run interpreted on this backend.

    They compile for TPU and run in interpret mode on the CPU backend (the
    correctness path); any other backend is an error, never a silent
    interpreted run.
    """
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on TPU or interpreted on CPU, not on "
        f"{platform!r}")


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


#: Row counts at or below this are decode-shaped (engine slot counts): the
#: block picker specializes to the thinnest M tile and a single K step, so a
#: one-token-per-slot step never pays prefill-sized tiles.  The serving
#: engine's ``EngineConfig.slots`` maps onto M here via ``decode_slots``
#: (tokens are (slots, 1) during continuous decode).
DECODE_M_MAX = 8

#: Largest fully-unrolled K extent a decode step takes in one grid step
#: (a (8, 4096) activation tile + (4096, 128) weight tile stay far under
#: VMEM; a single K step also drops the cross-step accumulator carry).
DECODE_FULL_K_MAX = 4096


def _pick_blocks(mm: int, kk: int, nn: int, bm: int, bn: int, bk: int):
    """Shrink default blocks for small operands (keeps grid >= 1 per axis).

    Decode-shaped calls (mm <= DECODE_M_MAX) additionally widen the K block
    to the whole (padded) contraction when it fits, collapsing the grid's
    K axis to one parallel step.
    """
    from repro.quant.quantize import shrink_block as shrink

    bm_ = shrink(mm, bm, 8)
    bn_ = shrink(nn, bn, 128 if nn >= 128 else 8)
    bk_ = shrink(kk, bk, 128 if kk >= 128 else 8)
    if mm <= DECODE_M_MAX:
        # one K block spanning the whole padded contraction (same padding
        # granularity, merged steps)
        bk_full = -(-kk // bk_) * bk_
        if bk_full <= DECODE_FULL_K_MAX:
            bk_ = bk_full
    return bm_, bn_, bk_


def approx_matmul_cv_op(
    a_q: jax.Array,  # (..., K) uint8 codes
    w_q: jax.Array,  # (K, N) uint8 codes
    c: jax.Array,
    c0: jax.Array,
    sum_qw: jax.Array,
    bias: jax.Array | None,
    sa,
    sw,
    za,
    zw,
    *,
    mode: Mode,
    m: int,
    use_cv: bool = True,
    bm: int = _amk.DEFAULT_BM,
    bn: int = _amk.DEFAULT_BN,
    bk: int = _amk.DEFAULT_BK,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused approx-matmul+CV over arbitrary leading dims; returns f32 (..., N)."""
    if interpret is None:
        interpret = interpret_mode()

    lead = a_q.shape[:-1]
    kk = a_q.shape[-1]
    nn = w_q.shape[-1]
    a2 = a_q.reshape(-1, kk)
    mm = a2.shape[0]

    bm_, bn_, bk_ = _pick_blocks(mm, kk, nn, bm, bn, bk)
    a2 = _pad_to(_pad_to(a2, 0, bm_), 1, bk_)
    w2 = _pad_to(_pad_to(w_q, 0, bk_), 1, bn_)

    # K padding: padded activation and weight codes are 0, every AM is 0 on
    # zero codes and x(0) = 0, so acc/sumx/sumqa/sum_qw are unaffected; the
    # kernel takes the true k for its k*za*zw term
    cN = _pad_to(jnp.asarray(c, jnp.float32), 0, bn_)
    c0N = _pad_to(jnp.asarray(c0, jnp.float32), 0, bn_)
    sqwN = _pad_to(jnp.asarray(sum_qw, jnp.int32), 0, bn_)
    biasN = (
        _pad_to(jnp.asarray(bias, jnp.float32), 0, bn_)
        if bias is not None
        else jnp.zeros((w2.shape[1],), jnp.float32)
    )

    out = _amk.approx_matmul_cv(
        a2,
        w2,
        cN,
        c0N,
        sqwN,
        biasN,
        jnp.float32(sa),
        jnp.float32(sw),
        jnp.float32(za),
        jnp.float32(zw),
        mode=mode,
        m=m,
        k=kk,
        use_cv=use_cv,
        bm=bm_,
        bn=bn_,
        bk=bk_,
        interpret=interpret,
    )
    return out[:mm, :nn].reshape(*lead, nn)


def quantized_dense_fused_op(
    x: jax.Array,  # (..., k) FLOAT activations
    blocked,  # repro.quant.BlockedPack
    *,
    mode: Mode,
    m: int,
    use_cv: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Zero-overhead serving path: float activations against an
    offline-blocked pack, one kernel launch (quantize + matmul + epilogue).

    Only the activations are padded here (M to the picked tile, K from the
    true fan-in to the pack's blocked extent); every static operand was laid
    out at pack time.  Returns ``x.dtype`` (..., n).
    """
    if interpret is None:
        interpret = interpret_mode()

    lead = x.shape[:-1]
    kk = x.shape[-1]
    assert kk == blocked.k, (x.shape, blocked.k)
    kb, nb = blocked.w_qb.shape
    x2 = x.reshape(-1, kk)
    mm = x2.shape[0]

    bm_, _, bk_ = _pick_blocks(mm, kb, nb, _amk.DEFAULT_BM, blocked.bn,
                               blocked.bk)
    # K blocks must tile the offline layout exactly: fall back to the pack
    # granularity unless the decode merge consumed all of Kb
    if kb % bk_ != 0:
        bk_ = blocked.bk
    x2 = _pad_to(_pad_to(x2, 0, bm_), 1, kb)

    out = _amk.approx_matmul_cv_fused(
        x2,
        blocked.w_qb,
        blocked.epilogue,
        blocked.meta,
        mode=mode,
        m=m,
        use_cv=use_cv,
        k=blocked.k,
        bm=bm_,
        bn=blocked.bn,
        bk=bk_,
        out_dtype=x.dtype,
        interpret=interpret,
    )
    return out[:mm, : blocked.n].reshape(*lead, blocked.n)


def quantized_dense_pallas(x: jax.Array, qd) -> jax.Array:
    """Bridge: packed params (QuantizedDense or a fan-out-fused
    QuantizedDenseGroup) + float activations -> fused kernel.

    Packs carrying the offline-blocked serving layout take the
    float-in/float-out fused kernel (quantize-in-kernel, no per-call padding
    of static operands); legacy packs quantize here and run the original
    kernel with per-call padding.
    """
    from repro.quant.quantize import quantize

    pol = qd.policy
    if qd.blocked is not None:
        return quantized_dense_fused_op(
            x, qd.blocked, mode=pol.mode, m=pol.m, use_cv=pol.use_cv)
    a_q = quantize(x, qd.a_qp)
    pack = qd.pack
    bias = pack.bias
    return approx_matmul_cv_op(
        a_q,
        pack.w_q,
        pack.c,
        pack.c0,
        pack.sum_qw,
        bias,
        qd.a_qp.scale,
        pack.w_scale,
        qd.a_qp.zero_point,
        pack.w_zp,
        mode=pol.mode,
        m=pol.m,
        use_cv=pol.use_cv,
    )
