"""Pieces every family's plain float32 reference is built from.

Written from the published descriptions in straightforward ``jax.numpy``:
the serving's fake quantization for the control, a dense product, OLMo's
non-parametric LayerNorm and RMSNorm, rotary embeddings in the
rotate-half convention, grouped-query causal attention over blocks of
``Q_BLOCK`` query rows (so that a long sequence fits beside the weights),
and the readings taken from a sequence's logits.  A family runs its
forward under ``jax.default_matmul_precision(PRECISION)``, so on a TPU a
float32 matmul is not silently computed in bfloat16.

``bits`` turns a reference into the control: the weights (per tensor,
min-max) and inputs (over the serving's static activation range) of every
layer the serving packs are fake-quantized to ``bits``-bit affine codes;
what is served in float stays float.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: the matmul precision every reference runs under
PRECISION = "highest"
#: query rows per attention block
Q_BLOCK = 256
#: the activation range the serving packs quantize over when uncalibrated
ACT_RANGE = (-8.0, 8.0)


def fake_quant(x, lo, hi, bits: int):
    """Affine ``bits``-bit quantize-dequantize over ``[lo, hi]`` (0 kept
    exact, as the serving packs do)."""
    levels = 2 ** bits - 1
    lo = jnp.minimum(lo, 0.0)
    hi = jnp.maximum(hi, 0.0)
    scale = jnp.maximum((hi - lo) / levels, 1e-12)
    zp = jnp.clip(jnp.round(-lo / scale), 0, levels)
    q = jnp.clip(jnp.round(x / scale) + zp, 0, levels)
    return (q - zp) * scale


def dense(x, w, bits):
    """``x @ w``, a packed layer: fake-quantized to ``bits`` when given."""
    if bits:
        x = fake_quant(x, ACT_RANGE[0], ACT_RANGE[1], bits)
        w = fake_quant(w, jnp.min(w), jnp.max(w), bits)
    return x @ w


def norm(x, scale, kind: str):
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
            * scale
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5)


def rope(x, pos, theta: float):
    """x: (T, H, hd), rotate-half convention."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv  # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal GQA.  q: (T, H, hd); k, v: (T, Hkv, hd).  Query rows in
    blocks of ``Q_BLOCK``."""
    t, h, hd = q.shape
    hkv = k.shape[1]
    qg = q.reshape(t // Q_BLOCK, Q_BLOCK, hkv, h // hkv, hd)
    kpos = jnp.arange(t)

    def block(inp):
        qb, i = inp
        s = jnp.einsum("qhgd,khd->hgqk", qb, k) * hd ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        return jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, -1), v)

    ctx = jax.lax.map(block, (qg, jnp.arange(t // Q_BLOCK)))
    return ctx.reshape(t, h * hd)


def picked(logits, picks):
    """What a family's ``readings`` returns for one sequence's logits
    (T, vocab): at every position the largest logit, the logit of each row
    of ``picks`` (n, T), and the token ranked first."""
    at = jnp.take_along_axis(logits[None], picks[..., None], -1)[..., 0]
    return jnp.max(logits, -1), at, jnp.argmax(logits, -1)
