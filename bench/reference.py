"""The plain reference: a float32 forward of a dense GQA language model.

Written from the published description in straightforward ``jax.numpy``
and imported from nothing of the program: pre-norm blocks (OLMo's
non-parametric LayerNorm or RMSNorm), rotary embeddings in the rotate-half
convention, grouped-query causal attention, a SwiGLU MLP, and a head tied
to the embedding.  Every product runs under
``jax.default_matmul_precision("highest")``, so on a TPU a float32 matmul
is not silently computed in bfloat16.  It is computed layer by layer (a
scan over the stacked layers) and attention in blocks of query rows, so
that a long sequence fits beside the weights.

``bits`` turns the reference into the control: every dense layer's
weights (per tensor, min-max) and inputs (over the serving's static
activation range) are fake-quantized to ``bits``-bit affine codes, the
layers that the serving packs; the embedding and the tied head stay float,
as they are served.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: query rows per attention block
Q_BLOCK = 256
#: the activation range the serving packs quantize over when uncalibrated
ACT_RANGE = (-8.0, 8.0)


def _fake_quant(x, lo, hi, bits: int):
    """Affine ``bits``-bit quantize-dequantize over ``[lo, hi]`` (0 kept
    exact, as the serving packs do)."""
    levels = 2 ** bits - 1
    lo = jnp.minimum(lo, 0.0)
    hi = jnp.maximum(hi, 0.0)
    scale = jnp.maximum((hi - lo) / levels, 1e-12)
    zp = jnp.clip(jnp.round(-lo / scale), 0, levels)
    q = jnp.clip(jnp.round(x / scale) + zp, 0, levels)
    return (q - zp) * scale


def _dense(x, w, bits):
    if bits:
        x = _fake_quant(x, ACT_RANGE[0], ACT_RANGE[1], bits)
        w = _fake_quant(w, jnp.min(w), jnp.max(w), bits)
    return x @ w


def _norm(x, scale, kind: str):
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
            * scale
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5)


def _rope(x, pos, theta: float):
    """x: (T, H, hd), rotate-half convention."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv  # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
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


def _layer(x, lw, model: dict, bits):
    t = x.shape[0]
    h, hkv, hd = model["n_heads"], model["kv_heads"], model["head_dim"]
    pos = jnp.arange(t)
    a_in = _norm(x, lw["attn_norm"].get("scale"), model["norm"])
    q = _dense(a_in, lw["attn"]["q"]["w"], bits).reshape(t, h, hd)
    k = _dense(a_in, lw["attn"]["k"]["w"], bits).reshape(t, hkv, hd)
    v = _dense(a_in, lw["attn"]["v"]["w"], bits).reshape(t, hkv, hd)
    q = _rope(q, pos, model["rope_theta"])
    k = _rope(k, pos, model["rope_theta"])
    x = x + _dense(_attention(q, k, v), lw["attn"]["o"]["w"], bits)
    m_in = _norm(x, lw["mlp_norm"].get("scale"), model["norm"])
    g = _dense(m_in, lw["mlp"]["gate"]["w"], bits)
    u = _dense(m_in, lw["mlp"]["up"]["w"], bits)
    return x + _dense(jax.nn.silu(g) * u, lw["mlp"]["down"]["w"], bits)


def _logits(weights, tokens, model: dict, bits):
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))
    table = weights["embed"]["table"].astype(jnp.float32)
    x = table[tokens]

    def body(x, lw):
        return _layer(x, f32(lw), model, bits), None

    x, _ = jax.lax.scan(body, x, weights["blocks"])
    x = _norm(x, f32(weights["final_norm"]).get("scale"), model["norm"])
    return x @ table.T


@functools.partial(jax.jit, static_argnames=("model_items", "bits"))
def _readings(weights, tokens, picks, model_items, bits):
    with jax.default_matmul_precision("highest"):
        logits = _logits(weights, tokens, dict(model_items), bits)
    at = jnp.take_along_axis(logits[None], picks[..., None], -1)[..., 0]
    return jnp.max(logits, -1), at, jnp.argmax(logits, -1)


_MODEL_KEYS = ("n_heads", "kv_heads", "head_dim", "norm", "rope_theta")


def readings(weights, tokens, picks, model: dict, bits: int | None = None):
    """For one token sequence (length a multiple of ``Q_BLOCK``): at every
    position, the largest logit, the logit of each row of ``picks``
    (shape (n, T): token ids to read at each position), and the token
    ranked first.  Only these leave the device.  ``bits`` makes it the
    control."""
    return _readings(weights, jnp.asarray(tokens, jnp.int32),
                     jnp.asarray(picks, jnp.int32),
                     tuple((k, model[k]) for k in _MODEL_KEYS), bits)


@functools.partial(jax.jit, static_argnames=("model_items", "bits"))
def _full(weights, tokens, model_items, bits):
    with jax.default_matmul_precision("highest"):
        return _logits(weights, tokens, dict(model_items), bits)


def forward(weights, tokens, model: dict, bits: int | None = None):
    """All logits (T, vocab), float32 (for the tests at small sizes)."""
    return _full(weights, jnp.asarray(tokens, jnp.int32),
                 tuple((k, model[k]) for k in _MODEL_KEYS), bits)
