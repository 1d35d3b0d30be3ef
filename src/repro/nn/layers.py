"""Common layers: norms, embeddings, MLPs, rotary embeddings (RoPE + M-RoPE).

Parameter convention: plain nested dicts of arrays; ``init_*`` builds them,
``*_apply``-style pure functions consume them.  Linear leaves are
``{"w": (k, n)[, "b": (n,)]}`` so :func:`repro.core.approx_linear.pack_params`
can swap them for approximate packed versions.
"""

from __future__ import annotations

import dataclasses
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core.approx_linear import dense, init_dense


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_rmsnorm(d: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * p["scale"]).astype(dt)


def init_layernorm(d: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p: dict | None, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """Parametric LN, or non-parametric (olmo-style) when ``p`` is None."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    if p is not None:
        x = x * p["scale"] + p["bias"]
    return x.astype(dt)


def init_norm(kind: str, d: int, dtype=jnp.float32):
    if kind == "rmsnorm":
        return init_rmsnorm(d, dtype)
    if kind == "layernorm":
        return init_layernorm(d, dtype)
    if kind == "nonparametric_ln":
        return {}  # no params
    raise ValueError(kind)


def apply_norm(kind: str, p, x: jax.Array) -> jax.Array:
    if kind == "rmsnorm":
        return rmsnorm(p, x)
    if kind == "layernorm":
        return layernorm(p, x)
    if kind == "nonparametric_ln":
        return layernorm(None, x)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Embeddings / LM head
# ---------------------------------------------------------------------------


def init_embedding(key, vocab: int, d: int, dtype=jnp.float32) -> dict:
    return {"table": (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)}


def embed(p: dict, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["table"], tokens, axis=0)


def unembed(p: dict, x: jax.Array) -> jax.Array:
    """Logits head; accepts an embedding table (tied) or a linear leaf."""
    if "table" in p:
        return jnp.matmul(x, p["table"].T)
    return dense(p, x, name="lm_head")


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_swiglu(key, d: int, ff: int, dtype=jnp.float32) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": init_dense(k1, d, ff, bias=False, dtype=dtype),
        "up": init_dense(k2, d, ff, bias=False, dtype=dtype),
        "down": init_dense(k3, ff, d, bias=False, dtype=dtype),
    }


def swiglu(p: dict, x: jax.Array) -> jax.Array:
    with jax.named_scope("mlp_in"):
        if "gateup" in p:  # fan-out-fused serving pack: one wide-N call
            from repro.core.approx_linear import dense_group

            gu = dense_group(p["gateup"], x)
            g, u = gu["gate"], gu["up"]
        else:
            g = dense(p["gate"], x, name="gate")
            u = dense(p["up"], x, name="up")
        h = jax.nn.silu(g) * u
    with jax.named_scope("mlp_out"):
        return dense(p["down"], h, name="down")


def init_gelu_mlp(key, d: int, ff: int, dtype=jnp.float32) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "up": init_dense(k1, d, ff, bias=True, dtype=dtype),
        "down": init_dense(k2, ff, d, bias=True, dtype=dtype),
    }


def gelu_mlp(p: dict, x: jax.Array) -> jax.Array:
    with jax.named_scope("mlp_in"):
        h = jax.nn.gelu(dense(p["up"], x, name="up"))
    with jax.named_scope("mlp_out"):
        return dense(p["down"], h, name="down")


# ---------------------------------------------------------------------------
# Rotary embeddings (RoPE) and multimodal M-RoPE (qwen2-vl)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float = 10000.0) -> jax.Array:
    """Inverse frequencies, shape (head_dim // 2,)."""
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def rope_angles(positions: jax.Array, head_dim: int, theta: float = 10000.0):
    """cos/sin tables for integer positions (..., T) -> (..., T, head_dim//2)."""
    ang = positions[..., None].astype(jnp.float32) * rope_freqs(head_dim, theta)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, T, H, D) with cos/sin (B, T, D//2) (head-broadcast).

    Rotate-half convention (llama-style: split halves, not interleaved).
    """
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def mrope_angles(
    positions_3d: jax.Array,  # (3, B, T): temporal / height / width ids
    head_dim: int,
    sections: tuple[int, int, int],
    theta: float = 10000.0,
):
    """qwen2-vl M-RoPE: the head_dim//2 frequency slots are partitioned into
    (temporal, height, width) sections, each driven by its own position id.
    For pure text the three ids coincide and M-RoPE reduces to RoPE.
    Returns cos/sin of shape (B, T, head_dim//2).
    """
    assert sum(sections) == head_dim // 2, (sections, head_dim)
    freqs = rope_freqs(head_dim, theta)  # (d2,)
    splits = [0]
    for s in sections:
        splits.append(splits[-1] + s)
    parts_cos, parts_sin = [], []
    for i in range(3):
        f = freqs[splits[i] : splits[i + 1]]
        ang = positions_3d[i][..., None].astype(jnp.float32) * f
        parts_cos.append(jnp.cos(ang))
        parts_sin.append(jnp.sin(ang))
    return jnp.concatenate(parts_cos, -1), jnp.concatenate(parts_sin, -1)
