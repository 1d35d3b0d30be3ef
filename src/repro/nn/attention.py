"""Attention variants for the assigned architectures.

  * GQA multi-head attention with optional qk-norm (qwen3), sliding window
    (hymba), bidirectional mode (hubert), RoPE / M-RoPE (qwen2-vl) or no
    positional encoding.
  * MLA — DeepSeek-V2 multi-head latent attention (kv_lora compression),
    with decompressed prefill and weight-absorbed decode over the latent
    cache.

Both expose ``prefill`` (full-sequence, also the training forward) and
``decode_step`` (single token against a cache).  Caches are dicts of arrays
so they shard/checkpoint like any other pytree:

  GQA cache: {"k": (B, Hkv, S, hd), "v": (B, Hkv, S, hd), "pos": i32[]}
  MLA cache: {"latent": (B, S, r), "rope": (B, S, dr), "pos": i32[]}

QKV/O projections are `dense` leaves (approximable); score/softmax/context
math is exact vector-unit work, matching the paper's array/non-array split.
"""

from __future__ import annotations

import dataclasses
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core.approx_linear import dense, dense_group, init_dense
from repro.nn.layers import (
    apply_rope,
    init_rmsnorm,
    mrope_angles,
    rmsnorm,
    rope_angles,
)
from repro.quant import observers


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    kv_heads: int
    head_dim: int
    causal: bool = True
    qk_norm: bool = False
    window: int | None = None  # sliding-window size (hymba)
    rope: Literal["rope", "mrope", "none"] = "rope"
    rope_theta: float = 10000.0
    mrope_sections: tuple[int, int, int] = (16, 24, 24)
    qkv_bias: bool = False  # qwen2-vl uses bias on qkv

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim


def init_attention(key, cfg: AttnConfig, dtype=jnp.float32) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "q": init_dense(kq, cfg.d_model, cfg.q_dim, bias=cfg.qkv_bias, dtype=dtype),
        "k": init_dense(kk, cfg.d_model, cfg.kv_dim, bias=cfg.qkv_bias, dtype=dtype),
        "v": init_dense(kv, cfg.d_model, cfg.kv_dim, bias=cfg.qkv_bias, dtype=dtype),
        "o": init_dense(ko, cfg.q_dim, cfg.d_model, bias=False, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(cfg.head_dim, dtype)
        p["k_norm"] = init_rmsnorm(cfg.head_dim, dtype)
    return p


def _angles(cfg: AttnConfig, positions: jax.Array):
    """positions: (B, T) int32, or (3, B, T) for mrope."""
    if cfg.rope == "none":
        return None
    if cfg.rope == "mrope":
        if positions.ndim == 2:  # text-only: broadcast the same ids
            positions = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        return mrope_angles(positions, cfg.head_dim, cfg.mrope_sections, cfg.rope_theta)
    return rope_angles(positions, cfg.head_dim, cfg.rope_theta)


def _project_qkv(p: dict, x: jax.Array, cfg: AttnConfig, angles):
    b, t, _ = x.shape
    if "qkv" in p:  # fan-out-fused serving pack: one wide-N projection call
        qkv = dense_group(p["qkv"], x)
        q, k, v = qkv["q"], qkv["k"], qkv["v"]
    else:
        q = dense(p["q"], x, name="q")
        k = dense(p["k"], x, name="k")
        v = dense(p["v"], x, name="v")
    q = q.reshape(b, t, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, t, cfg.kv_heads, cfg.head_dim)
    v = v.reshape(b, t, cfg.kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q)
        k = rmsnorm(p["k_norm"], k)
    if angles is not None:
        cos, sin = angles
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def _sdpa(
    q: jax.Array,  # (B, Tq, Hq, d)
    k: jax.Array,  # (B, Tk, Hkv, d)
    v: jax.Array,  # (B, Tk, Hkv, d)
    *,
    causal: bool,
    window: int | None,
    kv_valid_len: jax.Array | None = None,
) -> jax.Array:
    """Grouped-head attention without materializing repeated KV heads.

    Query rows are aligned to the END of the key axis (training: Tq == Tk;
    decode: Tq == 1 with ``kv_valid_len`` marking the filled cache length).
    """
    b, tq, hq, d = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, tq, hkv, g, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) * (d**-0.5)

    end = kv_valid_len if kv_valid_len is not None else jnp.int32(tk)
    q_pos = jnp.arange(tq)[:, None] + (end - tq)
    k_pos = jnp.arange(tk)[None, :]
    mask = k_pos < end  # only filled cache slots
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window is not None:
        mask = mask & (k_pos > q_pos - window)
    logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    ctx = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return ctx.reshape(b, tq, hq, d)


def attention_prefill(
    p: dict,
    x: jax.Array,  # (B, T, D)
    cfg: AttnConfig,
    positions: jax.Array | None = None,
) -> jax.Array:
    b, t, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    q, k, v = _project_qkv(p, x, cfg, _angles(cfg, positions))
    ctx = _sdpa(q, k, v, causal=cfg.causal, window=cfg.window)
    return dense(p["o"], ctx.reshape(b, t, cfg.q_dim), name="o")


def init_kv_cache(cfg: AttnConfig, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
    return {
        "k": jnp.zeros((batch, cfg.kv_heads, max_len, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, cfg.kv_heads, max_len, cfg.head_dim), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


#: fixed-point scale for int8 KV caches (values are O(1) after qk-norm /
#: rope; 1/16 resolution keeps decode logits within ~1e-2 of bf16 — the
#: int8-cache serving mode halves decode cache traffic, §Perf)
KV_INT8_SCALE = 16.0


def _to_cache(x: jax.Array, dtype) -> jax.Array:
    if dtype == jnp.int8:
        return jnp.clip(jnp.round(x * KV_INT8_SCALE), -127, 127).astype(jnp.int8)
    return x.astype(dtype)


def _from_cache(x: jax.Array, dtype) -> jax.Array:
    if x.dtype == jnp.int8:
        return x.astype(dtype) * (1.0 / KV_INT8_SCALE)
    return x.astype(dtype)


def attention_decode_step(
    p: dict,
    x: jax.Array,  # (B, 1, D)
    cache: dict,
    cfg: AttnConfig,
) -> tuple[jax.Array, dict]:
    """One-token decode against the (B, Hkv, S, d) cache (bf16 or int8).

    The score/context einsums consume the cache LAYOUT DIRECTLY — an earlier
    version transposed the full cache to (B, S, H, d) per layer per token,
    which materialized ~77 GB/step of pure layout traffic on the decode_32k
    cells (EXPERIMENTS.md §Perf, qwen3 decode iteration 1)."""
    b = x.shape[0]
    pos = cache["pos"]
    positions = jnp.broadcast_to(pos[None, None], (b, 1)).astype(jnp.int32)
    q, k, v = _project_qkv(p, x, cfg, _angles(cfg, positions))
    # cache layout (B, Hkv, S, d); new k/v: (B, 1, Hkv, d)
    k_c = jax.lax.dynamic_update_slice(
        cache["k"], _to_cache(jnp.moveaxis(k, 1, 2), cache["k"].dtype), (0, 0, pos, 0)
    )
    v_c = jax.lax.dynamic_update_slice(
        cache["v"], _to_cache(jnp.moveaxis(v, 1, 2), cache["v"].dtype), (0, 0, pos, 0)
    )

    hq, hkv, d = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    g = hq // hkv
    qg = q.reshape(b, 1, hkv, g, d)
    logits = jnp.einsum(
        "bqhgd,bhkd->bhgqk", qg, _from_cache(k_c, q.dtype)) * (d**-0.5)
    mask = jnp.arange(k_c.shape[2]) < (pos + 1)
    if cfg.window is not None:
        mask = mask & (jnp.arange(k_c.shape[2]) > pos - cfg.window)
    logits = jnp.where(mask[None, None, None, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(q.dtype)
    ctx = jnp.einsum("bhgqk,bhkd->bqhgd", probs, _from_cache(v_c, q.dtype))
    y = dense(p["o"], ctx.reshape(b, 1, cfg.q_dim), name="o")
    return y, {"k": k_c, "v": v_c, "pos": pos + 1}


def attention_decode_ring(
    p: dict,
    x: jax.Array,  # (B, 1, D)
    cache: dict,  # k/v: (B, Hkv, W, d) ring buffers
    cfg: AttnConfig,
) -> tuple[jax.Array, dict]:
    """Sliding-window decode against a RING cache of length W.

    Invariant: absolute position a lives at slot a mod W.  The window mask
    is implicit — the ring only ever holds the last W positions; slots not
    yet written (pos < W) are masked via the recovered absolute position
    abs_j = pos - ((pos - j) mod W) >= 0.
    """
    b = x.shape[0]
    pos = cache["pos"]
    w_len = cache["k"].shape[2]
    positions = jnp.broadcast_to(pos[None, None], (b, 1)).astype(jnp.int32)
    q, k, v = _project_qkv(p, x, cfg, _angles(cfg, positions))
    slot = pos % w_len
    k_c = jax.lax.dynamic_update_slice(
        cache["k"], jnp.moveaxis(k, 1, 2).astype(cache["k"].dtype), (0, 0, slot, 0)
    )
    v_c = jax.lax.dynamic_update_slice(
        cache["v"], jnp.moveaxis(v, 1, 2).astype(cache["v"].dtype), (0, 0, slot, 0)
    )

    hq, hkv, d = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    g = hq // hkv
    qg = q.reshape(b, 1, hkv, g, d)
    kk = jnp.moveaxis(k_c, 1, 2).astype(q.dtype)  # (B, W, Hkv, d)
    vv = jnp.moveaxis(v_c, 1, 2).astype(q.dtype)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kk) * (d**-0.5)
    j = jnp.arange(w_len)
    abs_j = pos - ((pos - j) % w_len)
    mask = abs_j >= 0
    logits = jnp.where(mask[None, None, None, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(q.dtype)
    ctx = jnp.einsum("bhgqk,bkhd->bqhgd", probs, vv).reshape(b, 1, hq * d)
    y = dense(p["o"], ctx, name="o")
    return y, {"k": k_c, "v": v_c, "pos": pos + 1}


# ---------------------------------------------------------------------------
# MLA — DeepSeek-V2 multi-head latent attention
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    d_model: int
    n_heads: int
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    #: YaRN (:class:`repro.configs.base.YaRN`): its rope frequencies and
    #: its softmax factor; None: plain rope
    rope_scaling: object = None

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def softmax_scale(self) -> float:
        """``qk_head_dim**-0.5``, times YaRN's ``m(factor,
        mscale_all_dim)**2`` when it scales the rope."""
        scale = self.qk_head_dim**-0.5
        if self.rope_scaling is not None:
            scale *= self.rope_scaling.softmax_factor
        return scale

    def rope_angles(self, positions: jax.Array):
        return rope_angles(positions, self.qk_rope_dim, self.rope_theta,
                           self.rope_scaling)


def init_mla(key, cfg: MLAConfig, dtype=jnp.float32) -> dict:
    kq, ka, kb, ko = jax.random.split(key, 4)
    h = cfg.n_heads
    return {
        "q": init_dense(kq, cfg.d_model, h * cfg.qk_head_dim, bias=False, dtype=dtype),
        "kv_a": init_dense(
            kq, cfg.d_model, cfg.kv_lora_rank + cfg.qk_rope_dim, bias=False, dtype=dtype
        ),
        "kv_a_norm": init_rmsnorm(cfg.kv_lora_rank, dtype),
        # kv_b stays float (absorbed-decode einsums need the raw matrix; see
        # DESIGN.md Arch-applicability) — policy functions skip "kv_b".
        "kv_b": init_dense(
            kb,
            cfg.kv_lora_rank,
            h * (cfg.qk_nope_dim + cfg.v_head_dim),
            bias=False,
            dtype=dtype,
        ),
        "o": init_dense(ko, h * cfg.v_head_dim, cfg.d_model, bias=False, dtype=dtype),
    }


def _mla_q(p, x, cfg: MLAConfig, positions):
    b, t, _ = x.shape
    q = dense(p["q"], x, name="q").reshape(b, t, cfg.n_heads, cfg.qk_head_dim)
    q_nope, q_rope = q[..., : cfg.qk_nope_dim], q[..., cfg.qk_nope_dim :]
    cos, sin = cfg.rope_angles(positions)
    q_rope = apply_rope(q_rope, cos, sin)
    return q_nope, q_rope


def _mla_latent(p, x, cfg: MLAConfig, positions):
    kv_a = dense(p["kv_a"], x, name="kv_a")
    latent = rmsnorm(p["kv_a_norm"], kv_a[..., : cfg.kv_lora_rank])
    k_rope = kv_a[..., cfg.kv_lora_rank :][:, :, None, :]  # (B, T, 1, dr)
    cos, sin = cfg.rope_angles(positions)
    k_rope = apply_rope(k_rope, cos, sin)[:, :, 0, :]  # shared across heads
    return latent, k_rope


def mla_prefill(p, x, cfg: MLAConfig, positions=None) -> jax.Array:
    """Decompressed path: materialize per-head K/V from the latent."""
    b, t, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    latent, k_rope = _mla_latent(p, x, cfg, positions)
    kv = dense(p["kv_b"], latent, name="kv_b").reshape(
        b, t, cfg.n_heads, cfg.qk_nope_dim + cfg.v_head_dim
    )
    k_nope, v = kv[..., : cfg.qk_nope_dim], kv[..., cfg.qk_nope_dim :]

    scale = cfg.softmax_scale
    logits = (
        jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
        + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope)
    ) * scale
    q_pos = jnp.arange(t)[:, None]
    mask = jnp.arange(t)[None, :] <= q_pos
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(x.dtype)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return dense(p["o"], ctx.reshape(b, t, -1), name="o")


def init_mla_cache(cfg: MLAConfig, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
    return {
        "latent": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def mla_decode_step(p, x, cache: dict, cfg: MLAConfig) -> tuple[jax.Array, dict]:
    """Weight-absorbed decode: attention runs entirely in latent space.

    q~ = q_nope @ W_UK  per head (r-dim);  logits = q~ . latent + rope part;
    ctx_latent = probs . latent;  out_head = ctx_latent @ W_UV.
    """
    b = x.shape[0]
    pos = cache["pos"]
    positions = jnp.broadcast_to(pos[None, None], (b, 1)).astype(jnp.int32)
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    latent_t, k_rope_t = _mla_latent(p, x, cfg, positions)

    lat_c = jax.lax.dynamic_update_slice(
        cache["latent"], latent_t.astype(cache["latent"].dtype), (0, pos, 0)
    )
    rope_c = jax.lax.dynamic_update_slice(
        cache["rope"], k_rope_t.astype(cache["rope"].dtype), (0, pos, 0)
    )

    w_b = p["kv_b"]["w"].reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_dim + cfg.v_head_dim
    )
    w_uk, w_uv = w_b[..., : cfg.qk_nope_dim], w_b[..., cfg.qk_nope_dim :]

    q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_uk)  # absorbed q
    scale = cfg.softmax_scale
    lat = lat_c.astype(x.dtype)
    logits = (
        jnp.einsum("bqhr,bkr->bhqk", q_lat, lat)
        + jnp.einsum("bqhd,bkd->bhqk", q_rope, rope_c.astype(x.dtype))
    ) * scale
    mask = jnp.arange(lat_c.shape[1])[None, :] < (pos + 1)
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(x.dtype)
    ctx_lat = jnp.einsum("bhqk,bkr->bqhr", probs, lat)
    ctx = jnp.einsum("bqhr,rhd->bqhd", ctx_lat, w_uv)
    y = dense(p["o"], ctx.reshape(b, 1, -1), name="o")
    return y, {"latent": lat_c, "rope": rope_c, "pos": pos + 1}
