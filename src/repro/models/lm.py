"""Generic transformer LM covering 9 of the 10 assigned architectures.

ArchConfig switches select: GQA vs MLA attention, swiglu/gelu/MoE MLP,
parallel SSM heads (hymba), qk-norm, sliding windows, RoPE/M-RoPE/none,
causal vs bidirectional (hubert), token vs embedding inputs (vlm/audio).

Scale-critical implementation choices (these are what make the 512-chip
dry-run lower/compile):

  * layer stacks are SCANNED: block params are stacked (L, ...) pytrees and
    the forward is one `lax.scan` — HLO size is O(1) in depth (95-layer
    deepseek-67b compiles like a 1-layer model);
  * attention is Q-CHUNKED for long sequences: a scan over query chunks
    bounds the live (chunk, S) score tile instead of materializing the
    (T, S) matrix (32k prefill would otherwise allocate TBs);
  * the LM head + cross-entropy are FUSED AND CHUNKED: logits for a 152k
    vocab are never materialized for the full sequence;
  * prefill is SINGLE-PASS: each block projects K/V once and shares them
    between attention and the decode-cache capture;
  * sliding-window decode uses RING-BUFFER caches of length W (slot of
    absolute position a is a mod W), making long_500k hymba decode state
    O(W), not O(S);
  * remat policy per config ("none" | "full" | "dots") wraps the scanned
    block body.

Caches are plain pytrees stacked over layers: `lax.scan` slices them per
layer in `decode_step`, carries them through the served `decode_slots`
(which writes each step's columns in place), and pjit shards them like
any other state.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.configs.base import ArchConfig
from repro.core.approx_linear import dense
from repro.nn import attention as attn_lib
from repro.nn import moe as moe_lib
from repro.nn import ssm as ssm_lib
from repro.nn.layers import (
    apply_norm,
    embed,
    init_embedding,
    init_gelu_mlp,
    init_norm,
    init_rmsnorm,
    init_swiglu,
    gelu_mlp,
    rmsnorm,
    swiglu,
)
from repro.quant import observers

Params = Any

Q_CHUNK = 1024  # live attention score tile: (B, H, Q_CHUNK, S)
LOSS_CHUNK = 512  # live logits tile: (B, LOSS_CHUNK, V)


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[name]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_block(key, cfg: ArchConfig, dtype) -> dict:
    ks = jax.random.split(key, 4)
    p: dict = {
        "attn_norm": init_norm(cfg.norm, cfg.d_model, dtype),
        "mlp_norm": init_norm(cfg.norm, cfg.d_model, dtype),
    }
    if cfg.attn == "mla":
        p["attn"] = attn_lib.init_mla(ks[0], cfg.mla_config(), dtype)
    elif cfg.attn == "gqa":
        p["attn"] = attn_lib.init_attention(ks[0], cfg.attn_config(), dtype)
    if cfg.parallel_ssm:
        p["ssm"] = ssm_lib.init_ssm(ks[1], cfg.ssm_config(), dtype)
        p["attn_out_norm"] = init_rmsnorm(cfg.d_model, dtype)
        p["ssm_out_norm"] = init_rmsnorm(cfg.d_model, dtype)
    if cfg.mlp == "moe":
        p["mlp"] = moe_lib.init_moe(ks[2], cfg.moe_config(), dtype)
    elif cfg.mlp == "swiglu":
        p["mlp"] = init_swiglu(ks[2], cfg.d_model, cfg.d_ff, dtype)
    else:
        p["mlp"] = init_gelu_mlp(ks[2], cfg.d_model, cfg.d_ff, dtype)
    return p


def init_params(key, cfg: ArchConfig) -> Params:
    dtype = _dtype(cfg.param_dtype)
    k_emb, k_blocks, k_head, k_dense = jax.random.split(key, 4)
    n_scan = cfg.n_layers - cfg.first_dense_layers
    block_keys = jax.random.split(k_blocks, n_scan)
    p: dict = {
        "embed": init_embedding(k_emb, cfg.vocab, cfg.d_model, dtype),
        "blocks": jax.vmap(lambda k: _init_block(k, cfg, dtype))(block_keys),
        "final_norm": init_norm(cfg.norm, cfg.d_model, dtype),
    }
    if cfg.first_dense_layers:
        dense_cfg = dataclasses.replace(cfg, mlp="swiglu")
        p["dense_blocks"] = [
            _init_block(k, dense_cfg, dtype)
            for k in jax.random.split(k_dense, cfg.first_dense_layers)
        ]
    if not cfg.tie_embeddings:
        p["lm_head"] = {
            "w": (
                jax.random.normal(k_head, (cfg.d_model, cfg.vocab)) * cfg.d_model**-0.5
            ).astype(dtype)
        }
    return p


# ---------------------------------------------------------------------------
# attention (full-sequence, q-chunked), with optional cache capture
# ---------------------------------------------------------------------------


def _gqa_full(bp, h, cfg: ArchConfig, positions, want_cache: bool):
    acfg = cfg.attn_config()
    b, t, _ = h.shape
    angles = attn_lib._angles(acfg, positions)
    q, k, v = attn_lib._project_qkv(bp, h, acfg, angles)
    if t <= Q_CHUNK:
        ctx = attn_lib._sdpa(q, k, v, causal=acfg.causal, window=acfg.window)
    else:
        assert t % Q_CHUNK == 0, (t, Q_CHUNK)
        nch = t // Q_CHUNK

        def chunk_fn(_, inp):
            qc, i = inp
            return None, attn_lib._sdpa(
                qc, k, v,
                causal=acfg.causal,
                window=acfg.window,
                kv_valid_len=(i + 1) * Q_CHUNK if acfg.causal else None,
            )

        qch = jnp.moveaxis(q.reshape(b, nch, Q_CHUNK, acfg.n_heads, acfg.head_dim), 1, 0)
        _, ctx = jax.lax.scan(chunk_fn, None, (qch, jnp.arange(nch)))
        ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, t, acfg.n_heads, acfg.head_dim)
    out = dense(bp["o"], ctx.reshape(b, t, acfg.q_dim), name="o")
    entry = None
    if want_cache:
        entry = {"k": jnp.moveaxis(k, 1, 2), "v": jnp.moveaxis(v, 1, 2)}  # (B,H,T,d)
    return out, entry


def _mla_full(bp, h, cfg: ArchConfig, positions, want_cache: bool):
    mcfg = cfg.mla_config()
    b, t, _ = h.shape
    q_nope, q_rope = attn_lib._mla_q(bp, h, mcfg, positions)
    latent, k_rope = attn_lib._mla_latent(bp, h, mcfg, positions)
    kv = dense(bp["kv_b"], latent, name="kv_b").reshape(
        b, t, mcfg.n_heads, mcfg.qk_nope_dim + mcfg.v_head_dim
    )
    k_nope, v = kv[..., : mcfg.qk_nope_dim], kv[..., mcfg.qk_nope_dim :]
    scale = mcfg.qk_head_dim**-0.5

    def score_chunk(qn, qr, q_off):
        logits = (
            jnp.einsum("bqhd,bkhd->bhqk", qn, k_nope)
            + jnp.einsum("bqhd,bkd->bhqk", qr, k_rope)
        ) * scale
        qpos = q_off + jnp.arange(qn.shape[1])[:, None]
        mask = jnp.arange(t)[None, :] <= qpos
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
        probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(h.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    if t <= Q_CHUNK:
        ctx = score_chunk(q_nope, q_rope, 0)
    else:
        assert t % Q_CHUNK == 0
        nch = t // Q_CHUNK

        def chunks(a):
            return jnp.moveaxis(a.reshape(b, nch, Q_CHUNK, *a.shape[2:]), 1, 0)

        _, ctx = jax.lax.scan(
            lambda _, inp: (None, score_chunk(inp[0], inp[1], inp[2] * Q_CHUNK)),
            None,
            (chunks(q_nope), chunks(q_rope), jnp.arange(nch)),
        )
        ctx = jnp.moveaxis(ctx, 0, 1)
    out = dense(bp["o"], ctx.reshape(b, t, -1), name="o")
    entry = {"latent": latent, "rope": k_rope} if want_cache else None
    return out, entry


# ---------------------------------------------------------------------------
# block forward (training / prefill)
# ---------------------------------------------------------------------------


def _block_forward(bp: dict, x, cfg: ArchConfig, positions, mesh,
                   want_cache: bool = False):
    b, t, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    h = apply_norm(cfg.norm, bp["attn_norm"], x)
    entry: dict = {}
    if cfg.attn == "mla":
        a, e = _mla_full(bp["attn"], h, cfg, positions, want_cache)
    elif cfg.attn == "gqa":
        a, e = _gqa_full(bp["attn"], h, cfg, positions, want_cache)
    else:
        a, e = 0.0, None
    if e:
        entry.update(e)
    if cfg.parallel_ssm:
        if want_cache:
            s, st = _ssm_with_state(bp["ssm"], h, cfg.ssm_config())
            entry["ssm_conv"], entry["ssm_h"] = st["conv"], st["h"]
        else:
            s = ssm_lib.ssm_prefill(bp["ssm"], h, cfg.ssm_config())
        a = 0.5 * (rmsnorm(bp["attn_out_norm"], a.astype(x.dtype)) +
                   rmsnorm(bp["ssm_out_norm"], s.astype(x.dtype)))
    x = (x + a).astype(x.dtype)

    h = apply_norm(cfg.norm, bp["mlp_norm"], x)
    if cfg.mlp == "moe" and "router" in bp["mlp"]:
        m = moe_lib.moe_apply(bp["mlp"], h, cfg.moe_config(), mesh=mesh)
    elif cfg.mlp == "gelu":
        m = gelu_mlp(bp["mlp"], h)
    else:
        m = swiglu(bp["mlp"], h)
    return (x + m).astype(x.dtype), entry


def _sp_constrain(x: jax.Array, cfg: ArchConfig, mesh):
    """Sequence-parallel residual stream: (B, T, D) sharded
    (batch over DP axes, T over "model") at block boundaries."""
    if not cfg.sequence_parallel or mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data")) or None
    t = x.shape[1]
    if t % mesh.shape["model"] != 0:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(dp, "model", None)))


def _sp_gather(x: jax.Array, cfg: ArchConfig, mesh):
    """The Megatron-SP all-gather point: sequence re-assembled, ready for
    the TP-sharded projections.  Pinning this explicitly stops GSPMD from
    emitting redundant reshard ping-pong inside the block (measured 3.6k
    all-reduces/step -> see EXPERIMENTS.md §Perf iteration 5)."""
    if not cfg.sequence_parallel or mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data")) or None
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(dp, None, None)))


def _remat_wrap(fn, cfg: ArchConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.checkpoint_dots)


def backbone(params: Params, x: jax.Array, cfg: ArchConfig, positions=None,
             mesh=None) -> jax.Array:
    """Embedded input -> final-norm output (training / forward path)."""
    cdt = _dtype(cfg.compute_dtype)
    x = x.astype(cdt)
    for i, bp in enumerate(params.get("dense_blocks", [])):
        with observers.scope("dense_blocks", i):
            x, _ = _block_forward(bp, x, cfg, positions, mesh)

    body = _remat_wrap(
        lambda carry, bp: (
            _sp_constrain(
                _block_forward(bp, _sp_constrain(carry, cfg, mesh), cfg,
                               positions, mesh)[0],
                cfg, mesh),
            None,
        ),
        cfg,
    )
    if cfg.scan_layers:
        x, _ = jax.lax.scan(body, x, params["blocks"])
    else:
        n = jax.tree.leaves(params["blocks"])[0].shape[0]
        for i in range(n):
            bp = jax.tree.map(lambda a: a[i], params["blocks"])
            with observers.scope("blocks", i):
                x, _ = body(x, bp)
    return apply_norm(cfg.norm, params["final_norm"], x)


def _embed_input(params, batch: dict, cfg: ArchConfig):
    if "embeds" in batch:
        return batch["embeds"]
    return embed(params["embed"], batch["tokens"])


def _head_w(params):
    head = params.get("lm_head", params["embed"])
    return head["table"].T if "table" in head else head["w"]


def _logits_head(params, x: jax.Array) -> jax.Array:
    """Unembedding that also accepts a PACKED (approximate) lm_head."""
    from repro.core.approx_linear import QuantizedDense

    head = params.get("lm_head", params["embed"])
    if isinstance(head, QuantizedDense):
        return dense(head, x, name="lm_head").astype(jnp.float32)
    w = head["table"].T if "table" in head else head["w"]
    return jnp.matmul(x, w.astype(x.dtype)).astype(jnp.float32)


def forward(params: Params, batch: dict, cfg: ArchConfig, mesh=None) -> jax.Array:
    """Full-sequence logits (test/benchmark use; training uses train_loss)."""
    x = backbone(params, _embed_input(params, batch, cfg), cfg,
                 batch.get("positions"), mesh)
    return _logits_head(params, x)


# ---------------------------------------------------------------------------
# fused chunked LM-head + cross-entropy
# ---------------------------------------------------------------------------


def _ce_from_logits(logits, labels, mask):
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (lse - ll) * mask
    return nll.sum(), mask.sum()


def chunked_ce_loss(x, head_w, labels, mask):
    """Mean CE over (B, T, D) features without a (B, T, V) logits tensor."""
    b, t, _ = x.shape
    if t <= LOSS_CHUNK:
        nll, cnt = _ce_from_logits(jnp.matmul(x, head_w.astype(x.dtype)), labels, mask)
        return nll / jnp.maximum(cnt, 1.0)
    pad = (-t) % LOSS_CHUNK
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nch = x.shape[1] // LOSS_CHUNK

    def chunks(a):
        return jnp.moveaxis(a.reshape(b, nch, LOSS_CHUNK, *a.shape[2:]), 1, 0)

    def body(acc, inp):
        xc, lc, mc = inp
        nll, cnt = _ce_from_logits(jnp.matmul(xc, head_w.astype(xc.dtype)), lc, mc)
        return (acc[0] + nll, acc[1] + cnt), None

    (nll, cnt), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)),
        (chunks(x), chunks(labels), chunks(mask)),
    )
    return nll / jnp.maximum(cnt, 1.0)


def train_loss(params: Params, batch: dict, cfg: ArchConfig, mesh=None) -> jax.Array:
    """Next-token (causal) or masked-frame (encoder) cross-entropy."""
    x = backbone(params, _embed_input(params, batch, cfg), cfg,
                 batch.get("positions"), mesh)
    labels = batch["labels"]
    mask = batch.get("mask")
    if cfg.causal:
        x, labels = x[:, :-1], labels[:, 1:]
        mask = jnp.ones(labels.shape, jnp.float32) if mask is None else mask[:, 1:]
    else:
        mask = jnp.ones(labels.shape, jnp.float32) if mask is None else mask
    return chunked_ce_loss(x, _head_w(params), labels, mask)


# ---------------------------------------------------------------------------
# serving: cache, prefill, decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
    """Stacked-over-layers decode cache.  Sliding-window archs get ring
    buffers of length W; MLA gets latent caches; hybrids add SSM state."""
    n_scan = cfg.n_layers - cfg.first_dense_layers
    s = min(max_len, cfg.window) if cfg.window else max_len
    cache: dict = {"pos": jnp.zeros((), jnp.int32)}
    if cfg.attn == "mla":
        cache["latent"] = jnp.zeros((n_scan, batch, s, cfg.kv_lora_rank), dtype)
        cache["rope"] = jnp.zeros((n_scan, batch, s, cfg.qk_rope_dim), dtype)
    elif cfg.attn == "gqa":
        cache["k"] = jnp.zeros((n_scan, batch, cfg.kv_heads, s, cfg.head_dim), dtype)
        cache["v"] = jnp.zeros((n_scan, batch, cfg.kv_heads, s, cfg.head_dim), dtype)
    if cfg.first_dense_layers:
        fd = cfg.first_dense_layers
        if cfg.attn == "mla":
            cache["dense_latent"] = jnp.zeros((fd, batch, s, cfg.kv_lora_rank), dtype)
            cache["dense_rope"] = jnp.zeros((fd, batch, s, cfg.qk_rope_dim), dtype)
        else:
            cache["dense_k"] = jnp.zeros((fd, batch, cfg.kv_heads, s, cfg.head_dim), dtype)
            cache["dense_v"] = jnp.zeros((fd, batch, cfg.kv_heads, s, cfg.head_dim), dtype)
    if cfg.parallel_ssm:
        scfg = cfg.ssm_config()
        cache["ssm_conv"] = jnp.zeros(
            (n_scan, batch, scfg.conv_kernel - 1, scfg.d_inner), jnp.float32)
        cache["ssm_h"] = jnp.zeros(
            (n_scan, batch, scfg.d_inner, scfg.d_state), jnp.float32)
    return cache


def _ring_align(data: jax.Array, s: int, t: int) -> jax.Array:
    """Place the last ``s`` of ``t`` positions so that absolute position a
    sits at slot a mod s (ring invariant).  data seq axis = -2."""
    if t > s:
        data = data[..., t - s :, :]
        data = jnp.roll(data, t % s, axis=-2)
    elif t < s:
        pad = [(0, 0)] * data.ndim
        pad[-2] = (0, s - t)
        data = jnp.pad(data, pad)
    return data


def _block_decode(bp: dict, x, lc: dict, pos, cfg: ArchConfig, mesh):
    """One block's decode step.  lc: this layer's cache slices (no 'pos')."""
    acfg = cfg.attn_config()
    h = apply_norm(cfg.norm, bp["attn_norm"], x)
    new: dict = {}
    if cfg.attn == "mla":
        a, c2 = attn_lib.mla_decode_step(
            bp["attn"], h, {"latent": lc["latent"], "rope": lc["rope"], "pos": pos},
            cfg.mla_config(),
        )
        new["latent"], new["rope"] = c2["latent"], c2["rope"]
    elif cfg.attn == "gqa":
        step = attn_lib.attention_decode_ring if cfg.window else attn_lib.attention_decode_step
        a, c2 = step(bp["attn"], h, {"k": lc["k"], "v": lc["v"], "pos": pos}, acfg)
        new["k"], new["v"] = c2["k"], c2["v"]
    else:
        a = 0.0
    if cfg.parallel_ssm:
        s, st = ssm_lib.ssm_decode_step(
            bp["ssm"], h, {"conv": lc["ssm_conv"], "h": lc["ssm_h"]}, cfg.ssm_config()
        )
        new["ssm_conv"], new["ssm_h"] = st["conv"], st["h"]
        a = 0.5 * (rmsnorm(bp["attn_out_norm"], a.astype(x.dtype)) +
                   rmsnorm(bp["ssm_out_norm"], s.astype(x.dtype)))
    x = (x + a).astype(x.dtype)
    h = apply_norm(cfg.norm, bp["mlp_norm"], x)
    if cfg.mlp == "moe" and "router" in bp["mlp"]:
        m = moe_lib.moe_apply(bp["mlp"], h, cfg.moe_config(), mesh=mesh)
    elif cfg.mlp == "gelu":
        m = gelu_mlp(bp["mlp"], h)
    else:
        m = swiglu(bp["mlp"], h)
    return (x + m).astype(x.dtype), new


def decode_step(params: Params, tokens: jax.Array, cache: dict, cfg: ArchConfig,
                mesh=None) -> tuple[jax.Array, dict]:
    """tokens: (B, 1) -> (logits (B, V) f32, updated cache)."""
    cdt = _dtype(cfg.compute_dtype)
    x = embed(params["embed"], tokens).astype(cdt)
    pos = cache["pos"]
    new_cache = dict(cache)

    dense_keys = ("latent", "rope") if cfg.attn == "mla" else ("k", "v")
    for i, bp in enumerate(params.get("dense_blocks", [])):
        lc = {k: cache[f"dense_{k}"][i] for k in dense_keys}
        x, new = _block_decode(bp, x, lc, pos, cfg, mesh)
        for k in dense_keys:
            new_cache[f"dense_{k}"] = new_cache[f"dense_{k}"].at[i].set(new[k])

    layer_keys = [k for k in ("latent", "rope", "k", "v", "ssm_conv", "ssm_h")
                  if k in cache]

    lcs = {k: cache[k] for k in layer_keys}

    def body(x, inp):
        bp, lc = inp
        return _block_decode(bp, x, lc, pos, cfg, mesh)

    x, new_layers = jax.lax.scan(body, x, (params["blocks"], lcs))
    new_cache.update(new_layers)
    new_cache["pos"] = pos + 1

    x = apply_norm(cfg.norm, params["final_norm"], x)
    logits = _logits_head(params, x[:, 0])
    return logits, new_cache


def prefill(params: Params, batch: dict, cfg: ArchConfig, max_len: int,
            mesh=None, cache_dtype=jnp.bfloat16) -> tuple[jax.Array, dict]:
    """Single-pass prompt processing: last-token logits + filled cache."""
    x = _embed_input(params, batch, cfg)
    b, t = x.shape[:2]
    cdt = _dtype(cfg.compute_dtype)
    x = x.astype(cdt)
    positions = batch.get("positions")
    cache = init_cache(cfg, b, max_len, cache_dtype)
    s = min(max_len, cfg.window) if cfg.window else max_len

    dense_keys = ("latent", "rope") if cfg.attn == "mla" else ("k", "v")
    for i, bp in enumerate(params.get("dense_blocks", [])):
        with observers.scope("dense_blocks", i):
            x, e = _block_forward(bp, x, cfg, positions, mesh, want_cache=True)
        from repro.nn.attention import _to_cache as _tc
        for k in dense_keys:
            cache[f"dense_{k}"] = cache[f"dense_{k}"].at[i].set(
                _tc(_ring_align(e[k], s, t), cache_dtype))

    def body(carry, bp):
        out, entry = _block_forward(bp, carry, cfg, positions, mesh, want_cache=True)
        return out, entry

    x, entries = jax.lax.scan(body, x, params["blocks"])

    from repro.nn.attention import _to_cache

    for key in ("latent", "rope", "k", "v"):
        if key in entries:
            cache[key] = _to_cache(_ring_align(entries[key], s, t), cache_dtype)
    if cfg.parallel_ssm:
        cache["ssm_conv"] = entries["ssm_conv"].astype(jnp.float32)
        cache["ssm_h"] = entries["ssm_h"].astype(jnp.float32)
    cache["pos"] = jnp.asarray(t, jnp.int32)

    x = apply_norm(cfg.norm, params["final_norm"], x)
    logits = _logits_head(params, x[:, -1])
    return logits, cache


# ---------------------------------------------------------------------------
# serving: slot-indexed decode (continuous batching)
# ---------------------------------------------------------------------------
#
# The serving engine keeps ONE pooled cache of shape (slots, ...) with a
# per-slot write cursor ("lengths") instead of the single shared "pos"
# scalar.  ``decode_slots`` processes a fixed-shape (slots, C) token block
# where each row advances by its own ``n_valid[b] <= C`` tokens:
#
#   * C == 1           -> continuous decode over heterogeneous sequences;
#   * C == chunk size  -> one bounded-shape chunk of a prompt (chunked
#                         prefill) — and, in a MIXED batch, decode rows
#                         riding the same call with n_valid == 1, so a
#                         running decode never stalls behind a prefill turn.
#
# n_valid is fully per-row: any mix of 0 (idle padding), 1 (decode) and C
# (whole prompt chunk) is valid in one call.  Rows with n_valid == 0 are
# padding: their K/V writes land beyond their cursor (never attended,
# overwritten by the slot's next real tokens) and their cursor does not
# move — so the jitted step only ever sees the two shapes (slots, 1) and
# (slots, chunk) and never recompiles mid-serve.


def _slot_unsupported(cfg: ArchConfig) -> str | None:
    if cfg.window is not None:
        return "sliding-window ring caches have no per-slot phase yet"
    if cfg.parallel_ssm:
        return "parallel-SSM state is not slot-managed yet"
    if cfg.attn == "none":
        return "arch has no attention cache"
    return None


def init_slot_cache(cfg: ArchConfig, slots: int, max_len: int,
                    dtype=jnp.bfloat16) -> dict:
    """Pooled (slots, ...) decode cache with per-slot write cursors."""
    reason = _slot_unsupported(cfg)
    if reason is not None:
        raise NotImplementedError(f"slot decode for {cfg.name}: {reason}")
    cache = init_cache(cfg, slots, max_len, dtype)
    del cache["pos"]
    cache["lengths"] = jnp.zeros((slots,), jnp.int32)
    return cache


def init_paged_slot_cache(cfg: ArchConfig, num_blocks: int, block_size: int,
                          slots: int, dtype=jnp.bfloat16) -> dict:
    """Block-pool decode cache: KV leaves are indexed by PHYSICAL block id
    on axis 1 — ``(L, num_blocks, ..., block_size, d)`` — instead of by
    slot.  Per-slot block tables (an input to ``decode_slots``, managed by
    ``repro.serving.paged``) map logical token positions onto pool rows;
    ``lengths`` stays the per-slot write cursor.  Row 0 of the pool is the
    reserved NULL block that padding table entries point at — it is never
    allocated, so stale gathers from it are masked and stale scatters to
    it rewrite its own unchanged content."""
    reason = _slot_unsupported(cfg)
    if reason is not None:
        raise NotImplementedError(f"paged decode for {cfg.name}: {reason}")
    n_scan = cfg.n_layers - cfg.first_dense_layers
    cache: dict = {"lengths": jnp.zeros((slots,), jnp.int32)}
    if cfg.attn == "mla":
        cache["latent"] = jnp.zeros(
            (n_scan, num_blocks, block_size, cfg.kv_lora_rank), dtype)
        cache["rope"] = jnp.zeros(
            (n_scan, num_blocks, block_size, cfg.qk_rope_dim), dtype)
    elif cfg.attn == "gqa":
        cache["k"] = jnp.zeros(
            (n_scan, num_blocks, cfg.kv_heads, block_size, cfg.head_dim), dtype)
        cache["v"] = jnp.zeros(
            (n_scan, num_blocks, cfg.kv_heads, block_size, cfg.head_dim), dtype)
    if cfg.first_dense_layers:
        fd = cfg.first_dense_layers
        if cfg.attn == "mla":
            cache["dense_latent"] = jnp.zeros(
                (fd, num_blocks, block_size, cfg.kv_lora_rank), dtype)
            cache["dense_rope"] = jnp.zeros(
                (fd, num_blocks, block_size, cfg.qk_rope_dim), dtype)
        else:
            cache["dense_k"] = jnp.zeros(
                (fd, num_blocks, cfg.kv_heads, block_size, cfg.head_dim), dtype)
            cache["dense_v"] = jnp.zeros(
                (fd, num_blocks, cfg.kv_heads, block_size, cfg.head_dim), dtype)
    return cache


def rollback_slots(cache: dict, new_lengths) -> dict:
    """Retreat per-slot write cursors (speculative-decode rollback).

    Moving ``lengths`` back is a complete rollback for every cache layout
    this module builds: the attention mask hides entries at positions
    ``>= lengths[b]``, and the cache write puts a slot's next tokens
    over those positions BEFORE attention reads the cache — so stale
    K/V from rejected speculative tokens is never attended and is
    overwritten before it can be.  Works identically for the contiguous
    and paged layouts (``lengths`` is slot-indexed in both; the paged
    block tables are position-stable so no block bookkeeping changes).
    """
    out = dict(cache)
    out["lengths"] = jnp.asarray(new_lengths, jnp.int32)
    return out


def _paged_gather(pool: jax.Array, bt: jax.Array) -> jax.Array:
    """Assemble each slot's logically-contiguous KV view from the block
    pool.  pool: (num_blocks, ..., block_size, d), block axis -2;
    bt: (slots, nb) physical ids.  Returns (slots, ..., nb*block_size, d)
    — exactly the contiguous slot-cache layout, so the attention math and
    the clamp-aware ``_slot_update`` run unchanged on the view."""
    g = pool[bt]  # (slots, nb, ..., bs, d)
    g = jnp.moveaxis(g, 1, -3)  # (slots, ..., nb, bs, d)
    return g.reshape(g.shape[:-3] + (g.shape[-3] * g.shape[-2], g.shape[-1]))


def _paged_scatter(pool: jax.Array, bt: jax.Array, view: jax.Array) -> jax.Array:
    """Write each slot's updated contiguous view back into its blocks.
    Duplicate ids across rows (shared prefix blocks, NULL-block padding)
    are safe: shared blocks are frozen — every row's cursor is past them,
    so all duplicates carry bit-identical content and scatter order cannot
    matter.  The serving layer guarantees writable blocks are uniquely
    owned (copy-on-write happens host-side before the step)."""
    nb = bt.shape[1]
    bs = pool.shape[-2]
    blocks = view.reshape(view.shape[:-2] + (nb, bs, view.shape[-1]))
    return pool.at[bt].set(jnp.moveaxis(blocks, -3, 1))


def _write_plan(u: jax.Array, st, nv, s: int):
    """One row's cache write: row's first ``nv`` columns of the update
    block ``u`` (seq axis -2, C columns) land at [st, st + nv) of a stripe
    of length ``s``.  Returns the block to write, the mask of the columns
    that really write (the others keep the OLD cache values), and the start
    the block is written at.

    Padding columns (>= nv) must never write.  This matters beyond
    hygiene: ``dynamic_update_slice`` CLAMPS out-of-range starts.  A padding
    row (n_valid == 0) whose cursor exceeds S - C would otherwise have its
    block write clamped back over valid, attended entries — and a MIXED
    batch legitimately carries short rows deep in their stripe (a decode
    row with n_valid == 1 riding a chunk-shaped call can sit anywhere up
    to S - 1).  The write is therefore clamp-aware: the update block is
    rolled by the clamp displacement so its valid head still lands at
    [st, st + nv), and the mask is expressed in the clamped coordinates.
    For rows that do not clamp this reduces to the plain masked blend."""
    c_len = u.shape[-2]
    if c_len > 1:
        # where dynamic_update_slice will actually place the block
        st_eff = jnp.clip(st, 0, max(s - c_len, 0))
        shift = st - st_eff  # > 0 only when the raw start would clamp
        u = jnp.roll(u, shift, axis=-2)  # u[0] realigns to cache col st
        idx = jnp.arange(c_len)
        mask = (idx >= shift) & (idx < shift + nv)
        st = st_eff
    else:
        # static fast path: a one-column write can never clamp (every
        # cursor is <= S - 1), so skip the dynamic roll on the thin
        # (slots, 1) decode step — the hottest per-layer write
        mask = jnp.arange(c_len) < nv
    return u, mask.reshape((1,) * (u.ndim - 2) + (c_len, 1)), st


def _slot_update(cache_arr: jax.Array, update: jax.Array, starts: jax.Array,
                 n_valid: jax.Array):
    """Per-row write into one layer's stripes (B, ..., S, d): row b's first
    ``n_valid[b]`` update columns land at [starts[b], starts[b]+n_valid[b])
    on the -2 axis of row b (:func:`_write_plan`); returns the new stripes."""

    def write(c, u, st, nv):
        u, mask, st = _write_plan(u, st, nv, c.shape[-2])
        start = (0,) * (c.ndim - 2) + (st, 0)
        old = jax.lax.dynamic_slice(c, start, u.shape)
        return jax.lax.dynamic_update_slice(c, jnp.where(mask, u, old), start)

    return jax.vmap(write)(cache_arr, update, starts, n_valid)


def _slot_write(buf: jax.Array, layer, update: jax.Array, starts: jax.Array,
                n_valid: jax.Array) -> jax.Array:
    """In-place form of :func:`_slot_update` on a layer-stacked buffer
    (L, B, ..., S, d): row b's columns land at [layer, b, ..., st, :].

    One ``dynamic_update_slice`` per slot, of the C columns alone, and only
    those C old columns are read for the blend, all before the first
    write (a ``vmap`` over the slots would lower to a scatter).  On a
    buffer the step donates, the updates stay in place."""
    rows = []
    for b in range(update.shape[0]):
        u, mask, st = _write_plan(update[b], starts[b], n_valid[b],
                                  buf.shape[-2])
        start = (layer, b) + (0,) * (u.ndim - 2) + (st, 0)
        old = jax.lax.dynamic_slice(buf, start, (1, 1) + u.shape)
        rows.append((start, jnp.where(mask, u[None, None], old)))
    for start, block in rows:
        buf = jax.lax.dynamic_update_slice(buf, block, start)
    return buf


def _write_kv(lc: dict, new: dict, lengths, n_valid, layer) -> tuple[dict, dict]:
    """Write each slot's new cache columns ``new`` into the leaves ``lc``;
    returns (the stripes attention reads, the updated leaves).

    ``layer`` None: ``lc`` holds one layer's stripes and the updated
    stripes are both.  Otherwise ``lc`` holds the layer-stacked buffers:
    the columns land in place at ``layer``, then attention reads that
    layer's stripe from the updated buffer.  The read sits outside every
    inner scope, so a copy XLA makes of the stripe counts under
    ``layer_stack``."""
    with jax.named_scope("kv_write"):
        if layer is None:
            out = {k: _slot_update(lc[k], u, lengths, n_valid)
                   for k, u in new.items()}
            return out, out
        # the buffers keep their row-major layout through the scan: left
        # to itself, the v5e compiler lays a decode-shaped step's carried
        # cache out with the positions outside the heads, and copies the
        # whole stack into and out of the loop
        out = {k: with_layout_constraint(
                   _slot_write(lc[k], layer, u, lengths, n_valid),
                   Layout(tuple(range(lc[k].ndim))))
               for k, u in new.items()}
    return ({k: jax.lax.dynamic_index_in_dim(v, layer, keepdims=False)
             for k, v in out.items()}, out)


#: Fewest query columns the slot attention's score and context products
#: see.  A decode-shaped call has one, and XLA lowers a one-column product
#: differently from the chunk-shaped call's wider ones: on a TPU v5e the
#: bfloat16 scores then differ in the last bit, so a decode row's numbers
#: would depend on the shape of the call it rides in.  Padded to this many
#: columns, both calls compute each column alike; only the real columns
#: are kept.
MIN_QUERY_COLS = 8


def _pad_queries(q: jax.Array, positions: jax.Array):
    """Pad the query axis (1) of ``q`` and of ``positions`` (B, C) up to
    :data:`MIN_QUERY_COLS`; padded columns repeat the last position, so
    every padded row attends to at least one key."""
    pad = MIN_QUERY_COLS - q.shape[1]
    if pad <= 0:
        return q, positions
    widths = [(0, 0)] * q.ndim
    widths[1] = (0, pad)
    return (jnp.pad(q, widths),
            jnp.pad(positions, ((0, 0), (0, pad)), mode="edge"))


def _gqa_slots(bp, h, lc: dict, lengths, n_valid, cfg: ArchConfig, positions,
               layer=None):
    """Multi-token slot attention.  h: (B, C, D); lc k/v: (B, Hkv, S, hd),
    or (L, B, Hkv, S, hd) written at ``layer`` (:func:`_write_kv`);
    positions: (B, C) absolute positions lengths[b] + i."""
    from repro.nn.attention import _from_cache, _to_cache

    acfg = cfg.attn_config()
    b, c, _ = h.shape
    with jax.named_scope("qkv"):
        q, k, v = attn_lib._project_qkv(bp, h, acfg,
                                        attn_lib._angles(acfg, positions))
    with jax.named_scope("kv_write"):
        new = {"k": _to_cache(jnp.moveaxis(k, 1, 2), lc["k"].dtype),
               "v": _to_cache(jnp.moveaxis(v, 1, 2), lc["v"].dtype)}
    kv, out = _write_kv(lc, new, lengths, n_valid, layer)
    k_c, v_c = kv["k"], kv["v"]
    hq, hkv, d = acfg.n_heads, acfg.kv_heads, acfg.head_dim
    g = hq // hkv
    with jax.named_scope("attn"):
        qg, q_pos = _pad_queries(q.reshape(b, c, hkv, g, d), positions)
        logits = jnp.einsum("bqhgd,bhkd->bhgqk", qg,
                            _from_cache(k_c, q.dtype)) * (d**-0.5)
        s = k_c.shape[2]
        # causal + filled-cache combined: key j visible to query i iff
        # j <= pos_i
        mask = jnp.arange(s)[None, None, :] <= q_pos[:, :, None]  # (B, Cq, S)
        logits = jnp.where(mask[:, None, None], logits, -jnp.inf)
        probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(q.dtype)
        ctx = jnp.einsum("bhgqk,bhkd->bqhgd", probs, _from_cache(v_c, q.dtype))
    with jax.named_scope("o_proj"):
        y = dense(bp["o"], ctx[:, :c].reshape(b, c, acfg.q_dim), name="o")
    return y, out


def _mla_slots(bp, h, lc: dict, lengths, n_valid, cfg: ArchConfig, positions,
               layer=None):
    """Weight-absorbed MLA slot attention over the pooled latent cache
    (one layer's, or the layer-stacked one written at ``layer``)."""
    mcfg = cfg.mla_config()
    b, c, _ = h.shape
    with jax.named_scope("qkv"):
        q_nope, q_rope = attn_lib._mla_q(bp, h, mcfg, positions)
        latent_t, k_rope_t = attn_lib._mla_latent(bp, h, mcfg, positions)
    with jax.named_scope("kv_write"):
        new = {"latent": latent_t.astype(lc["latent"].dtype),
               "rope": k_rope_t.astype(lc["rope"].dtype)}
    kv, out = _write_kv(lc, new, lengths, n_valid, layer)
    lat_c, rope_c = kv["latent"], kv["rope"]

    with jax.named_scope("attn"):
        w_b = bp["kv_b"]["w"].reshape(
            mcfg.kv_lora_rank, mcfg.n_heads,
            mcfg.qk_nope_dim + mcfg.v_head_dim)
        w_uk, w_uv = w_b[..., : mcfg.qk_nope_dim], w_b[..., mcfg.qk_nope_dim:]
        q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_uk)
        scale = mcfg.qk_head_dim**-0.5
        lat = lat_c.astype(h.dtype)
        logits = (
            jnp.einsum("bqhr,bkr->bhqk", q_lat, lat)
            + jnp.einsum("bqhd,bkd->bhqk", q_rope, rope_c.astype(h.dtype))
        ) * scale
        s = lat_c.shape[1]
        mask = jnp.arange(s)[None, None, :] <= positions[:, :, None]
        logits = jnp.where(mask[:, None], logits, -jnp.inf)
        probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(h.dtype)
        ctx_lat = jnp.einsum("bhqk,bkr->bqhr", probs, lat)
        ctx = jnp.einsum("bqhr,rhd->bqhd", ctx_lat, w_uv)
    with jax.named_scope("o_proj"):
        y = dense(bp["o"], ctx.reshape(b, c, -1), name="o")
    return y, out


#: Named scopes of the served step (``jax.named_scope``): every operation of
#: ``decode_slots`` falls under one of them, so a device trace, through
#: each op's ``op_name``, says which layer of the step it belongs to.  An
#: op under ``layer_stack`` and no inner scope is the layer scan's own
#: slicing of the stacked weights and its read of each layer's cache stripe
#: (on the paged layout, its slicing and restacking of the stripes);
#: ``kv_write`` holds the cache writes; ``cv`` (the
#: control-variate correction, :func:`repro.quant.quantize.quantized_linear`)
#: nests under the dense scope that calls it.
STEP_SCOPES = ("embed", "attn_norm", "qkv", "kv_write", "attn", "o_proj",
               "mlp_norm", "mlp_in", "mlp_out", "final_norm", "head",
               "layer_stack", "cv")


def _block_decode_slots(bp: dict, x, lc: dict, lengths, n_valid,
                        cfg: ArchConfig, positions, mesh, block_tables=None,
                        layer=None):
    with jax.named_scope("attn_norm"):
        h = apply_norm(cfg.norm, bp["attn_norm"], x)
    pool_lc = None
    if block_tables is not None:
        # paged layout: gather each slot's blocks into the contiguous view
        # the slot attention expects, run it unchanged, scatter back
        pool_lc = lc
        with jax.named_scope("kv_write"):
            lc = {k: _paged_gather(v, block_tables) for k, v in lc.items()}
    slots_attn = _mla_slots if cfg.attn == "mla" else _gqa_slots
    a, new = slots_attn(bp["attn"], h, lc, lengths, n_valid, cfg, positions,
                        layer)
    if pool_lc is not None:
        with jax.named_scope("kv_write"):
            new = {k: _paged_scatter(pool_lc[k], block_tables, v)
                   for k, v in new.items()}
    with jax.named_scope("o_proj"):
        x = (x + a).astype(x.dtype)
    with jax.named_scope("mlp_norm"):
        h = apply_norm(cfg.norm, bp["mlp_norm"], x)
    if cfg.mlp == "moe" and "router" in bp["mlp"]:
        # the routed experts' in and out products are not told apart
        with jax.named_scope("mlp_in"):
            m = moe_lib.moe_apply(bp["mlp"], h, cfg.moe_config(), mesh=mesh)
    elif cfg.mlp == "gelu":
        m = gelu_mlp(bp["mlp"], h)
    else:
        m = swiglu(bp["mlp"], h)
    with jax.named_scope("mlp_out"):
        return (x + m).astype(x.dtype), new


def decode_slots(params: Params, tokens: jax.Array, cache: dict,
                 cfg: ArchConfig, n_valid: jax.Array,
                 mesh=None, block_tables=None,
                 unroll_layers: bool = False) -> tuple[jax.Array, dict]:
    """Fixed-shape continuous-batching step.

    tokens: (slots, C) int32 — row b's first ``n_valid[b]`` entries are real
    (its next prompt chunk, or its one decode token), the rest padding.
    Returns (logits (slots, C, V) f32, cache with per-row cursors advanced
    by ``n_valid``).  The caller reads row b's logits at column
    ``n_valid[b] - 1``.

    ``block_tables`` selects the PAGED cache layout: a (slots, nb) int32
    map from each slot's logical block index to a physical row of the
    block-pool cache (``init_paged_slot_cache``).  Each layer gathers the
    slot's blocks into the contiguous view, runs the identical attention +
    clamp-aware cursor write, and scatters the touched blocks back — so the
    paged step is token-identical to the contiguous one by construction.
    Table shape is fixed, so each layout keeps its own two compiled shapes.

    Speculative decode (:mod:`repro.serving.speculative`) runs this same
    step twice per round with two parameter sets over ONE cache: k thin
    ``(slots, 1)`` calls with the approximate draft params (writing draft
    K/V at [L, L+k)), then one chunk-shaped call with the exact params
    whose verify rows carry ``n_valid = k+1`` and overwrite [L, L+k] with
    exact K/V.  Rollback between and after the phases is
    :func:`rollback_slots` — a pure cursor move, sound because writes land
    before attention and positions past the cursor are masked.  The C == 1
    fast path in ``_write_plan`` asserts no clamping, so draft cursors
    must stay ``<= max_len - 1``; the serving layer guarantees it by
    capping k at the request's remaining generation budget minus one.

    Kernel decode specialization: the packed-dense fast path keys its tile
    choice on the flattened row count slots*C, so continuous decode (C == 1,
    slots <= repro.kernels.ops.DECODE_M_MAX) runs thin-M single-K-step
    launches while prefill chunks (C == prefill_chunk) keep prefill tiles —
    both from the same jitted step, one compiled shape each.

    The contiguous layout's scan carries the layer-stacked cache: each
    layer writes its slots' new columns into it in place
    (:func:`_slot_write`) and attention reads the layer's stripe back, so
    a step whose cache argument is donated moves no more of the cache than
    it writes and attends.  The paged layout and ``unroll_layers`` take
    each layer's stripes in and give them back (:func:`_slot_update`),
    with the same numbers.

    ``unroll_layers`` replaces the layer ``lax.scan`` with a python loop
    (per-layer ``observers.scope``d) — ``lax.scan`` traces its body even
    when run eagerly, so concrete per-layer values only exist unrolled.
    The approximation-error probe (:mod:`repro.quant.error_probe`) runs
    its eager single-row forwards this way; the jitted serving step never
    sets it (the scan keeps HLO size O(1) in depth).
    """
    reason = _slot_unsupported(cfg)
    if reason is not None:
        raise NotImplementedError(f"slot decode for {cfg.name}: {reason}")
    b, c = tokens.shape
    cdt = _dtype(cfg.compute_dtype)
    lengths = cache["lengths"]
    n_valid = jnp.asarray(n_valid, jnp.int32)
    if block_tables is not None:
        block_tables = jnp.asarray(block_tables, jnp.int32)
    with jax.named_scope("embed"):
        positions = lengths[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
        x = embed(params["embed"], tokens).astype(cdt)
    new_cache = dict(cache)
    # the contiguous jitted step updates the stacked cache in place (one
    # write per slot of its new columns, into the buffer the engine
    # donates); the paged layout and the unrolled probe forward update
    # per-layer stripes
    in_place = block_tables is None and not unroll_layers

    dense_keys = ("latent", "rope") if cfg.attn == "mla" else ("k", "v")
    for i, bp in enumerate(params.get("dense_blocks", [])):
        stacked = {k: new_cache[f"dense_{k}"] for k in dense_keys}
        if in_place:
            lc, layer = stacked, i
        else:
            with jax.named_scope("kv_write"):
                lc, layer = {k: v[i] for k, v in stacked.items()}, None
        with observers.scope("dense_blocks", i):
            x, new = _block_decode_slots(bp, x, lc, lengths, n_valid, cfg,
                                         positions, mesh, block_tables, layer)
        with jax.named_scope("kv_write"):
            for k in dense_keys:
                new_cache[f"dense_{k}"] = (
                    new[k] if in_place else stacked[k].at[i].set(new[k]))

    layer_keys = [k for k in ("latent", "rope", "k", "v") if k in cache]
    lcs = {k: cache[k] for k in layer_keys}
    n_layers = lcs[layer_keys[0]].shape[0]

    if unroll_layers:
        acc: dict[str, list] = {k: [] for k in layer_keys}
        for i in range(n_layers):
            bp = jax.tree.map(lambda a: a[i], params["blocks"])
            lc = {k: lcs[k][i] for k in layer_keys}
            with observers.scope("blocks", i):
                x, new = _block_decode_slots(bp, x, lc, lengths, n_valid,
                                             cfg, positions, mesh,
                                             block_tables)
            for k in layer_keys:
                acc[k].append(new[k])
        new_layers = {k: jnp.stack(acc[k]) for k in layer_keys}
    elif in_place:
        # the stacked cache rides the carry: each layer writes its columns
        # into it and reads its own stripe back, nothing is restacked
        def body(carry, inp):
            x, kv = carry
            bp, i = inp
            return _block_decode_slots(bp, x, kv, lengths, n_valid, cfg,
                                       positions, mesh, layer=i), None

        with jax.named_scope("layer_stack"):
            (x, new_layers), _ = jax.lax.scan(
                body, (x, lcs),
                (params["blocks"], jnp.arange(n_layers, dtype=jnp.int32)))
    else:
        def body(x, inp):
            bp, lc = inp
            return _block_decode_slots(bp, x, lc, lengths, n_valid, cfg,
                                       positions, mesh, block_tables)

        with jax.named_scope("layer_stack"):
            x, new_layers = jax.lax.scan(body, x, (params["blocks"], lcs))
    new_cache.update(new_layers)
    with jax.named_scope("kv_write"):
        new_cache["lengths"] = lengths + n_valid

    with jax.named_scope("final_norm"):
        x = apply_norm(cfg.norm, params["final_norm"], x)
    with jax.named_scope("head"):
        logits = _logits_head(params, x)
    return logits, new_cache


def _ssm_with_state(p, x, scfg):
    """SSM prefill that also returns the final (conv, h) state."""
    y = ssm_lib.ssm_prefill(p, x, scfg)
    # re-derive final state (cheap relative to the scan; shares projections
    # would need scan surgery — conv tail + one more scan over h only)
    xz = dense(p["in_proj"], x, name="in_proj")
    xin, _ = jnp.split(xz, 2, axis=-1)
    conv_state = xin[:, -(scfg.conv_kernel - 1):, :]
    xc = jax.nn.silu(ssm_lib._causal_conv(p, xin))
    dt, bmat, _ = ssm_lib._ssm_inputs(p, scfg, xc)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))

    def step(h, inp):
        xc_t, dt_t, b_t = inp
        da = jnp.exp(dt_t[..., None] * a)
        return da * h + (dt_t * xc_t)[..., None] * b_t[:, None, :], None

    h0 = jnp.zeros((x.shape[0], scfg.d_inner, scfg.d_state), jnp.float32)
    h, _ = jax.lax.scan(
        step, h0,
        (jnp.moveaxis(xc, 1, 0), jnp.moveaxis(dt, 1, 0), jnp.moveaxis(bmat, 1, 0)),
    )
    return y, {"conv": conv_state, "h": h}
