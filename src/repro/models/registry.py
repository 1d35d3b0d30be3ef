"""Uniform model API over the zoo + per-shape input specs.

``build_model(cfg)`` returns a :class:`ModelApi` whose methods are pure
functions of (params, batch[, cache]); ``input_specs(cfg, shape)`` returns
ShapeDtypeStruct stand-ins for every model input of the assigned input
shapes (weak-type-correct, shardable, no device allocation) — the dry-run
contract.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str  # "train" | "prefill" | "decode"
    seq_len: int
    global_batch: int


SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeSpec("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeSpec("long_500k", "decode", 524288, 1),
}


def shape_applicable(cfg: ArchConfig, shape: str) -> tuple[bool, str]:
    """(runnable, reason-if-skipped) per the assignment's skip rules."""
    s = SHAPES[shape]
    if s.kind == "decode" and not cfg.has_decode:
        return False, "encoder-only arch has no decode step"
    if shape == "long_500k" and not cfg.sub_quadratic:
        return False, "full-attention arch; 500k decode assigned to SSM/hybrid only"
    return True, ""


@dataclasses.dataclass(frozen=True)
class ModelApi:
    cfg: ArchConfig
    init: Callable  # (key) -> params
    forward: Callable  # (params, batch, mesh=None) -> logits
    train_loss: Callable  # (params, batch, mesh=None) -> scalar
    prefill: Callable  # (params, batch, max_len, mesh=None) -> (logits, cache)
    decode_step: Callable  # (params, tokens, cache, mesh=None) -> (logits, cache)
    init_cache: Callable  # (batch, max_len, dtype) -> cache
    # continuous-batching surface (serving engine): pooled per-slot cache +
    # fixed-shape multi-token step with per-slot cursors
    init_slot_cache: Callable = None  # (slots, max_len, dtype) -> cache
    # (params, tokens, cache, n_valid, mesh=None, block_tables=None);
    # block_tables selects the paged layout (repro.serving.paged);
    # moe_counts=True (MoE archs) adds the MoE layers' counts as a third
    # output
    decode_slots: Callable = None
    # paged layout: (num_blocks, block_size, slots, dtype) -> block-pool cache
    init_paged_cache: Callable = None

    @property
    def supports_slots(self) -> bool:
        """True when the arch can serve through the slot engine."""
        if not self.cfg.has_decode:
            return False
        if self.cfg.rwkv:
            return True
        from repro.models.lm import _slot_unsupported

        return _slot_unsupported(self.cfg) is None

    @property
    def supports_paged(self) -> bool:
        """True when the arch can serve through the paged (block) KV
        layout.  Recurrent archs (RWKV) have per-slot state, not a KV
        sequence, so there is nothing to page."""
        return self.init_paged_cache is not None and self.supports_slots


def build_model(cfg: ArchConfig) -> ModelApi:
    if cfg.rwkv:
        from repro.models import rwkv_lm as m

        return ModelApi(
            cfg=cfg,
            init=lambda key: m.init_params(key, cfg),
            forward=lambda p, b, mesh=None: m.forward(p, b, cfg, mesh),
            train_loss=lambda p, b, mesh=None: m.train_loss(p, b, cfg, mesh),
            prefill=lambda p, b, max_len=0, mesh=None, cache_dtype=jnp.bfloat16:
                m.prefill(p, b, cfg, max_len, mesh, cache_dtype),
            decode_step=lambda p, t, c, mesh=None: m.decode_step(p, t, c, cfg, mesh),
            init_cache=lambda batch, max_len=0, dtype=jnp.bfloat16: m.init_cache(
                cfg, batch, max_len, dtype),
            init_slot_cache=lambda slots, max_len=0, dtype=jnp.bfloat16:
                m.init_slot_cache(cfg, slots, max_len, dtype),
            decode_slots=lambda p, t, c, n_valid, mesh=None:
                m.decode_slots(p, t, c, cfg, n_valid, mesh),
        )
    from repro.models import lm as m

    return ModelApi(
        cfg=cfg,
        init=lambda key: m.init_params(key, cfg),
        forward=lambda p, b, mesh=None: m.forward(p, b, cfg, mesh),
        train_loss=lambda p, b, mesh=None: m.train_loss(p, b, cfg, mesh),
        prefill=lambda p, b, max_len, mesh=None, cache_dtype=jnp.bfloat16:
            m.prefill(p, b, cfg, max_len, mesh, cache_dtype),
        decode_step=lambda p, t, c, mesh=None: m.decode_step(p, t, c, cfg, mesh),
        init_cache=lambda batch, max_len, dtype=jnp.bfloat16: m.init_cache(
            cfg, batch, max_len, dtype),
        init_slot_cache=lambda slots, max_len, dtype=jnp.bfloat16:
            m.init_slot_cache(cfg, slots, max_len, dtype),
        # unroll_layers: eager python-loop layer stack for the error probe
        # (repro.quant.error_probe); jitted serving keeps the lax.scan
        decode_slots=lambda p, t, c, n_valid, mesh=None, block_tables=None,
            unroll_layers=False, moe_counts=False:
            m.decode_slots(p, t, c, cfg, n_valid, mesh, block_tables,
                           unroll_layers, moe_counts),
        init_paged_cache=lambda num_blocks, block_size, slots,
            dtype=jnp.bfloat16:
            m.init_paged_slot_cache(cfg, num_blocks, block_size, slots, dtype),
    )


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; no allocation)
# ---------------------------------------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ArchConfig, shape: str) -> dict:
    """Model-input stand-ins for one (arch, shape) cell.

    [vlm]/[audio] archs take STUB precomputed embeddings for the sequence
    body (the modality frontend is out of scope per the assignment); decode
    still consumes token ids through the embedding table.
    """
    s = SHAPES[shape]
    b, t = s.global_batch, s.seq_len
    embeds_input = cfg.input_mode == "embeds"

    if s.kind == "train":
        if embeds_input:
            batch = {
                "embeds": _sds((b, t, cfg.d_model), jnp.bfloat16),
                "labels": _sds((b, t), jnp.int32),
            }
            if cfg.family == "audio":
                batch["mask"] = _sds((b, t), jnp.float32)
        else:
            batch = {
                "tokens": _sds((b, t), jnp.int32),
                "labels": _sds((b, t), jnp.int32),
            }
        return {"batch": batch}

    if s.kind == "prefill":
        if embeds_input:
            batch = {"embeds": _sds((b, t, cfg.d_model), jnp.bfloat16)}
            if cfg.rope == "mrope":
                batch["positions"] = _sds((3, b, t), jnp.int32)
        else:
            batch = {"tokens": _sds((b, t), jnp.int32)}
        return {"batch": batch, "max_len": t}

    # decode: one new token against a cache of seq_len
    api = build_model(cfg)
    cache = jax.eval_shape(
        lambda: api.init_cache(b, t, jnp.bfloat16)
    )
    return {"tokens": _sds((b, 1), jnp.int32), "cache": cache}
