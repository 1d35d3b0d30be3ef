"""Unified architecture configuration.

One frozen dataclass describes every assigned architecture; model builders
(models/lm.py, models/rwkv_lm.py) interpret it.  Published configs live in
one module per arch (configs/<id>.py) and are registered in
configs/registry.py.  ``reduced()`` derives the CPU-smoke-test variant of
the same family (small depth/width/vocab/experts — structure preserved).
"""

from __future__ import annotations

import dataclasses
import math
from collections.abc import Mapping
from typing import Literal


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Continuous-batching serving knobs (consumed by ``repro.serving``).

    ``slots`` fixes the decode batch shape (the jitted step never
    recompiles); ``max_len`` is the per-slot KV capacity; prompts are
    processed in ``prefill_chunk``-token pieces interleaved with decode.
    Slot counts <= repro.kernels.ops.DECODE_M_MAX additionally hit the
    packed-dense kernels' decode-specialized (thin-M, single-K-step) tiles.
    """

    slots: int = 8
    max_len: int = 256
    prefill_chunk: int = 32
    max_queue: int = 256
    cache_dtype: str = "bfloat16"  # "bfloat16" | "float32" | "int8"
    interleave: bool = True  # alternate prefill/decode when both are pending
    #: decode rows ride chunk-shaped prefill calls with n_valid=1, so a
    #: running decode advances every iteration (no stall behind prefill
    #: turns); off falls back to whole-batch alternation (``interleave``)
    mixed_batches: bool = True
    #: "contiguous": every slot owns a max_len KV stripe (the original
    #: layout).  "paged": slots map logical positions onto refcounted
    #: fixed-size blocks from a shared pool (repro.serving.paged) —
    #: heterogeneous lengths stop costing max_len each, and requests
    #: sharing a prompt prefix attach to already-filled blocks
    #: copy-on-write instead of re-prefilling them.  Either layout keeps
    #: the two-compiled-shapes invariant for its jitted step.
    kv_layout: str = "contiguous"  # "contiguous" | "paged"
    kv_block_size: int = 16  # tokens per KV block (paged layout)
    #: usable KV blocks in the shared pool; 0 = capacity parity with the
    #: contiguous layout (slots * ceil(max_len / kv_block_size))
    kv_blocks: int = 0
    #: content-hash prefix cache over full prompt blocks (paged layout):
    #: requests sharing a cached prefix skip its prefill entirely
    prefix_cache: bool = True
    #: request-span tracing (repro.serving.telemetry): record typed span
    #: events (queued/admitted/prefill_chunk/decode_step/...) into a
    #: per-engine ring buffer, exportable as JSONL or Chrome trace JSON
    trace: bool = False
    trace_buffer: int = 65536  # span ring capacity; oldest events dropped
    #: windowed time-series: every ``metrics_window_s`` seconds the
    #: metrics emit one sample of rates/depths/utilization (0 disables)
    metrics_window_s: float = 0.0
    #: approximation-error probe: every N engine steps re-run one
    #: scheduled batch row through the exact-int8 path and record
    #: per-layer + logits error moments (repro.quant.error_probe);
    #: 0 disables (the default — two extra eager forwards per probe)
    error_probe_every: int = 0
    #: self-verifying speculative decode (repro.serving.speculative):
    #: each decoding slot drafts up to k greedy tokens through the
    #: APPROXIMATE draft parameters on the thin (slots, 1) step, then one
    #: chunk-shaped EXACT call verifies all of them at once; the longest
    #: agreeing prefix plus the verifier's correction token is emitted,
    #: so outputs stay bit-identical to plain exact decode.  0 disables.
    #: Requires ``ServingEngine(..., draft_params=...)``.
    speculative_k: int = 0
    #: engine-side NaN/divergence detection on every step's consumed
    #: logits columns: flagged rows are quarantined — KV cursor rolled
    #: back, the step replayed on the exact pack — before any token is
    #: emitted (repro.quant.faults).  Implied on when a fault injector is
    #: attached; off (the default) costs nothing on the hot path.
    detect_faults: bool = False
    #: A/B shadow serving (repro.serving.shadow): every round(1/fraction)
    #: finished requests, one replays teacher-forced through a SECOND
    #: NumericsSpec pack on the same engine; token agreement, logit-delta
    #: moments and modeled power feed an automated accuracy-vs-power
    #: verdict.  0 disables.  Requires ``ServingEngine(shadow_params=)``.
    shadow_fraction: float = 0.0


@dataclasses.dataclass(frozen=True, eq=False)
class YaRN:
    """A published ``rope_scaling`` of type ``yarn`` (DeepSeek-V2's form),
    under its published keys.  Equal to the mapping it was read from, and
    hashable, so an :class:`ArchConfig` holding it stays a static value."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    type: str = "yarn"

    def _items(self) -> dict:
        return dataclasses.asdict(self)

    def __eq__(self, other):
        if isinstance(other, Mapping):
            return self._items() == dict(other)
        if isinstance(other, YaRN):
            return self._items() == other._items()
        return NotImplemented

    def __hash__(self) -> int:
        return hash(tuple(self._items().items()))

    @staticmethod
    def m(scale: float, mscale: float) -> float:
        """YaRN's attention factor ``0.1 * mscale * ln(scale) + 1``."""
        return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0

    @property
    def softmax_factor(self) -> float:
        """What the softmax scale is multiplied by: ``m(factor,
        mscale_all_dim)**2`` (1 when ``mscale_all_dim`` is 0)."""
        if not self.mscale_all_dim:
            return 1.0
        return self.m(self.factor, self.mscale_all_dim) ** 2

    @property
    def cos_sin_factor(self) -> float:
        """What cos and sin are multiplied by: ``m(factor, mscale) /
        m(factor, mscale_all_dim)``."""
        return (self.m(self.factor, self.mscale)
                / self.m(self.factor, self.mscale_all_dim))


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: Literal["dense", "moe", "hybrid", "vlm", "audio", "ssm"]
    n_layers: int
    d_model: int
    n_heads: int
    kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0  # 0 -> d_model // n_heads

    # norm / positional
    norm: Literal["rmsnorm", "layernorm", "nonparametric_ln"] = "rmsnorm"
    qk_norm: bool = False
    rope: Literal["rope", "mrope", "none"] = "rope"
    rope_theta: float = 10000.0
    mrope_sections: tuple[int, int, int] = (16, 24, 24)
    #: YaRN context extension of the MLA rope (None: plain rope)
    rope_scaling: YaRN | None = None

    # attention
    attn: Literal["gqa", "mla", "none"] = "gqa"
    causal: bool = True
    window: int | None = None  # sliding-window attention
    qkv_bias: bool = False
    # MLA (deepseek-v2)
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128

    # mlp
    mlp: Literal["swiglu", "gelu", "moe"] = "swiglu"
    mlp_bias: bool = False
    # MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    d_ff_expert: int = 0
    first_dense_layers: int = 0
    #: False: the gates are the top-k softmax probabilities as they are
    #: (DeepSeek-V2-Lite); True: renormalised to sum to one (Moonlight)
    norm_topk_prob: bool = True
    #: the routed experts this chip holds, [expert_offset, expert_offset +
    #: experts_held) of n_experts (0: all of them); the layer computes only
    #: their part of the result, the router still scores all n_experts
    experts_held: int = 0
    expert_offset: int = 0
    moe_impl: str = "local"  # "local" | "ep_psum" (launch overrides for pods)
    capacity_factor: float = 1.25

    # hybrid SSM heads (hymba)
    parallel_ssm: bool = False
    ssm_state: int = 16
    ssm_expand: int = 2
    # rwkv
    rwkv: bool = False
    rwkv_head_dim: int = 64

    # io
    input_mode: Literal["tokens", "embeds"] = "tokens"
    tie_embeddings: bool = False

    # execution
    scan_layers: bool = True
    remat: Literal["none", "full", "dots"] = "none"
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    # sequence parallelism: shard the residual stream's sequence axis over
    # "model" between blocks (Megatron-SP style; GSPMD inserts the
    # all-gather/reduce-scatter pairs).  Cuts per-layer saved activations by
    # the TP degree — the §Perf lever for the large dense train cells.
    sequence_parallel: bool = False

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)

    # -- derived sub-configs ------------------------------------------------

    def attn_config(self):
        from repro.nn.attention import AttnConfig

        return AttnConfig(
            d_model=self.d_model,
            n_heads=self.n_heads,
            kv_heads=self.kv_heads,
            head_dim=self.head_dim,
            causal=self.causal,
            qk_norm=self.qk_norm,
            window=self.window,
            rope=self.rope,
            rope_theta=self.rope_theta,
            mrope_sections=self.mrope_sections,
            qkv_bias=self.qkv_bias,
        )

    def mla_config(self):
        from repro.nn.attention import MLAConfig

        return MLAConfig(
            d_model=self.d_model,
            n_heads=self.n_heads,
            kv_lora_rank=self.kv_lora_rank,
            qk_nope_dim=self.qk_nope_dim,
            qk_rope_dim=self.qk_rope_dim,
            v_head_dim=self.v_head_dim,
            rope_theta=self.rope_theta,
            rope_scaling=self.rope_scaling,
        )

    def moe_config(self):
        from repro.nn.moe import MoEConfig

        return MoEConfig(
            d_model=self.d_model,
            d_ff_expert=self.d_ff_expert,
            n_experts=self.n_experts,
            top_k=self.top_k,
            n_shared=self.n_shared_experts,
            capacity_factor=self.capacity_factor,
            impl=self.moe_impl,
            norm_topk_prob=self.norm_topk_prob,
            experts_held=self.experts_held,
            expert_offset=self.expert_offset,
        )

    def ssm_config(self):
        from repro.nn.ssm import SSMConfig

        return SSMConfig(
            d_model=self.d_model,
            d_inner=self.ssm_expand * self.d_model,
            d_state=self.ssm_state,
        )

    def rwkv_config(self):
        from repro.nn.rwkv import RWKVConfig

        return RWKVConfig(
            d_model=self.d_model, d_ff=self.d_ff, head_dim=self.rwkv_head_dim
        )

    # -- bookkeeping ---------------------------------------------------------

    @property
    def sub_quadratic(self) -> bool:
        """Eligible for long_500k (SSM / linear-attn / windowed hybrids)."""
        return self.rwkv or (self.parallel_ssm and self.window is not None)

    @property
    def has_decode(self) -> bool:
        return self.causal  # encoder-only archs have no decode step

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks + head)."""
        d, ff, v, L = self.d_model, self.d_ff, self.vocab, self.n_layers
        hd = self.head_dim
        emb = v * d * (1 if self.tie_embeddings else 2)
        if self.rwkv:
            tm = d * d * 5 + d * (5 * 32 + 5 * 32) + d * 64 * 2 + 2 * d
            cm = d * ff * 2 + d * d
            return emb + L * (tm + cm + 4 * d)
        if self.attn == "mla":
            attn = (
                d * self.n_heads * (self.qk_nope_dim + self.qk_rope_dim)
                + d * (self.kv_lora_rank + self.qk_rope_dim)
                + self.kv_lora_rank * self.n_heads * (self.qk_nope_dim + self.v_head_dim)
                + self.n_heads * self.v_head_dim * d
            )
        else:
            attn = d * self.n_heads * hd + 2 * d * self.kv_heads * hd + self.n_heads * hd * d
        if self.mlp == "moe":
            moe_l = (
                3 * d * self.d_ff_expert * self.n_experts
                + 3 * d * self.d_ff_expert * self.n_shared_experts
                + d * self.n_experts
            )
            dense_l = 3 * d * ff
            n_moe = L - self.first_dense_layers
            mlp_total = n_moe * moe_l + self.first_dense_layers * dense_l
        else:
            mlp_total = L * 3 * d * ff
        ssm = 0
        if self.parallel_ssm:
            di = self.ssm_expand * d
            ssm = L * (2 * d * di + di * d + di * (self.ssm_state * 2 + d // 16))
        return emb + L * attn + mlp_total + ssm

    def active_param_count(self) -> int:
        """Activated parameters per token (MoE: only routed top-k)."""
        if self.mlp != "moe":
            return self.param_count()
        full = self.param_count()
        moe_all = 3 * self.d_model * self.d_ff_expert * self.n_experts
        moe_act = 3 * self.d_model * self.d_ff_expert * self.top_k
        n_moe = self.n_layers - self.first_dense_layers
        return full - n_moe * (moe_all - moe_act)

    def reduced(self) -> "ArchConfig":
        """CPU smoke-test variant: same family/structure, tiny dims."""
        return dataclasses.replace(
            self,
            name=self.name + "-reduced",
            n_layers=2 + self.first_dense_layers,
            d_model=64,
            n_heads=4,
            kv_heads=max(1, min(self.kv_heads, 2)),
            head_dim=16,
            d_ff=128,
            vocab=256,
            n_experts=8 if self.n_experts else 0,
            top_k=min(self.top_k, 2) if self.top_k else 0,
            d_ff_expert=32 if self.d_ff_expert else 0,
            kv_lora_rank=32,
            qk_nope_dim=16,
            qk_rope_dim=8,
            v_head_dim=16,
            rwkv_head_dim=16,
            mrope_sections=(2, 3, 3) if self.rope == "mrope" else self.mrope_sections,
            window=min(self.window, 8) if self.window else None,
            remat="none",
            compute_dtype="float32",
            moe_impl="local",
        )
