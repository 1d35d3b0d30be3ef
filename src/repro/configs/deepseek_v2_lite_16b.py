"""deepseek-v2-lite-16b [moe]: 27L d_model=2048 16H d_ff_expert=1408
vocab=102400, MLA kv_lora=512 (no q_lora), MoE 64 routed experts top-6 + 2
shared, first layer dense (d_ff 10944).  [arXiv:2405.04434; hf]

The rope is YaRN-scaled (factor 40 over an original context of 4096,
beta_fast 32, beta_slow 1, mscale = mscale_all_dim = 0.707), which also
multiplies the softmax scale by m(40, 0.707)**2.  Routing: softmax over the
64 experts, greedy top-6, the gates not renormalised
(``norm_topk_prob: false``, ``routed_scaling_factor`` 1).

The rope columns use the rotate-half convention; DeepSeek's checkpoint
interleaves them, a fixed permutation of the rope columns of ``q`` and
``kv_a`` that loading a real checkpoint would apply.
"""

from repro.configs.base import ArchConfig, YaRN

CONFIG = ArchConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    kv_heads=16,
    d_ff=10944,          # the first (dense) layer's FFN width
    vocab=102400,
    rope_theta=10000.0,
    rope_scaling=YaRN(factor=40.0, original_max_position_embeddings=4096,
                      beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                      mscale_all_dim=0.707),
    attn="mla",
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    mlp="moe",
    n_experts=64,
    top_k=6,
    n_shared_experts=2,
    d_ff_expert=1408,
    first_dense_layers=1,
    norm_topk_prob=False,
    remat="full",
)
