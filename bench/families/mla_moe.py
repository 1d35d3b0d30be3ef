"""DeepSeek-V2's layout: multi-head latent attention (MLA) with YaRN rope,
one or more leading dense SwiGLU layers, then mixture-of-experts layers of
a softmax router over every routed expert, greedy top-k, shared experts
always on.  DeepSeek-V2-Lite has this layout.

The tree is ``embed``, ``dense_blocks`` (a list, one per leading dense
layer), layer-stacked ``blocks`` (the MoE layers), ``final_norm`` and an
untied ``lm_head``.  A chip of an expert-parallel deployment holds
``experts_held`` of the ``n_experts`` routed experts, from
``expert_offset``: its expert stacks have that many rows, its router all
``n_experts`` outputs.

The reference is written from the published equations
(arXiv:2405.04434 and the model's ``config.json``), in float32:

- MLA in its decompressed form: ``q = x W_q`` split into a no-position
  part and a rope part; ``[c, k_pe] = x W_kva``, ``c`` RMS-normed;
  ``[k_nope, v] = c W_kvb`` per head; keys ``[k_nope, rope(k_pe)]`` with
  the rope part shared by the heads; softmax scale ``(d_nope +
  d_rope)**-0.5 * m(factor, mscale_all_dim)**2``;
- YaRN rope: each frequency blended with itself over ``factor`` by a ramp
  over the correction range, cos and sin times ``m(factor, mscale) /
  m(factor, mscale_all_dim)``, ``m(s, a) = 0.1 a ln s + 1``, rotate-half
  columns;
- MoE: ``shared(x) + sum over held e of g_e(x) E_e(x)``, ``g_e`` the
  softmax probability over all ``n_experts`` where e is among the top-k,
  renormalised over the top-k only under ``norm_topk_prob``, 0 otherwise:
  the part of the layer that the held experts give;
- ``bits``, for the control: every layer the serving packs (q, o, the
  dense and expert SwiGLUs, the head) fake-quantized; the router,
  ``kv_a``, ``kv_b``, the norms and the embedding stay float, as served.

It imports nothing of the program.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench import counts
from bench import reference as ref
from bench.families.dense_gqa import leaf
from bench.weights import make_tree

#: ArchConfig fields a configuration file states, checked against the
#: program's registry entry after the file's overrides
MODEL_KEYS = ("n_layers", "d_model", "n_heads", "kv_lora_rank",
              "qk_nope_dim", "qk_rope_dim", "v_head_dim", "rope_theta",
              "rope_scaling", "first_dense_layers", "d_ff", "n_experts",
              "top_k", "n_shared_experts", "d_ff_expert", "norm_topk_prob",
              "experts_held", "expert_offset", "vocab", "norm",
              "tie_embeddings")
#: the keys the reference is compiled for
_REF_KEYS = ("n_heads", "kv_lora_rank", "qk_nope_dim", "qk_rope_dim",
             "v_head_dim", "rope_theta", "n_experts", "top_k",
             "norm_topk_prob", "experts_held", "expert_offset")


def held(model: dict) -> int:
    """Routed experts this chip holds."""
    return model["experts_held"] or model["n_experts"]


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _attn_shapes(model: dict, *lead) -> dict:
    d, h = model["d_model"], model["n_heads"]
    r, dn, dr, dv = (model["kv_lora_rank"], model["qk_nope_dim"],
                     model["qk_rope_dim"], model["v_head_dim"])
    return {"q": {"w": (*lead, d, h * (dn + dr))},
            "kv_a": {"w": (*lead, d, r + dr)},
            "kv_a_norm": {"scale": (*lead, r)},
            "kv_b": {"w": (*lead, r, h * (dn + dv))},
            "o": {"w": (*lead, h * dv, d)}}


def _swiglu_shapes(d: int, f: int, *lead) -> dict:
    return {"gate": {"w": (*lead, d, f)}, "up": {"w": (*lead, d, f)},
            "down": {"w": (*lead, f, d)}}


def shapes(model: dict) -> dict:
    """Leaf shapes of the tree, keyed like the tree."""
    d, v = model["d_model"], model["vocab"]
    n_moe = model["n_layers"] - model["first_dense_layers"]
    fe, e = model["d_ff_expert"], held(model)

    def norms(*lead):
        return {"attn_norm": {"scale": (*lead, d)},
                "mlp_norm": {"scale": (*lead, d)}}

    return {
        "embed": {"table": (v, d)},
        "dense_blocks": [
            {**norms(), "attn": _attn_shapes(model),
             "mlp": _swiglu_shapes(d, model["d_ff"])}
            for _ in range(model["first_dense_layers"])],
        "blocks": {
            **norms(n_moe),
            "attn": _attn_shapes(model, n_moe),
            "mlp": {
                "router": {"w": (n_moe, d, model["n_experts"])},
                "experts": _swiglu_shapes(d, fe, n_moe, e),
                "shared": _swiglu_shapes(
                    d, model["n_shared_experts"] * fe, n_moe),
            },
        },
        "final_norm": {"scale": (d,)},
        "lm_head": {"w": (d, v)},
    }


def make(model: dict, seed: int, dtype: str = "float32") -> dict:
    """The weight tree of ``model`` from ``seed``, in ``dtype`` (the dense
    family's leaf rule: norm scales of one, a normal embedding of standard
    deviation 0.02, truncated-normal projections over the square root of
    their fan-in)."""
    return make_tree(shapes(model), seed, dtype, leaf)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def yarn_m(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_range(dim: int, theta: float, yarn: dict) -> tuple[int, int]:
    """The correction range ``(floor(corr(beta_fast)),
    ceil(corr(beta_slow)))`` clamped to [0, dim - 1], with
    ``corr(r) = dim ln(L / (2 pi r)) / (2 ln theta)``, L the original
    context."""
    def corr(r):
        return (dim * math.log(yarn["original_max_position_embeddings"]
                               / (2 * math.pi * r)) / (2 * math.log(theta)))

    return (max(math.floor(corr(yarn["beta_fast"])), 0),
            min(math.ceil(corr(yarn["beta_slow"])), dim - 1))


def softmax_scale(model: dict) -> float:
    scale = (model["qk_nope_dim"] + model["qk_rope_dim"]) ** -0.5
    yarn = model.get("rope_scaling")
    if yarn and yarn.get("mscale_all_dim"):
        scale *= yarn_m(yarn["factor"], yarn["mscale_all_dim"]) ** 2
    return scale


def rope(x, pos, model: dict):
    """YaRN rope of the last axis of x: (T, H, dim), rotate-half."""
    dim = x.shape[-1]
    theta = model["rope_theta"]
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    yarn = model.get("rope_scaling")
    mul = 1.0
    if yarn:
        low, high = yarn_range(dim, theta, yarn)
        ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                        / max(high - low, 1e-3), 0.0, 1.0)
        inv = inv / yarn["factor"] * ramp + inv * (1.0 - ramp)
        mul = (yarn_m(yarn["factor"], yarn["mscale"])
               / yarn_m(yarn["factor"], yarn["mscale_all_dim"]))
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = (jnp.cos(ang) * mul)[:, None], (jnp.sin(ang) * mul)[:, None]
    x1, x2 = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, scale: float):
    """Causal attention, q and k (T, H, dq), v (T, H, dv), query rows in
    blocks of ``Q_BLOCK``."""
    t, h, _ = q.shape
    qb = q.reshape(t // ref.Q_BLOCK, ref.Q_BLOCK, h, q.shape[-1])
    kpos = jnp.arange(t)

    def block(inp):
        qi, i = inp
        s = jnp.einsum("qhd,khd->hqk", qi, k) * scale
        qpos = i * ref.Q_BLOCK + jnp.arange(ref.Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    ctx = jax.lax.map(block, (qb, jnp.arange(t // ref.Q_BLOCK)))
    return ctx.reshape(t, h * v.shape[-1])


def mla(x, aw, model: dict, bits):
    """Decompressed MLA over one sequence x (T, D)."""
    t = x.shape[0]
    h, r = model["n_heads"], model["kv_lora_rank"]
    dn, dr, dv = model["qk_nope_dim"], model["qk_rope_dim"], model["v_head_dim"]
    pos = jnp.arange(t)
    q = ref.dense(x, aw["q"]["w"], bits).reshape(t, h, dn + dr)
    kva = x @ aw["kv_a"]["w"]
    c = ref.norm(kva[:, :r], aw["kv_a_norm"]["scale"], "rmsnorm")
    k_pe = rope(kva[:, None, r:], pos, model)  # (T, 1, dr)
    kv = (c @ aw["kv_b"]["w"]).reshape(t, h, dn + dv)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, model)], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (t, h, dr))],
                        -1)
    ctx = attention(q, k, kv[..., dn:], softmax_scale(model))
    return ref.dense(ctx, aw["o"]["w"], bits)


def swiglu(x, mw, bits):
    g = ref.dense(x, mw["gate"]["w"], bits)
    u = ref.dense(x, mw["up"]["w"], bits)
    return ref.dense(jax.nn.silu(g) * u, mw["down"]["w"], bits)


def gates(x, router_w, model: dict):
    """(T, held) gate of each held expert: its softmax probability over all
    ``n_experts`` where it is among the top-k, else 0."""
    probs = jax.nn.softmax(x @ router_w, -1)  # (T, n_experts)
    top, idx = jax.lax.top_k(probs, model["top_k"])
    if model["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    g = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], idx].set(top)
    e0 = model["expert_offset"]
    return g[:, e0:e0 + held(model)]


def moe(x, mw, model: dict, bits):
    """shared(x) + sum over the held experts of gate * expert(x)."""
    g = gates(x, mw["router"]["w"], model)

    def one(acc, inp):
        ew, ge = inp
        return acc + ge[:, None] * swiglu(x, ew, bits), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(x), (mw["experts"], g.T))
    return swiglu(x, mw["shared"], bits) + routed


def _layer(x, lw, model: dict, bits, is_moe: bool):
    x = x + mla(ref.norm(x, lw["attn_norm"]["scale"], "rmsnorm"),
                lw["attn"], model, bits)
    m_in = ref.norm(x, lw["mlp_norm"]["scale"], "rmsnorm")
    return x + (moe(m_in, lw["mlp"], model, bits) if is_moe
                else swiglu(m_in, lw["mlp"], bits))


def _logits(weights, tokens, model: dict, bits):
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))
    x = weights["embed"]["table"].astype(jnp.float32)[tokens]
    for lw in weights["dense_blocks"]:
        x = _layer(x, f32(lw), model, bits, False)

    def body(x, lw):
        return _layer(x, f32(lw), model, bits, True), None

    x, _ = jax.lax.scan(body, x, weights["blocks"])
    x = ref.norm(x, weights["final_norm"]["scale"].astype(jnp.float32),
                 "rmsnorm")
    return ref.dense(x, weights["lm_head"]["w"].astype(jnp.float32), bits)


def _items(model: dict) -> tuple:
    """The reference's static keys, hashable (the YaRN group as pairs)."""
    yarn = model.get("rope_scaling")
    return tuple((k, model[k]) for k in _REF_KEYS) + (
        ("rope_scaling", tuple(sorted(yarn.items())) if yarn else None),)


def _model(items: tuple) -> dict:
    model = dict(items)
    if model["rope_scaling"] is not None:
        model["rope_scaling"] = dict(model["rope_scaling"])
    return model


@functools.partial(jax.jit, static_argnames=("model_items", "bits"))
def _readings(weights, tokens, picks, model_items, bits):
    with jax.default_matmul_precision(ref.PRECISION):
        logits = _logits(weights, tokens, _model(model_items), bits)
    return ref.picked(logits, picks)


def readings(weights, tokens, picks, model: dict, bits: int | None = None):
    """For one token sequence (length a multiple of ``Q_BLOCK``): at every
    position, the largest logit, the logit of each row of ``picks``
    (shape (n, T)), and the token ranked first.  ``bits`` makes it the
    control."""
    return _readings(weights, jnp.asarray(tokens, jnp.int32),
                     jnp.asarray(picks, jnp.int32), _items(model), bits)


@functools.partial(jax.jit, static_argnames=("model_items", "bits"))
def _full(weights, tokens, model_items, bits):
    with jax.default_matmul_precision(ref.PRECISION):
        return _logits(weights, tokens, _model(model_items), bits)


def forward(weights, tokens, model: dict, bits: int | None = None):
    """All logits (T, vocab), float32 (for the tests at small sizes)."""
    return _full(weights, jnp.asarray(tokens, jnp.int32), _items(model),
                 bits)


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------


def routed_share(model: dict) -> float:
    """Held experts a token is routed to, on average: top_k x held /
    n_experts (0.75 for 8 of 64 at top-6)."""
    return model["top_k"] * held(model) / model["n_experts"]


def _packed_layers(model: dict) -> list[tuple[str, int, int, int, float]]:
    """(name, fan-in K, fan-out N, layers, rows per token) of the packed
    layers; a routed expert's rows are its expected share."""
    d, h = model["d_model"], model["n_heads"]
    L, fd = model["n_layers"], model["first_dense_layers"]
    fe, f = model["d_ff_expert"], model["d_ff"]
    fs = model["n_shared_experts"] * fe
    dq = h * (model["qk_nope_dim"] + model["qk_rope_dim"])
    per_expert = routed_share(model) / held(model)
    out = [("q", d, dq, L, 1.0), ("o", h * model["v_head_dim"], d, L, 1.0),
           ("gate", d, f, fd, 1.0), ("up", d, f, fd, 1.0),
           ("down", f, d, fd, 1.0),
           ("shared_gate", d, fs, L - fd, 1.0),
           ("shared_up", d, fs, L - fd, 1.0),
           ("shared_down", fs, d, L - fd, 1.0)]
    n_experts = (L - fd) * held(model)
    out += [("expert_gate", d, fe, n_experts, per_expert),
            ("expert_up", d, fe, n_experts, per_expert),
            ("expert_down", fe, d, n_experts, per_expert),
            ("lm_head", d, model["vocab"], 1, 1.0)]
    return out


def dense_work(model: dict, rows: int, cv: bool) -> tuple[float, float]:
    """(operations, bytes) of every packed layer for one call of ``rows``
    activation rows (``counts.layer_work`` summed).  Each held expert is
    counted at its expected rows, rows x top_k / n_experts (so the routed
    experts together at ``routed_share`` of the rows), each reading all
    its weights."""
    ops = nbytes = 0.0
    for _, k, n, times, share in _packed_layers(model):
        o, b = counts.layer_work(rows * share, k, n, cv)
        ops += o * times
        nbytes += b * times
    return ops, nbytes


def matmul_params(model: dict) -> float:
    """Parameters of the matmuls a token passes, per token: every layer's
    q, kv_a, kv_b (absorbed into the query and the context, the same
    number) and o, the dense SwiGLU, each MoE layer's router, shared
    experts and its routed experts at ``routed_share``, and the head."""
    d, h = model["d_model"], model["n_heads"]
    r, dn, dr, dv = (model["kv_lora_rank"], model["qk_nope_dim"],
                     model["qk_rope_dim"], model["v_head_dim"])
    L, fd = model["n_layers"], model["first_dense_layers"]
    fe = model["d_ff_expert"]
    attn = d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d
    moe_l = (d * model["n_experts"]
             + 3 * d * fe * (model["n_shared_experts"] + routed_share(model)))
    return (L * attn + fd * 3 * d * model["d_ff"] + (L - fd) * moe_l
            + d * model["vocab"])


def attn_ops_per_position(model: dict) -> float:
    """Operations of the absorbed latent attention per query row, layer and
    live position: scores over the latent and the rope part, and the
    context over the latent, 2 x heads x (r + d_rope + r)."""
    r = model["kv_lora_rank"]
    return 2.0 * model["n_heads"] * (2 * r + model["qk_rope_dim"])


def latent_bytes_per_position(model: dict, cache_bytes: int = 2) -> float:
    """Bytes of the latent and rope cache one live position holds per
    layer."""
    return cache_bytes * (model["kv_lora_rank"] + model["qk_rope_dim"])


def nominal_ops(model: dict, tokens: float, context_sum: float) -> float:
    """Nominal model operations of ``tokens`` processed tokens whose live
    contexts sum to ``context_sum`` positions: 2 x the matmul parameters
    per token plus the absorbed latent attention over the live context."""
    return (2.0 * matmul_params(model) * tokens
            + model["n_layers"] * attn_ops_per_position(model) * context_sum)


def expert_work(model: dict, pairs: float, groups: float,
                cv: bool) -> tuple[float, float]:
    """(operations, bytes) of the held experts' grouped products for
    ``pairs`` (token, held expert) rows over ``groups`` (layer, expert)
    groups given a row: ``counts.layer_work`` on the pairs for the gate,
    up and down products, with the weight codes (and per-column constants)
    read once per group."""
    d, fe = model["d_model"], model["d_ff_expert"]
    ops = nbytes = 0.0
    for k, n in ((d, fe), (d, fe), (fe, d)):
        o, b = counts.layer_work(pairs, k, n, cv)
        _, w = counts.layer_work(0, k, n, cv)  # the weights' bytes alone
        ops += o
        nbytes += b - w + groups * w
    return ops, nbytes
