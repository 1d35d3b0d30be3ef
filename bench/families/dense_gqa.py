"""The dense GQA language model: pre-norm blocks (OLMo's non-parametric
LayerNorm or RMSNorm), rotary embeddings, grouped-query causal attention
through q/k/v/o projections, a SwiGLU MLP (gate, up, down), and a head
tied to the embedding.  OLMo-1B and Granite-8B-Code have this layout.

The tree is ``embed``, layer-stacked ``blocks`` and ``final_norm``.  The
reference runs layer by layer (a scan over the stacked layers); the
packed layers are the seven projections of each block, and the embedding
and the tied head stay float, as they are served.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import counts
from bench import reference as ref
from bench.weights import make_tree

#: ArchConfig fields a configuration file states, checked against the
#: program's registry entry after the file's overrides
MODEL_KEYS = ("n_layers", "d_model", "n_heads", "kv_heads", "head_dim",
              "d_ff", "vocab", "norm", "rope_theta", "tie_embeddings")
#: the keys the reference is compiled for
_REF_KEYS = ("n_heads", "kv_heads", "head_dim", "norm", "rope_theta")


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def shapes(model: dict) -> dict:
    """Leaf shapes of the tree, keyed like the tree."""
    L, d, f, v = (model["n_layers"], model["d_model"], model["d_ff"],
                  model["vocab"])
    qd = model["n_heads"] * model["head_dim"]
    kvd = model["kv_heads"] * model["head_dim"]
    norm = (lambda *lead: {"scale": (*lead, d)}) \
        if model["norm"] == "rmsnorm" else (lambda *lead: {})
    return {
        "embed": {"table": (v, d)},
        "blocks": {
            "attn_norm": norm(L),
            "mlp_norm": norm(L),
            "attn": {"q": {"w": (L, d, qd)}, "k": {"w": (L, d, kvd)},
                     "v": {"w": (L, d, kvd)}, "o": {"w": (L, qd, d)}},
            "mlp": {"gate": {"w": (L, d, f)}, "up": {"w": (L, d, f)},
                    "down": {"w": (L, f, d)}},
        },
        "final_norm": norm(),
    }


def leaf(key, path: str, shp: tuple, dt):
    """The usual initialisation of such a model: norm scales of one, a
    normal embedding of standard deviation 0.02, and truncated-normal
    projections scaled by the inverse square root of their fan-in, so that
    activations keep a realistic scale through the depth."""
    if path.endswith("['scale']"):
        return jnp.ones(shp, dt)
    if path.startswith("['embed']"):
        return (jax.random.normal(key, shp, jnp.float32) * 0.02).astype(dt)
    fan_in = shp[-2]
    w = jax.random.truncated_normal(key, -2.0, 2.0, shp, jnp.float32)
    return (w * fan_in ** -0.5).astype(dt)


def make(model: dict, seed: int, dtype: str = "float32") -> dict:
    """The weight tree of ``model`` from ``seed``, in ``dtype``."""
    return make_tree(shapes(model), seed, dtype, leaf)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def _layer(x, lw, model: dict, bits):
    t = x.shape[0]
    h, hkv, hd = model["n_heads"], model["kv_heads"], model["head_dim"]
    pos = jnp.arange(t)
    a_in = ref.norm(x, lw["attn_norm"].get("scale"), model["norm"])
    q = ref.dense(a_in, lw["attn"]["q"]["w"], bits).reshape(t, h, hd)
    k = ref.dense(a_in, lw["attn"]["k"]["w"], bits).reshape(t, hkv, hd)
    v = ref.dense(a_in, lw["attn"]["v"]["w"], bits).reshape(t, hkv, hd)
    q = ref.rope(q, pos, model["rope_theta"])
    k = ref.rope(k, pos, model["rope_theta"])
    x = x + ref.dense(ref.attention(q, k, v), lw["attn"]["o"]["w"], bits)
    m_in = ref.norm(x, lw["mlp_norm"].get("scale"), model["norm"])
    g = ref.dense(m_in, lw["mlp"]["gate"]["w"], bits)
    u = ref.dense(m_in, lw["mlp"]["up"]["w"], bits)
    return x + ref.dense(jax.nn.silu(g) * u, lw["mlp"]["down"]["w"], bits)


def _logits(weights, tokens, model: dict, bits):
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))
    table = weights["embed"]["table"].astype(jnp.float32)
    x = table[tokens]

    def body(x, lw):
        return _layer(x, f32(lw), model, bits), None

    x, _ = jax.lax.scan(body, x, weights["blocks"])
    x = ref.norm(x, f32(weights["final_norm"]).get("scale"), model["norm"])
    return x @ table.T


@functools.partial(jax.jit, static_argnames=("model_items", "bits"))
def _readings(weights, tokens, picks, model_items, bits):
    with jax.default_matmul_precision(ref.PRECISION):
        logits = _logits(weights, tokens, dict(model_items), bits)
    return ref.picked(logits, picks)


def readings(weights, tokens, picks, model: dict, bits: int | None = None):
    """For one token sequence (length a multiple of ``Q_BLOCK``): at every
    position, the largest logit, the logit of each row of ``picks``
    (shape (n, T): token ids to read at each position), and the token
    ranked first.  Only these leave the device.  ``bits`` makes it the
    control."""
    return _readings(weights, jnp.asarray(tokens, jnp.int32),
                     jnp.asarray(picks, jnp.int32),
                     tuple((k, model[k]) for k in _REF_KEYS), bits)


@functools.partial(jax.jit, static_argnames=("model_items", "bits"))
def _full(weights, tokens, model_items, bits):
    with jax.default_matmul_precision(ref.PRECISION):
        return _logits(weights, tokens, dict(model_items), bits)


def forward(weights, tokens, model: dict, bits: int | None = None):
    """All logits (T, vocab), float32 (for the tests at small sizes)."""
    return _full(weights, jnp.asarray(tokens, jnp.int32),
                 tuple((k, model[k]) for k in _REF_KEYS), bits)


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------


def dense_layers(model: dict) -> list[tuple[str, int, int]]:
    """(name, fan-in K, fan-out N) of one layer's packed projections."""
    d, f = model["d_model"], model["d_ff"]
    qd = model["n_heads"] * model["head_dim"]
    kvd = model["kv_heads"] * model["head_dim"]
    return [("q", d, qd), ("k", d, kvd), ("v", d, kvd), ("o", qd, d),
            ("gate", d, f), ("up", d, f), ("down", f, d)]


def dense_work(model: dict, rows: int, cv: bool) -> tuple[float, float]:
    """(operations, bytes) of every packed dense layer of the model for one
    call of ``rows`` activation rows (``counts.layer_work`` summed)."""
    ops = nbytes = 0.0
    for _, k, n in dense_layers(model):
        o, b = counts.layer_work(rows, k, n, cv)
        ops += o
        nbytes += b
    return ops * model["n_layers"], nbytes * model["n_layers"]


def matmul_params(model: dict) -> int:
    """Parameters of every matmul a token passes: the packed projections of
    every layer and the tied head."""
    per_layer = sum(k * n for _, k, n in dense_layers(model))
    return per_layer * model["n_layers"] + model["d_model"] * model["vocab"]


def nominal_ops(model: dict, tokens: float, context_sum: float) -> float:
    """Nominal model operations of ``tokens`` processed tokens whose live
    contexts sum to ``context_sum`` positions: 2 x the matmul parameters
    per token plus attention over the live context, 2 x 2 x layers x heads
    x head size per context position (scores and weighted values)."""
    attn = 4.0 * model["n_layers"] * model["n_heads"] * model["head_dim"]
    return 2.0 * matmul_params(model) * tokens + attn * context_sum
