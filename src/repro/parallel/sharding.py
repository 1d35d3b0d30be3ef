"""PartitionSpec rules for every architecture in the zoo.

Megatron-style TP on the "model" axis (column-parallel QKV/up projections,
row-parallel O/down), vocab-parallel embeddings/heads, expert-parallel MoE
stacks, head- or sequence-sharded decode caches, and optional FSDP (2D
weight sharding over ("data", "model")) for the large dense train cells.

The engine is shape-aware: `fit_spec` drops any sharding a dimension cannot
honor (e.g. hymba's 32001 vocab is not divisible by 16 -> the embedding
falls back to replicated), so one rule set serves all 10 architectures and
every mesh, including the reduced CPU meshes used in tests.

Batch ("data") sharding composes ("pod", "data") on the multi-pod mesh —
DP across pods (gradient all-reduce over DCN), TP inside a pod (ICI).
"""

from __future__ import annotations

import re
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig


def _dp_axes(mesh: Mesh):
    axes = [a for a in mesh.axis_names if a in ("pod", "data")]
    return tuple(axes) if axes else None


def fit_spec(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Drop sharding on dims the shape cannot honor (non-divisible/too small)."""
    out = []
    for i, entry in enumerate(spec):
        if entry is None or i >= len(shape):
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = int(np.prod([mesh.shape[a] for a in axes]))
        out.append(entry if shape[i] % n == 0 and shape[i] >= n else None)
    return P(*out)


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k))))
                    for k in path)


# ---------------------------------------------------------------------------
# parameter rules
# ---------------------------------------------------------------------------

# (regex on path, spec for the LAST ndims of the leaf).  First match wins.
# Leading (layer-stack / expert) dims are padded with the stack spec.
_COL = "COL"  # (in, out) -> P(maybe_fsdp, "model")
_ROW = "ROW"  # (in, out) -> P("model", maybe_fsdp)

_PARAM_RULES: list[tuple[str, Any]] = [
    # embeddings / heads (vocab-parallel)
    (r"embed/table$", P("model", None)),
    (r"lm_head/w$", P(None, "model")),
    # MoE: router replicated; expert stacks sharded on the expert dim
    (r"router/", P(None, None)),
    (r"experts/(gate|up)/w$", P("model", None, None)),
    (r"experts/down/w$", P("model", None, None)),
    # attention projections (qkv = the fan-out-fused Q|K|V group: its
    # concatenated output axis is column-parallel exactly like the members)
    (r"attn/(q|k|v|qkv)/w$", _COL),
    (r"attn/o/w$", _ROW),
    (r"attn/(q|k|v|qkv)/b$", P("model")),
    (r"attn/kv_a/", P(None, None)),  # tiny latent projection: replicate
    (r"attn/kv_b/w$", _COL),
    # MLPs (gateup = the fused gate|up group, column-parallel like members)
    (r"(mlp|shared)/(gate|up|gateup)/w$", _COL),
    (r"(mlp|shared)/down/w$", _ROW),
    (r"(mlp|shared)/(up|gateup)/b$", P("model")),
    (r"(mlp|shared)/down/b$", P(None)),
    # SSM (d_inner sharded on model)
    (r"ssm/in_proj/w$", _COL),
    (r"ssm/out_proj/w$", _ROW),
    (r"ssm/conv_w$", P(None, "model")),
    (r"ssm/conv_b$", P("model")),
    (r"ssm/x_proj/w$", P("model", None)),
    (r"ssm/dt_proj/w$", P(None, "model")),
    (r"ssm/dt_proj/b$", P("model")),
    (r"ssm/a_log$", P("model", None)),
    (r"ssm/d_skip$", P("model")),
    # RWKV time/channel mix
    (r"tm/(r|k|v|g)/w$", _COL),
    (r"tm/out/w$", _ROW),
    (r"tm/bonus$", P("model", None)),
    # decay-LoRA output + per-head norm scales sharded on "model": keeps the
    # (B, T, D) f32 decay tensors/cotangents head-sharded end to end — the
    # replicated versions forced ~22 (B,T,D) f32 all-gathers per layer
    # (EXPERIMENTS.md §Perf, rwkv6 iteration 3)
    (r"tm/decay_w2$", P(None, "model")),
    (r"tm/ln_x_(scale|bias)$", P("model")),
    (r"cm/key/w$", _COL),
    (r"cm/value/w$", _ROW),
    (r"cm/receptance/w$", _COL),
]


def _base_spec(path: str, ndim: int, fsdp: bool):
    for pat, spec in _PARAM_RULES:
        if re.search(pat, path):
            if spec == _COL:
                return P("data" if fsdp else None, "model")
            if spec == _ROW:
                return P("model", "data" if fsdp else None)
            return spec
    return None  # replicate


def _packed_leaf_spec(path: str, ndim: int, fsdp: bool):
    """Specs for QuantizedDense / PackedLinear leaves: derive from the parent
    linear's (in, out) rule.  Weight-shaped operands (w_q, the blocked
    serving codes, folded A/B matrices) shard like w; per-output vectors
    (c, c0, sum_qw, bias, epilogue table, fold delta) shard like the out
    dim; scalars/meta replicate."""
    m = re.search(
        r"(.*)/(pack|a_qp|blocked|fold)/"
        r"(w_q|sum_qw|c|c0|bias|w_scale|w_zp|scale|zero_point"
        r"|w_qb|epilogue|meta|A|B|delta|sa|za)$", path)
    if not m:
        return None
    parent, _, leaf = m.groups()
    base = _base_spec(parent + "/w", 2, fsdp)
    if base is None:
        return P()
    out_axis = base[1] if len(base) > 1 else None
    if leaf in ("w_q", "A"):
        return base
    if leaf == "w_qb" or leaf == "B":
        # K axis is padded/stacked in tile multiples: shard the out dim only
        return P(None, out_axis)
    if leaf in ("sum_qw", "c", "c0", "bias", "delta"):
        return P(out_axis)
    if leaf == "epilogue":
        return P(None, out_axis)
    return P()  # scalars / meta


def param_shardings(abstract_params: Any, mesh: Mesh, cfg: ArchConfig | None = None,
                    fsdp: bool = False, dp_only: bool = False) -> Any:
    """NamedSharding tree for a (possibly packed/stacked) parameter tree.

    dp_only: ZeRO-3 layout — every weight 1D-sharded over ALL mesh axes
    combined, no tensor parallelism.  The right layout for small
    attention-free models where TP activation collectives dominate
    (EXPERIMENTS.md §Perf, rwkv6 cell)."""
    all_axes = tuple(mesh.axis_names)

    def leaf_spec(path, leaf):
        pstr = _path_str(path)
        ndim = len(leaf.shape)
        if dp_only:
            if ndim >= 2:
                # shard the largest trailing dim over the flat mesh
                dims = list(leaf.shape)
                target = int(np.argmax(dims))
                spec = P(*(all_axes if i == target else None for i in range(ndim)))
            elif ndim == 1:
                spec = P(all_axes)
            else:
                spec = P()
            return NamedSharding(mesh, fit_spec(spec, leaf.shape, mesh))
        spec = _packed_leaf_spec(pstr, ndim, fsdp)
        if spec is None:
            spec = _base_spec(pstr, ndim, fsdp)
        if spec is None:
            spec = P()
        # pad leading stacked dims (layer stacks / per-layer packs)
        if len(spec) < ndim:
            spec = P(*((None,) * (ndim - len(spec)) + tuple(spec)))
        spec = fit_spec(spec, leaf.shape, mesh)
        return NamedSharding(mesh, spec)

    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_params)
    return jax.tree_util.tree_unflatten(treedef, [leaf_spec(p, l) for p, l in flat])


# ---------------------------------------------------------------------------
# batch / cache rules
# ---------------------------------------------------------------------------


def batch_shardings(abstract_batch: Any, mesh: Mesh, dp_only: bool = False) -> Any:
    """Shard the leading (batch) dim over ("pod","data") — or over ALL axes
    in dp_only (ZeRO-3) mode; positions for M-RoPE are (3, B, T) -> batch is
    dim 1."""
    dp = tuple(mesh.axis_names) if dp_only else _dp_axes(mesh)

    def leaf_spec(path, leaf):
        pstr = _path_str(path)
        shape = leaf.shape
        if dp is None or not shape:
            return NamedSharding(mesh, P())
        if pstr.endswith("positions") and len(shape) == 3:
            spec = P(None, dp, None)
        else:
            spec = P(*((dp,) + (None,) * (len(shape) - 1)))
        return NamedSharding(mesh, fit_spec(spec, shape, mesh))

    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_batch)
    return jax.tree_util.tree_unflatten(treedef, [leaf_spec(p, l) for p, l in flat])


def cache_shardings(abstract_cache: Any, mesh: Mesh, cfg: ArchConfig) -> Any:
    """Decode-cache shardings.

    GQA k/v (L, B, H, S, d): heads on "model" when divisible, else the
    SEQUENCE is sharded on "model" (attention then computes partial scores
    per shard and GSPMD inserts the softmax all-reduces — the
    collective-bound decode baseline discussed in EXPERIMENTS.md).
    MLA latent (L, B, S, r): sequence on "model" (no head dim exists).
    SSM / RWKV states: inner/head dims on "model".
    """
    dp = _dp_axes(mesh)
    msize = mesh.shape["model"]
    heads_ok = cfg.kv_heads % msize == 0

    def leaf_spec(path, leaf):
        pstr = _path_str(path)
        shape = leaf.shape
        if not shape:
            return NamedSharding(mesh, P())
        if re.search(r"(dense_)?(k|v)$", pstr) and len(shape) == 5:
            spec = (P(None, dp, "model", None, None) if heads_ok
                    else P(None, dp, None, "model", None))
        elif re.search(r"(dense_)?latent$", pstr):
            spec = P(None, dp, "model", None)
        elif re.search(r"(dense_)?rope$", pstr):
            spec = P(None, dp, "model", None)
        elif pstr.endswith("ssm_conv"):
            spec = P(None, dp, None, "model")
        elif pstr.endswith("ssm_h"):
            spec = P(None, dp, "model", None)
        elif pstr.endswith("wkv"):
            spec = P(None, dp, "model", None, None)
        elif pstr.endswith("shift_tm") or pstr.endswith("shift_cm"):
            spec = P(None, dp, None)
        else:
            spec = P()
        return NamedSharding(mesh, fit_spec(spec, shape, mesh))

    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_cache)
    return jax.tree_util.tree_unflatten(treedef, [leaf_spec(p, l) for p, l in flat])
