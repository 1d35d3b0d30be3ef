"""Mixture-of-Experts layer: shared experts + top-k routed experts with
dropless sort-based grouped GEMM, and expert parallelism via shard_map.

Routing follows DeepSeek-V2-lite / Moonlight: softmax router, top-k (k=6)
over 64 routed experts with renormalized gates, plus always-on shared
experts.

Execution strategies (cfg-selected, identical math):

  local      all experts on every device: sort tokens by expert ->
             `jax.lax.ragged_dot` grouped GEMM -> unsort.  Used on single
             host and as the per-shard body under EP.
  ep_psum    expert stacks sharded over the "model" mesh axis inside
             shard_map.  Each shard selects the (token, expert) pairs that
             hit its local experts (capacity-bounded, GShard-style drops),
             runs the local grouped GEMM, scatter-adds into the local token
             buffer and psums over "model".  Comm = one all-reduce of the
             token activations per MoE layer — the collective-bound baseline
             the §Perf hillclimb attacks with the a2a dispatch variant.

The router stays float (policy functions skip "router"); expert weight
stacks are (E, k, n) linear leaves, so `pack_params` gives every expert its
OWN quant scales and control-variate constants — the per-expert CV noted in
DESIGN.md.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.core.approx_linear import dense, init_dense
from repro.nn.layers import init_swiglu, swiglu


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff_expert: int  # per-expert FFN width (1408 for dsv2-lite)
    n_experts: int  # routed experts
    top_k: int
    n_shared: int = 0  # shared experts (width = n_shared * d_ff_expert)
    capacity_factor: float = 1.25
    impl: str = "local"  # "local" | "ep_psum"
    ep_axis: str = "model"


def init_moe(key, cfg: MoEConfig, dtype=jnp.float32) -> dict:
    kr, ks, kg, ku, kd = jax.random.split(key, 5)
    d, f, e = cfg.d_model, cfg.d_ff_expert, cfg.n_experts
    scale = d**-0.5
    p = {
        "router": init_dense(kr, d, e, bias=False, dtype=jnp.float32),
        "experts": {
            "gate": {"w": (jax.random.normal(kg, (e, d, f)) * scale).astype(dtype)},
            "up": {"w": (jax.random.normal(ku, (e, d, f)) * scale).astype(dtype)},
            "down": {"w": (jax.random.normal(kd, (e, f, d)) * (f**-0.5)).astype(dtype)},
        },
    }
    if cfg.n_shared:
        p["shared"] = init_swiglu(ks, d, cfg.n_shared * f, dtype)
    return p


def _route(p: dict, x_flat: jax.Array, cfg: MoEConfig):
    """Top-k routing with renormalized gates.  x_flat: (N, D)."""
    logits = dense(p["router"], x_flat.astype(jnp.float32), name="router")
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)  # (N, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx, probs


def _expert_ffn_sorted(experts: dict, xs: jax.Array, group_sizes: jax.Array):
    """Grouped swiglu over expert-sorted rows via ragged_dot.

    xs: (M, D) rows sorted by expert; group_sizes: (E_local,).
    Supports float expert stacks; packed (approximate) stacks run through
    the grouped approximate matmul in repro.core (quantized expert path).
    """
    from repro.core.approx_linear import QuantizedDense

    if isinstance(experts["gate"], QuantizedDense):
        from repro.core.grouped_approx import grouped_quantized_swiglu

        return grouped_quantized_swiglu(experts, xs, group_sizes)
    g = jax.lax.ragged_dot(xs, experts["gate"]["w"], group_sizes)
    u = jax.lax.ragged_dot(xs, experts["up"]["w"], group_sizes)
    h = jax.nn.silu(g) * u
    return jax.lax.ragged_dot(h, experts["down"]["w"], group_sizes)


def _moe_local(p: dict, x_flat: jax.Array, cfg: MoEConfig,
               e_start: int, e_local: int, capacity: int | None):
    """Dropless (or capacity-bounded) MoE over experts [e_start, e_start+e_local).

    Returns the combined routed-expert output for the local token buffer.
    """
    n, d = x_flat.shape
    k = cfg.top_k
    gates, idx, _ = _route(p, x_flat, cfg)

    pair_expert = idx.reshape(-1)  # (N*k,)
    pair_gate = gates.reshape(-1)
    pair_token = jnp.repeat(jnp.arange(n), k)

    local = (pair_expert >= e_start) & (pair_expert < e_start + e_local)
    # sort pairs: non-local pairs pushed to the end, locals ordered by expert
    sort_key = jnp.where(local, pair_expert - e_start, e_local)
    order = jnp.argsort(sort_key, stable=True)
    if capacity is not None and capacity < order.shape[0]:
        order = order[:capacity]
    sel_expert = sort_key[order]  # e_local == "dropped/non-local"
    sel_valid = sel_expert < e_local
    sel_token = pair_token[order]
    sel_gate = jnp.where(sel_valid, pair_gate[order], 0.0)

    xs = x_flat[sel_token]  # (M, D) gather
    group_sizes = jnp.bincount(
        jnp.where(sel_valid, sel_expert, e_local), length=e_local + 1
    )[:e_local].astype(jnp.int32)
    ys = _expert_ffn_sorted(p["experts"], xs, group_sizes)
    ys = ys * sel_gate[:, None].astype(ys.dtype)
    out = jnp.zeros((n, d), ys.dtype).at[sel_token].add(
        jnp.where(sel_valid[:, None], ys, 0.0)
    )
    return out


def moe_apply(p: dict, x: jax.Array, cfg: MoEConfig, mesh=None) -> jax.Array:
    """x: (B, T, D) -> (B, T, D)."""
    b, t, d = x.shape
    x_flat = x.reshape(-1, d)

    if cfg.impl == "local" or mesh is None:
        routed = _moe_local(p, x_flat, cfg, 0, cfg.n_experts, None)
    elif cfg.impl == "ep_psum":
        routed = _moe_ep_psum(p, x_flat, cfg, mesh)
    else:
        raise ValueError(cfg.impl)

    out = routed.reshape(b, t, d).astype(x.dtype)
    if "shared" in p:
        out = out + swiglu(p["shared"], x)
    return out


def _moe_ep_psum(p: dict, x_flat: jax.Array, cfg: MoEConfig, mesh) -> jax.Array:
    """Expert-parallel execution: experts sharded over cfg.ep_axis."""
    ep = cfg.ep_axis
    n_shards = mesh.shape[ep]
    assert cfg.n_experts % n_shards == 0, (cfg.n_experts, n_shards)
    e_local = cfg.n_experts // n_shards

    data_axes = tuple(a for a in mesh.axis_names if a != ep)

    def shard_fn(router, experts, xl):
        shard_id = jax.lax.axis_index(ep)
        n_loc = xl.shape[0]
        cap = int(n_loc * cfg.top_k * cfg.capacity_factor / n_shards)
        cap = max(cap, cfg.top_k)
        p_loc = {"router": router, "experts": experts}
        out = _moe_local(p_loc, xl, cfg, shard_id * e_local, e_local, cap)
        return jax.lax.psum(out, ep)

    return jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            P(),  # router replicated
            P(ep),  # expert stacks sharded on leading (expert) dim
            P(data_axes),  # tokens sharded over data axes
        ),
        out_specs=P(data_axes),
        check_vma=False,
    )(p["router"], p["experts"], x_flat)
