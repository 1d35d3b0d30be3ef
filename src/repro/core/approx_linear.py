"""The approximation-aware dense op used by every model in the framework.

Two parameter representations for a linear layer:

  * float dict ``{"w": (k, n), "b": (n,)?}`` — training / exact inference;
  * :class:`QuantizedDense` — the offline-packed serving representation:
    uint8 weight codes, quant params, CV constants, and the static
    :class:`~repro.core.policy.ApproxPolicy` as pytree metadata.

``dense(p, x)`` dispatches on the representation, so model code is agnostic
to whether it runs float, exact-int8, or approximate+CV — the paper's
technique is a parameter transformation (:func:`pack_params`), not a model
rewrite.  This mirrors the hardware story: the same network is simply mapped
onto a different MAC array.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import control_variate as cv
from repro.core import multipliers as am
from repro.core.policy import ApproxPolicy, PolicyFn
from repro.quant.quantize import (
    BlockedPack,
    PackedLinear,
    QuantParams,
    build_blocked_layout,
    build_fold,
    calibrate_minmax,
    concat_packs,
    folded_linear,
    pack_linear,
    quantized_linear,
    serving_blocks,
)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QuantizedDense:
    """Packed approximate linear layer.  ``policy`` is static metadata.

    ``blocked`` (pallas-backend packs only) is the offline-blocked serving
    layout: weight codes pre-padded to kernel tiles and all epilogue
    operands in one aligned table, so the forward pass never pads or
    assembles static parameters (see repro.quant.BlockedPack).
    """

    pack: PackedLinear
    a_qp: QuantParams
    policy: ApproxPolicy = dataclasses.field(metadata=dict(static=True))
    blocked: BlockedPack | None = None
    fold: dict | None = None


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QuantizedDenseGroup:
    """Fan-out-fused sibling linears (Q|K|V, gate|up) sharing one input.

    One concatenated pack (per-column weight quant params) executes all
    members in a single wide-N call: activations are quantized ONCE and the
    per-row MAC* statistics (sumx, sumqa) are computed ONCE and reused for
    every fused output column — they are per-row, column-independent, so the
    fused outputs are bit-identical to the separate member calls.
    ``names``/``splits`` recover the member outputs by column range.

    ``members`` carries the individually packed member layers for the
    decode-shape fallback: at small flattened row counts (M <=
    repro.kernels.ops.DECODE_M_MAX) the wide fused call measured SLOWER
    than separate member calls (BENCH_kernels.json decode_m4/qkv_fused,
    0.67x), so :func:`dense_group` gates the fusion on M.  Both
    representations produce bit-identical outputs by construction; the
    cost is carrying the member codes alongside the fused pack (~2x pack
    memory on fused layers), the classic compute-for-memory serving trade.
    """

    pack: PackedLinear
    a_qp: QuantParams
    policy: ApproxPolicy = dataclasses.field(metadata=dict(static=True))
    names: tuple[str, ...] = dataclasses.field(metadata=dict(static=True))
    splits: tuple[int, ...] = dataclasses.field(metadata=dict(static=True))
    blocked: BlockedPack | None = None
    fold: dict | None = None
    members: tuple[QuantizedDense, ...] | None = None


def is_linear_params(p: Any) -> bool:
    """Float linear leaf: 2D weights, 3D = (layers, k, n) scanned stack or
    (experts, k, n) expert stack, or 4D = (layers, experts, k, n) expert
    stacks of scanned MoE layers."""
    return isinstance(p, dict) and "w" in p and getattr(p["w"], "ndim", 0) in (2, 3, 4)


def _packed_forward(p: QuantizedDense | QuantizedDenseGroup,
                    x: jax.Array) -> jax.Array:
    """Forward dispatch for one packed leaf (or fused group's wide call)."""
    pol = p.policy
    if pol.backend == "pallas" and pol.is_approx:
        from repro.kernels import ops as kops

        return kops.quantized_dense_pallas(x, p).astype(x.dtype)
    if p.fold is not None:  # serving fast path: folded float GEMMs
        return folded_linear(x, p.fold, pol.mode, pol.m,
                             pol.use_cv).astype(x.dtype)
    return quantized_linear(
        x,
        p.pack,
        p.a_qp,
        pol.mode,
        pol.m,
        use_cv=pol.use_cv,
        groups=pol.groups,
    ).astype(x.dtype)


def dense(p: Any, x: jax.Array, name: str | None = None) -> jax.Array:
    """y = x @ W (+ b), under whatever numerics ``p`` encodes.

    x: (..., k).  ``name`` (optional) scopes calibration recording so the
    recorded activation-range path matches the parameter-tree path used by
    :func:`pack_params`.

    When a :class:`repro.quant.error_probe.ProbeRecorder` is active (eager
    probe forwards only — tracers are ignored, so jitted serving steps pay
    one thread-local ``None`` check at TRACE time and nothing at runtime),
    packed layers additionally compute the exact-int8 reference on the
    same codes: mode "observe" records the elementwise approx-vs-exact
    delta moments, mode "exact" returns the reference instead.
    """
    from repro.quant import error_probe, faults, observers

    if isinstance(p, QuantizedDense):
        probe = error_probe.active()
        if probe is not None and not isinstance(x, jax.core.Tracer):
            if probe.mode == "exact":
                return error_probe.exact_dense(p, x).astype(x.dtype)
            y = _packed_forward(p, x)
            flt = faults.active()
            if flt is not None:
                # armed fault injector (repro.quant.faults): corrupt the
                # approximate output BEFORE the delta is observed, so a
                # degraded MAC array shows up in the probe's variance
                y = flt.corrupt_dense(observers.current_path(),
                                      name or "dense", y)
            probe.observe(observers.current_path(), name or "dense",
                          np.asarray(y, np.float64)
                          - np.asarray(error_probe.exact_dense(p, x),
                                       np.float64))
            return y
        return _packed_forward(p, x)
    # float path (+ calibration recording when a recorder is active)
    if name is not None:
        with observers.scope(name):
            observers.record(x)
    else:
        observers.record(x)
    y = jnp.matmul(x, p["w"])
    if "b" in p and p["b"] is not None:
        y = y + p["b"]
    return y


def init_dense(key, k: int, n: int, *, bias: bool = True, scale: float | None = None,
               dtype=jnp.float32) -> dict:
    """Standard trunc-normal linear init (1/sqrt(k) fan-in scaling)."""
    if scale is None:
        scale = k**-0.5
    p = {"w": (jax.random.truncated_normal(key, -2.0, 2.0, (k, n)) * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((n,), dtype)
    return p


# ---------------------------------------------------------------------------
# Offline packing: float params + calibration stats -> approximate params
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("mode", "m", "groups"))
def _pack_stack(w: jax.Array, b: jax.Array, *, mode, m: int,
                groups: int) -> PackedLinear:
    """:func:`pack_linear` over a (layers, k, n) stack, one layer at a
    time: packing's int32/f32 temporaries stay the size of one layer
    instead of the whole stack's (at olmo-1b widths a vmapped pack held
    several GB of them in device memory)."""
    return jax.lax.map(
        lambda wb: pack_linear(wb[0], wb[1], mode, m, groups), (w, b))


def _pack_leaf(p: dict, policy: ApproxPolicy) -> PackedLinear:
    """Quantize one float linear leaf (2D, or matrix by matrix over the
    leading axes of a 3D or 4D stack)."""
    w = p["w"]
    b = p.get("b")
    if w.ndim >= 3:
        lead, (k, n) = w.shape[:-2], w.shape[-2:]
        pack = _pack_stack(
            w.reshape(-1, k, n),
            b.reshape(-1, n) if b is not None
            else jnp.zeros((math.prod(lead), n), w.dtype),
            mode=policy.mode, m=policy.m, groups=policy.groups)
        if b is None:
            pack = dataclasses.replace(pack, bias=None)
        return jax.tree.map(lambda a: a.reshape(lead + a.shape[1:]), pack)
    return pack_linear(w, b, policy.mode, policy.m, policy.groups)


def _act_qp(act_range, w: jax.Array) -> QuantParams:
    """Activation quant params; per-matrix arrays for stacks so
    ``lax.scan`` can slice the pack (and the grouped expert path index
    it by expert)."""
    if w.ndim >= 3:
        lead = w.shape[:-2]
        return calibrate_minmax(
            jnp.broadcast_to(jnp.asarray(act_range[0], jnp.float32), lead),
            jnp.broadcast_to(jnp.asarray(act_range[1], jnp.float32), lead),
        )
    return calibrate_minmax(act_range[0], act_range[1])


def _maybe_blocked(pack: PackedLinear, a_qp: QuantParams,
                   policy: ApproxPolicy, ndim: int) -> BlockedPack | None:
    """Offline-blocked serving layout for pallas-backend approximate packs."""
    if not (policy.backend == "pallas" and policy.is_approx):
        return None
    k, n = pack.w_q.shape[-2:]
    bn, bk = serving_blocks(k, n)
    build = lambda pk, aq: build_blocked_layout(pk, aq, bn, bk)  # noqa: E731
    for _ in range(ndim - 2):
        build = jax.vmap(build)
    return build(pack, a_qp)


def _maybe_fold(pack: PackedLinear, a_qp: QuantParams,
                policy: ApproxPolicy) -> dict | None:
    """Folded float serving operands for jnp-path packs (build_fold); the
    pallas-approx path reads the blocked layout instead."""
    if policy.backend == "pallas" and policy.is_approx:
        return None
    return build_fold(pack, a_qp, policy.mode, policy.m, policy.use_cv)


def pack_dense(
    p: dict,
    policy: ApproxPolicy,
    act_range: tuple[float, float] | tuple[jax.Array, jax.Array],
    fold: bool = True,
) -> QuantizedDense:
    """Pack one float linear layer for the approximate array.

    Handles both 2D weights and 3D (layers, k, n) scanned stacks — for the
    latter every per-layer slice gets its own quant/CV constants (vmapped),
    and `lax.scan` over the resulting QuantizedDense xs slices them per step.
    """
    w = p["w"]
    pack = _pack_leaf(p, policy)
    a_qp = _act_qp(act_range, w)
    return QuantizedDense(pack=pack, a_qp=a_qp, policy=policy,
                          blocked=_maybe_blocked(pack, a_qp, policy, w.ndim),
                          fold=_maybe_fold(pack, a_qp, policy) if fold
                          else None)


def pack_dense_group(
    members: list[tuple[str, dict]],
    policy: ApproxPolicy,
    act_range: tuple[float, float] | tuple[jax.Array, jax.Array],
    fold: bool = True,
) -> QuantizedDenseGroup:
    """Pack sibling linears that consume the SAME activations into one
    fan-out-fused wide-N pack (quantize once, shared MAC* statistics).

    Each member keeps its own weight quant scale/zero-point (per-column
    vectors in the fused pack) and CV constants, so per-column arithmetic —
    and therefore the outputs — are bit-identical to separate packing.
    """
    names = tuple(name for name, _ in members)
    leaves = [leaf for _, leaf in members]
    w0 = leaves[0]["w"]
    splits = tuple(int(leaf["w"].shape[-1]) for leaf in leaves)
    member_packs = [_pack_leaf(leaf, policy) for leaf in leaves]
    pack = concat_packs(member_packs)
    a_qp = _act_qp(act_range, w0)
    # the individually packed members ride along for the decode-shape
    # fallback (dense_group gates the wide fused call on M); per-column
    # quant params make both representations bit-identical, so which one
    # runs is purely a latency choice
    member_qd = tuple(
        QuantizedDense(pack=mp, a_qp=a_qp, policy=policy,
                       blocked=_maybe_blocked(mp, a_qp, policy, w0.ndim),
                       fold=_maybe_fold(mp, a_qp, policy) if fold else None)
        for mp in member_packs)
    return QuantizedDenseGroup(
        pack=pack, a_qp=a_qp, policy=policy, names=names, splits=splits,
        blocked=_maybe_blocked(pack, a_qp, policy, w0.ndim),
        fold=_maybe_fold(pack, a_qp, policy) if fold else None,
        members=member_qd)


def _fuse_m_min() -> int:
    """Smallest flattened row count that runs the wide fused group call.

    BENCH_kernels.json measured the fused wide-N call SLOWER than separate
    member calls at decode shapes (decode_m4/qkv_fused 0.67x): at thin M
    the wide GEMM's fixed cost dominates and the shared-quantize win
    vanishes.  The threshold is the kernel block picker's decode window —
    below/at DECODE_M_MAX the decode-specialized tiles fire anyway, so the
    same boundary splits the two regimes.
    """
    from repro.kernels import ops as kops

    return kops.DECODE_M_MAX + 1


def dense_group(g: QuantizedDenseGroup, x: jax.Array) -> dict[str, jax.Array]:
    """Run a fused fan-out group: one wide-N call, outputs split per member.

    Returns ``{name: (..., n_name)}`` in the group's member order.

    Decode-shape M-gate: when the flattened row count is inside the
    kernel decode window (M < :func:`_fuse_m_min`) and the group carries
    its packed ``members``, the members run as separate :func:`dense`
    calls instead of the wide fused GEMM — bit-identical outputs, faster
    thin-M latency.  The branch is on a STATIC shape, so each jitted
    batch shape compiles exactly one of the two paths.
    """
    rows = math.prod(x.shape[:-1]) if x.ndim > 1 else 1
    if g.members is not None and rows < _fuse_m_min():
        return {name: dense(member, x, name=name)
                for name, member in zip(g.names, g.members)}
    from repro.quant import error_probe, faults, observers

    probe = error_probe.active()
    if probe is not None and not isinstance(x, jax.core.Tracer):
        if probe.mode == "exact":
            y = error_probe.exact_dense(g, x).astype(x.dtype)
        else:
            y = _packed_forward(g, x)
            flt = faults.active()
            if flt is not None:
                y = flt.corrupt_dense(observers.current_path(),
                                      "|".join(g.names), y)
            probe.observe(observers.current_path(), "|".join(g.names),
                          np.asarray(y, np.float64)
                          - np.asarray(error_probe.exact_dense(g, x),
                                       np.float64))
    else:
        y = _packed_forward(g, x)
    out: dict[str, jax.Array] = {}
    off = 0
    for name, n in zip(g.names, g.splits):
        out[name] = jax.lax.slice_in_dim(y, off, off + n, axis=-1)
        off += n
    return out


#: Sibling sets eligible for fan-out fusion (consume the SAME activations):
#: (member names, fused key, companion key).  The companion key must also be
#: present — it anchors the dict to the module shape whose call sites
#: actually feed every member the same input (attention blocks have "o",
#: swiglu has "down"), so name-coincidences in other modules (e.g. RWKV
#: time-mix r/k/v with token-shifted inputs) can never fuse.  MoE expert
#: stacks ("experts" dicts) carry the same member names but run through the
#: ragged grouped-GEMM path, so they are never fused here.
FUSABLE_GROUPS: tuple[tuple[tuple[str, ...], str, str], ...] = (
    (("q", "k", "v"), "qkv", "o"),
    (("gate", "up"), "gateup", "down"),
)


def _ranges_equal(a, b) -> bool:
    import numpy as np

    try:
        return bool(np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
                    and np.array_equal(np.asarray(a[1]), np.asarray(b[1])))
    except Exception:
        return False


def _fusable(node: dict, names: tuple[str, ...], companion: str, path,
             policy_fn, act_ranges, default_range):
    """If ``names`` form a fusable sibling set in ``node``, return
    (policy, act_range); else None."""
    if companion not in node:
        return None
    if not all(n in node and is_linear_params(node[n]) for n in names):
        return None
    leaves = [node[n] for n in names]
    w0 = leaves[0]["w"]
    if any(leaf["w"].shape[:-1] != w0.shape[:-1] or leaf["w"].ndim != w0.ndim
           for leaf in leaves):
        return None  # different fan-in / stacking: not the same input
    if len({("b" in leaf and leaf.get("b") is not None) for leaf in leaves}) > 1:
        return None
    policies = [policy_fn(path + (n,)) for n in names]
    if policies[0] is None or any(p != policies[0] for p in policies):
        return None
    ranges = [(act_ranges or {}).get("/".join(path + (n,)), default_range)
              for n in names]
    if any(not _ranges_equal(r, ranges[0]) for r in ranges):
        return None
    return policies[0], ranges[0]


def pack_params(
    params: Any,
    policy_fn: PolicyFn,
    act_ranges: dict[str, tuple[float, float]] | None = None,
    default_range: tuple[float, float] = (-8.0, 8.0),
    fuse: bool = True,
    fold: bool = True,
) -> Any:
    """Walk a parameter tree, replacing float linear leaves with packed ones.

    ``policy_fn(path)`` picks the policy per layer (None keeps float);
    ``act_ranges`` maps "/".join(path) -> (lo, hi) calibration stats recorded
    by :mod:`repro.quant.observers`.  Layers without stats use
    ``default_range`` (safe-wide; accuracy benchmarks always calibrate).

    With ``fuse`` (default), sibling layers in :data:`FUSABLE_GROUPS` that
    share a policy and activation range are packed into ONE fan-out-fused
    :class:`QuantizedDenseGroup` (key "qkv" / "gateup", replacing the member
    keys) — bit-identical outputs, one wide-N kernel call at serving time.
    With ``fold`` (default), jnp-path packs carry the folded f32 serving
    operands (:func:`repro.quant.quantize.build_fold`); pass ``fold=False``
    to keep every pack on the exact-integer path (no f32 staging memory).
    """

    def walk(node: Any, path: tuple[str, ...]) -> Any:
        if is_linear_params(node):
            policy = policy_fn(path)
            if policy is None:
                return node
            key = "/".join(path)
            rng = (act_ranges or {}).get(key, default_range)
            # expert stacks run the ragged grouped-GEMM path, which reads
            # only the canonical pack — folded operands would be dead weight
            leaf_fold = fold and path[-2:-1] != ("experts",)
            return pack_dense(node, policy, rng, fold=leaf_fold)
        if isinstance(node, dict):
            groups: dict[str, Any] = {}  # first-member key -> (group key, group)
            consumed: set[str] = set()
            if fuse and path[-1:] != ("experts",):
                for names, gkey, companion in FUSABLE_GROUPS:
                    if consumed.intersection(names):
                        continue
                    hit = _fusable(node, names, companion, path, policy_fn,
                                   act_ranges, default_range)
                    if hit is None:
                        continue
                    policy, rng = hit
                    groups[names[0]] = (gkey, pack_dense_group(
                        [(n, node[n]) for n in names], policy, rng,
                        fold=fold))
                    consumed.update(names)
            out: dict[str, Any] = {}
            for k, v in node.items():
                if k in groups:
                    gkey, g = groups[k]
                    out[gkey] = g
                elif str(k) not in consumed:
                    out[k] = walk(v, path + (str(k),))
            return out
        if isinstance(node, (list, tuple)):
            t = type(node)
            return t(walk(v, path + (str(i),)) for i, v in enumerate(node))
        return node

    return walk(params, ())


def packed_layer_paths(params: Any) -> list[str]:
    """All paths that hold packed layers (for reporting/tests).

    Fan-out-fused groups report their ORIGINAL member paths (e.g. a group
    at ``blocks/attn/qkv`` lists ``blocks/attn/q`` etc.), and the listing is
    sorted, so it is stable across the fused and unfused representations.
    """
    out: list[str] = []

    def walk(node: Any, path: tuple[str, ...]):
        if isinstance(node, QuantizedDense):
            out.append("/".join(path))
        elif isinstance(node, QuantizedDenseGroup):
            for name in node.names:
                out.append("/".join(path[:-1] + (name,)))
        elif isinstance(node, dict):
            for k, v in node.items():
                walk(v, path + (str(k),))
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                walk(v, path + (str(i),))

    walk(params, ())
    return sorted(out)
