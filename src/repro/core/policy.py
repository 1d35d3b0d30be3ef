"""Per-layer approximation policies.

An :class:`ApproxPolicy` describes how one linear layer executes on the
(emulated) approximate MAC array: which multiplier family, its knob ``m``,
whether the control-variate correction V is added, how many CV groups
(beyond-paper extension), and which backend computes it.

Policies are static/hashable so jit can specialize on them; they travel with
packed parameters as pytree metadata.

This module is the *mechanism* layer.  The public way to choose policies
per layer is the declarative :mod:`repro.numerics` spec subsystem
(``NumericsSpec`` -> ``PackPlan`` -> ``apply_numerics``); the ``PolicyFn``
callables below are an internal detail of ``pack_params`` that specs lower
to.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Literal

from repro.core.multipliers import APPROX_MODES, PAPER_M_RANGE, Mode

Backend = Literal["jnp", "pallas"]


@dataclasses.dataclass(frozen=True)
class ApproxPolicy:
    """Static per-layer approximation configuration."""

    mode: Mode = "exact"  # multiplier family ("exact" = plain int8)
    m: int = 0  # approximation knob (paper Sec. 2)
    use_cv: bool = True  # add the control variate V (the paper's technique)
    groups: int = 1  # >1 = grouped CV (beyond paper)
    backend: Backend = "jnp"

    def __post_init__(self):
        if self.mode != "exact" and not (0 <= self.m <= 8):
            raise ValueError(f"m={self.m} out of range for 8-bit codes")
        if self.groups < 1:
            raise ValueError("groups must be >= 1")
        if self.backend == "pallas" and self.groups > 1:
            raise ValueError(
                "grouped CV (groups > 1) has no Pallas kernel; use "
                "backend='jnp'")

    @property
    def is_approx(self) -> bool:
        return self.mode != "exact" and self.m > 0

    def label(self) -> str:
        if not self.is_approx:
            return "int8-exact"
        cv = f"+cv(g={self.groups})" if self.use_cv else "-cv"
        return f"{self.mode}(m={self.m}){cv}"

    def to_dict(self) -> dict:
        """JSON-safe form (consumed by repro.numerics serialization)."""
        return {"mode": self.mode, "m": self.m, "use_cv": self.use_cv,
                "groups": self.groups, "backend": self.backend}

    @classmethod
    def from_dict(cls, d: dict) -> "ApproxPolicy":
        unknown = set(d) - {"mode", "m", "use_cv", "groups", "backend"}
        if unknown:
            raise ValueError(f"unknown ApproxPolicy fields {sorted(unknown)}")
        return cls(**d)


FLOAT = None  # sentinel: layer stays in float (not packed)
INT8_EXACT = ApproxPolicy("exact", 0)


def paper_policies(use_cv: bool = True, backend: Backend = "jnp") -> list[ApproxPolicy]:
    """The full grid the paper evaluates (Tables 2-4): three multipliers x
    their m ranges."""
    out = []
    for mode in APPROX_MODES:
        for m in PAPER_M_RANGE[mode]:
            out.append(ApproxPolicy(mode, m, use_cv=use_cv, backend=backend))
    return out


# A PolicyFn maps a parameter tree path (tuple of str keys) to a policy, or
# FLOAT/None to keep the layer in float.  Used by pack_params.  Internal:
# user-facing configuration goes through repro.numerics specs, which lower
# to a PolicyFn at apply time.
PolicyFn = Callable[[tuple[str, ...]], ApproxPolicy | None]


def uniform_policy(policy: ApproxPolicy | None, skip: tuple[str, ...] = ()) -> PolicyFn:
    """Apply one policy to every linear layer, except paths containing any of
    the ``skip`` substrings (e.g. first/last layers, router gates)."""

    def fn(path: tuple[str, ...]) -> ApproxPolicy | None:
        joined = "/".join(path)
        if any(s in joined for s in skip):
            return None
        return policy

    return fn


# ---------------------------------------------------------------------------
# Automatic per-layer policy search (beyond paper; ALWANN-flavoured)
# ---------------------------------------------------------------------------


def order_most_aggressive(candidates: list[ApproxPolicy]) -> list[ApproxPolicy]:
    """Candidates sorted most-aggressive-first by the analytic error sigma."""
    from repro.core.multipliers import analytic_error_moments_uniform

    return sorted(
        candidates,
        key=lambda p: analytic_error_moments_uniform(p.mode, p.m)[1],
        reverse=True,
    )


def greedy_assign(apply_fn, params, calib_inputs,
                  items: list[tuple[str, list[ApproxPolicy], float]],
                  act_ranges: dict | None = None) -> dict[str, ApproxPolicy]:
    """The greedy ALWANN-style per-layer assignment core (shared by
    :func:`auto_policy` and the ``auto(...)`` rule lowering in
    :mod:`repro.numerics`).

    ``items`` is ``[(path, candidates, budget_rel_err)]`` with candidates
    ordered most-aggressive-first (see :func:`order_most_aggressive`).  Per
    layer (independently), the first candidate whose model-output relative
    error on the calibration inputs fits the budget wins; layers too
    sensitive for any candidate fall back to exact int8.  Greedy-independent
    works because the CV keeps per-layer errors zero-mean, so sensitivities
    compose roughly additively at small errors.
    """
    import jax.numpy as jnp

    from repro.core.approx_linear import pack_params

    ref = apply_fn(params, calib_inputs)
    ref_scale = float(jnp.abs(ref).mean()) + 1e-12

    out: dict[str, ApproxPolicy] = {}
    for path, candidates, budget in items:
        chosen = INT8_EXACT
        for cand in candidates:
            one = pack_params(
                params,
                lambda p, path=path, cand=cand:
                    cand if "/".join(p) == path else None,
                act_ranges=act_ranges,
            )
            err = float(jnp.abs(apply_fn(one, calib_inputs) - ref).mean())
            if err / ref_scale <= budget:
                chosen = cand
                break
        out[path] = chosen
    return out


def auto_policy(
    apply_fn,
    params,
    calib_inputs,
    *,
    candidates: list[ApproxPolicy] | None = None,
    budget_rel_err: float = 0.05,
    skip: tuple[str, ...] = (),
    act_ranges: dict | None = None,
):
    """Greedy per-layer approximation assignment.

    For each packable linear layer (independently), measure the model-output
    relative error of every candidate policy against the float model on the
    calibration inputs, and keep the MOST AGGRESSIVE candidate whose error
    stays under ``budget_rel_err``; layers too sensitive for any candidate
    fall back to exact int8.  Greedy-independent is the ALWANN-style
    heuristic: per-layer sensitivities compose roughly additively at small
    errors (the CV keeps per-layer errors zero-mean, which is what makes the
    additive approximation work well here).

    Returns (policy_map: path -> ApproxPolicy, report rows).
    """
    from repro.core.approx_linear import pack_params, packed_layer_paths

    candidates = order_most_aggressive(candidates or paper_policies(use_cv=True))

    # enumerate packable layer paths
    probe = pack_params(params, uniform_policy(INT8_EXACT, skip=skip),
                        act_ranges=act_ranges)
    paths = packed_layer_paths(probe)
    policy_map = greedy_assign(
        apply_fn, params, calib_inputs,
        [(path, candidates, budget_rel_err) for path in paths],
        act_ranges=act_ranges)
    rows = [{"layer": path, "policy": policy_map[path].label()}
            for path in paths]

    def fn(p: tuple[str, ...]):
        return policy_map.get("/".join(p))

    return fn, rows
