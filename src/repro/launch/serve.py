"""Serving driver: the paper's technique as a first-class deployment mode.

Numerics are configured declaratively: a :class:`~repro.numerics.NumericsSpec`
(preset, JSON file, or built in code) resolves against the parameter tree
into a :class:`~repro.numerics.PackPlan`, and `build_serving_params` executes
that plan — float params become the approximate int8 + control-variate
representation (uint8 weight codes, per-layer CV constants, bf16 for the
non-array parts) in one parameter transformation, exactly the paper's
deployment story (same network, different MAC array).

`make_prefill_step` / `make_decode_step` build the sharded serving steps the
dry-run lowers for the prefill_32k / decode_32k / long_500k cells.

The CLI drives the continuous-batching engine (repro.serving) on a reduced
model with a mixed-length request trace:

    PYTHONPATH=src python -m repro.launch.serve --engine --requests 8 \
        --arch olmo-1b-reduced --mode perforated --m 2

``--kv-layout paged`` serves through the block-granular paged KV cache
(``--block-size``/``--kv-blocks``/``--no-prefix-cache`` knobs), and
``--shared-prefix-pair`` prepends a warmed shared-prefix request pair that
asserts the prefix-cache hit (the CI paged smoke).

``--speculative-k K`` turns on self-verifying speculative decode
(repro.serving.speculative): the SAME float init is packed twice — the
numerics flags (or ``--draft-spec``, a preset name or spec-JSON path)
describe the APPROXIMATE draft parameters, the verifier is always exact
int8 — and the engine emits bit-identical exact output while the cheap
path proposes.  ``--assert-acceptance`` fails the run unless the verifier
accepted at least one draft (the CI speculative smoke).

and `plan` prints the resolved per-layer assignment table without packing
anything (shapes only, runs in milliseconds):

    PYTHONPATH=src python -m repro.launch.serve plan --arch olmo-1b-reduced
    PYTHONPATH=src python -m repro.launch.serve plan --preset int8 --json

``plan --diff-checkpoint PATH`` additionally resolves the NumericsSpec
persisted in that checkpoint's metadata against the same abstract
parameters and exits nonzero if any layer's assignment drifted from the
CLI spec — the deploy-time guard against serving a checkpoint under
different numerics than it was saved with.

``--legacy`` keeps the old lock-step rectangular-batch loop for comparison;
``--spec-json FILE`` serves under a spec shipped as JSON (the same payload
checkpoints and engine metadata carry).

Robustness (PR 8): ``--governor --slo-err-var V`` attaches the accuracy-SLO
numerics governor (repro.serving.governor) — the error probe's running
variance estimate walks the degradation ladder CLI-spec -> int8 -> float,
hot-swapping the live pack; ``--inject-faults KIND@EVERY[@START-STOP]``
arms the deterministic fault injector (repro.quant.faults) and engine-side
quarantine (NaN rows are rolled back and replayed on the exact pack, so no
corrupted token is emitted — the run asserts it); ``--deadline-ms`` gives
every request a latency SLO; queue-full submissions retry with exponential
backoff (``--submit-retries``).

Fleet serving (PR 9): ``--fleet --tier SPEC=COUNT ...`` serves through
heterogeneous-numerics replica tiers behind the spec-aware router
(repro.serving.fleet) — one float init, one pack per tier, latency
traffic on exact tiers, bulk on approximate ones, cross-replica
prefix-cache sharing (``--share-prefixes-every``,
``--assert-prefix-share`` is the CI fleet smoke), per-replica traces
(``--trace-dir``).

Observability (PR 10): ``--shadow-spec NAME_OR_FILE --shadow-fraction F``
runs A/B shadow serving (repro.serving.shadow) — a deterministic sample
of finished requests is replayed teacher-forced through a second pack and
diffed token-by-token; the run prints the accuracy-vs-power verdict and
``--assert-shadow`` makes it a CI gate.  ``--layer-slo PATTERN=VAR``
(repeatable) gives the governor per-layer err-var ceilings on top of the
global SLO; ``--assert-layer-breach [PATTERN]`` asserts a matching layer
was named in a ``layer_slo_breach`` escalation AND is visible in the
windowed per-layer err-var time-series.  ``--inject-faults`` accepts a
fourth ``@LAYERS`` fnmatch segment (``dense-noise@1@blocks/0/*``) to
confine dense-surface noise to chosen layers.  ``--prom-out FILE``
exports the final metrics snapshot (engine or merged fleet) as
OpenMetrics text (repro.serving.prom), and ``tools/obs_dashboard.py``
renders the JSONL trace into a static HTML dashboard.
"""

from __future__ import annotations

import argparse
import dataclasses
import fnmatch
import json
import sys
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ArchConfig, EngineConfig
from repro.core.policy import ApproxPolicy
from repro.models import build_model
from repro.numerics import (NumericsSpec, PackPlan, apply_numerics,
                            get_preset)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Serving numerics + cache configuration.

    ``spec`` is the source of truth.  ``policy`` is a convenience shorthand
    — when ``spec`` is None, the policy is wrapped into the ``serve-default``
    preset (its documented keep-float rule-set plus this policy everywhere
    else), which reproduces the old uniform-policy behavior.
    """

    spec: NumericsSpec | None = None
    policy: ApproxPolicy | None = None
    act_range: tuple[float, float] = (-8.0, 8.0)  # default when uncalibrated
    cache_dtype: str = "bfloat16"
    fuse: bool = True  # fan-out fusion (Q|K|V, gate|up groups)
    fold: bool = True  # folded f32 serving operands (CPU fast path)

    def numerics_spec(self) -> NumericsSpec:
        if self.spec is not None:
            return self.spec
        return get_preset("serve-default", policy=self.policy)


def build_serving_params(params: Any, cfg: ArchConfig, scfg: ServeConfig,
                         act_ranges: dict | None = None,
                         plan: PackPlan | None = None) -> Any:
    """float params -> packed approximate serving params (+ bf16 float rest).

    ``plan`` short-circuits resolution when the caller already has one (e.g.
    printed/audited via the `plan` CLI, or restored from a checkpoint).
    """
    if plan is None:
        plan = scfg.numerics_spec().resolve(params)
    packed = apply_numerics(params, plan, act_ranges=act_ranges,
                            default_range=scfg.act_range,
                            fuse=scfg.fuse, fold=scfg.fold)

    def to_bf16(x):
        if hasattr(x, "dtype") and x.dtype == jnp.float32 and x.ndim >= 1:
            return x.astype(jnp.bfloat16)
        return x

    # only float leaves OUTSIDE packs go bf16 (pack internals stay exact)
    from repro.core.approx_linear import QuantizedDense

    def walk(node):
        if isinstance(node, QuantizedDense):
            return node
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v) for v in node)
        return to_bf16(node)

    return walk(packed)


_CACHE_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
                 "int8": jnp.int8}


def _cache_dt(scfg: ServeConfig):
    try:
        return _CACHE_DTYPES[scfg.cache_dtype]
    except KeyError:
        raise ValueError(
            f"unknown cache_dtype {scfg.cache_dtype!r}; "
            f"valid choices: {sorted(_CACHE_DTYPES)}") from None


def make_prefill_step(cfg: ArchConfig, max_len: int, mesh=None,
                      scfg: ServeConfig = ServeConfig()):
    api = build_model(cfg)

    def step(params, batch):
        return api.prefill(params, batch, max_len, mesh=mesh,
                           cache_dtype=_cache_dt(scfg))

    return step


def make_decode_step(cfg: ArchConfig, mesh=None, scfg: ServeConfig = ServeConfig()):
    api = build_model(cfg)

    def step(params, tokens, cache):
        return api.decode_step(params, tokens, cache, mesh=mesh)

    return step


# ---------------------------------------------------------------------------
# CLI: continuous-batching engine (default) / legacy lock-step demo / plan
# ---------------------------------------------------------------------------


def _spec_from_args(args) -> NumericsSpec | None:
    """Spec from CLI flags: --spec-json wins, then --preset, then --mode/--m
    shorthand.  Returns None for float serving (no packing at all)."""
    if getattr(args, "spec_json", None):
        with open(args.spec_json) as f:
            return NumericsSpec.from_json(f.read())
    if getattr(args, "preset", None):
        return get_preset(args.preset)
    if args.mode == "float":
        return None
    policy = ApproxPolicy(args.mode, 0 if args.mode == "exact" else args.m,
                          use_cv=not args.no_cv)
    return get_preset("serve-default", policy=policy)


def _prepare_params(cfg: ArchConfig, args):
    """Returns ``(serving_params, label, float_params, spec)`` — the float
    init and spec ride along so the robustness layer can build further
    packs (governor ladder rungs, the exact quarantine-replay pack) from
    the SAME weights."""
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    spec = _spec_from_args(args)
    if spec is None:
        return params, "float", params, None
    scfg = ServeConfig(spec=spec)
    return build_serving_params(params, cfg, scfg), spec.name, params, spec


def _draft_spec_from_args(args) -> NumericsSpec:
    """The draft spec under speculation.  ``--draft-spec`` names a preset
    or a spec-JSON file; otherwise the regular numerics flags
    (--mode/--m/--preset/--spec-json) describe the draft — the verifier
    is always exact int8, so under speculation those flags stop choosing
    the serving numerics and start choosing the proposer's."""
    from repro.numerics.presets import PRESETS

    ds = getattr(args, "draft_spec", None)
    if ds:
        if ds in PRESETS:
            return get_preset(ds)
        with open(ds) as f:
            return NumericsSpec.from_json(f.read())
    spec = _spec_from_args(args)
    if spec is None:
        raise SystemExit(
            "--speculative-k needs an approximate draft spec: float "
            "drafting buys nothing (pass --draft-spec, or --mode/--m)")
    return spec


def _spec_by_name_or_file(text: str) -> NumericsSpec:
    """A preset name or a spec-JSON file path -> NumericsSpec."""
    from repro.numerics.presets import PRESETS

    if text in PRESETS:
        return get_preset(text)
    with open(text) as f:
        return NumericsSpec.from_json(f.read())


def _parse_layer_slos(items: list[str] | None) -> dict[str, float]:
    """``--layer-slo PATTERN=VAR`` (repeatable) -> {pattern: ceiling}."""
    out: dict[str, float] = {}
    for item in items or []:
        pattern, sep, var = item.partition("=")
        if not sep or not pattern:
            raise SystemExit(f"--layer-slo {item!r}: expected PATTERN=VAR "
                             "(e.g. 'blocks/0/*=1e-4')")
        try:
            out[pattern] = float(var)
        except ValueError:
            raise SystemExit(
                f"--layer-slo {item!r}: VAR must be a float") from None
    return out


def _prepare_speculative_params(cfg: ArchConfig, args):
    """Pack the SAME float init twice: exact int8 for verification (and
    prefill), the draft spec for proposing — the one-checkpoint
    speculative pair (zero extra parameter memory at rest; both packs
    derive from one set of weights)."""
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    verify_spec = get_preset("int8")
    draft_spec = _draft_spec_from_args(args)
    verify = build_serving_params(params, cfg, ServeConfig(spec=verify_spec))
    draft = build_serving_params(params, cfg, ServeConfig(spec=draft_spec))
    return verify, verify_spec.name, draft, draft_spec.name


def mixed_trace(cfg: ArchConfig, n_requests: int, max_len: int,
                prefill_chunk: int, seed: int = 0):
    """A heterogeneous request trace: short chat turns + long documents."""
    rng = np.random.default_rng(seed)
    trace = []
    for i in range(n_requests):
        if i % 3 == 2:  # long-document request
            plen = int(rng.integers(max_len // 2, max(max_len - 16, max_len // 2 + 1)))
        else:  # short chat turn
            plen = int(rng.integers(2, max(prefill_chunk, 3)))
        gen = int(rng.integers(4, 17))
        gen = min(gen, max_len - plen)
        plen = min(plen, max_len - gen)
        trace.append((rng.integers(0, cfg.vocab, plen).tolist(), gen))
    return trace


def run_engine(args) -> dict:
    from repro.serving import ServingEngine

    cfg = get_config(args.arch)
    spec_k = getattr(args, "speculative_k", 0)
    if spec_k:
        params, label, draft_params, draft_label = (
            _prepare_speculative_params(cfg, args))
        params_float = spec = None
    else:
        params, label, params_float, spec = _prepare_params(cfg, args)
        draft_params = draft_label = None

    # -- robustness layer (repro.serving.governor / repro.quant.faults) ------
    governor = injector = pack_fn = exact_params = None
    probe_every = args.error_probe_every
    if getattr(args, "governor", False):
        if spec is None:
            raise SystemExit(
                "--governor needs an approximate serving spec (float serving "
                "has nothing to degrade; speculative serving is exact "
                "already) — pass --mode/--m, --preset, or --spec-json")
        if args.slo_err_var is None:
            raise SystemExit("--governor needs --slo-err-var: the logits "
                             "error-variance ceiling the ladder enforces")
        from repro.numerics import resolve_ladder
        from repro.serving import GovernorConfig, NumericsGovernor

        rungs: list = [spec]
        if spec.name != "int8":
            rungs.append("int8")
        rungs.append("float")
        ladder = resolve_ladder(rungs, params_float)
        governor = NumericsGovernor(ladder, GovernorConfig(
            slo_err_var=args.slo_err_var,
            window_probes=args.governor_window,
            clean_windows_to_relax=args.governor_relax_after,
            layer_slo=_parse_layer_slos(getattr(args, "layer_slo", None))))

        def pack_fn(s, _p=params_float, _cfg=cfg):
            if s is None:
                return _p  # the "float" rung serves the raw init
            return build_serving_params(_p, _cfg, ServeConfig(spec=s))

        if probe_every <= 0:
            probe_every = 4  # the governor consumes the probe; arm it
            print(f"governor: defaulting --error-probe-every to {probe_every}")
    if getattr(args, "inject_faults", None):
        from repro.quant.faults import FaultInjector, FaultSpec

        injector = FaultInjector(
            FaultSpec.parse(args.inject_faults, seed=args.fault_seed))
        if injector.spec.surface == "step" and label != "int8":
            # quarantine replays must run an exact pack; int8 IS exact
            exact_params = build_serving_params(
                params_float, cfg, ServeConfig(spec=get_preset("int8")))

    # -- A/B shadow serving (repro.serving.shadow) ----------------------------
    shadow_params = shadow_label = None
    shadow_fraction = 0.0
    if getattr(args, "shadow_spec", None):
        if spec_k:
            raise SystemExit("--shadow-spec is incompatible with "
                             "--speculative-k (the engine refuses mixed "
                             "draft/shadow dual-pack regimes)")
        if governor is not None:
            raise SystemExit("--shadow-spec is incompatible with --governor "
                             "(a hot-swapping primary makes the A/B verdict "
                             "a mixed-regime average)")
        if params_float is None:
            raise SystemExit("--shadow-spec needs the float init to pack "
                             "the shadow from")
        shadow_spec = _spec_by_name_or_file(args.shadow_spec)
        shadow_params = build_serving_params(
            params_float, cfg, ServeConfig(spec=shadow_spec))
        shadow_label = shadow_spec.name
        shadow_fraction = args.shadow_fraction

    ecfg = EngineConfig(slots=args.slots, max_len=args.max_len,
                        prefill_chunk=args.chunk, cache_dtype=args.cache_dtype,
                        mixed_batches=not args.no_mixed,
                        kv_layout=args.kv_layout,
                        kv_block_size=args.block_size,
                        kv_blocks=args.kv_blocks,
                        prefix_cache=not args.no_prefix_cache,
                        trace=bool(args.trace_out),
                        metrics_window_s=args.metrics_window,
                        error_probe_every=probe_every,
                        speculative_k=spec_k,
                        detect_faults=getattr(args, "detect_faults", False),
                        shadow_fraction=shadow_fraction)
    eng = ServingEngine(cfg, params, ecfg, numerics=label,
                        draft_params=draft_params, draft_numerics=draft_label,
                        governor=governor, pack_fn=pack_fn,
                        fault_injector=injector, exact_params=exact_params,
                        shadow_params=shadow_params,
                        shadow_numerics=shadow_label)
    print(f"arch={cfg.name} numerics={label} slots={ecfg.slots} "
          f"max_len={ecfg.max_len} chunk={ecfg.prefill_chunk} "
          f"kv={ecfg.cache_dtype} mixed={ecfg.mixed_batches} "
          f"layout={ecfg.kv_layout}"
          + (f" block_size={ecfg.kv_block_size} "
             f"prefix_cache={ecfg.prefix_cache}"
             if ecfg.kv_layout == "paged" else "")
          + (f" speculative_k={spec_k} draft={draft_label}"
             if spec_k else "")
          + (f" governor=[{' -> '.join(r.name for r in governor.ladder)}] "
             f"slo_err_var={args.slo_err_var}" if governor else "")
          + (f" inject={injector.spec.kind}@{injector.spec.every} "
             f"seed={injector.spec.seed}" if injector else "")
          + (f" shadow={shadow_label} fraction={shadow_fraction}"
             if shadow_params is not None else ""))

    trace = mixed_trace(cfg, args.requests, ecfg.max_len, ecfg.prefill_chunk)
    if args.shared_prefix_pair:
        # one warmed shared-prefix pair: the second request must attach to
        # the first one's cached blocks (the --paged-only CI smoke asserts
        # the hit below)
        rng = np.random.default_rng(17)
        shared = rng.integers(
            0, cfg.vocab,
            min(4 * ecfg.prefill_chunk, ecfg.max_len // 2)).tolist()
        warm = eng.submit(shared, 2)
        eng.run()
        hit = eng.submit(shared + rng.integers(0, cfg.vocab, 4).tolist(), 4)
        eng.run()
        print(f"  shared-prefix pair: warm gen={len(warm.generated)} "
              f"hit prefix_hit_tokens={hit.prefix_hit_tokens}")
        if ecfg.kv_layout == "paged" and ecfg.prefix_cache:
            # sharing is full-block granular and capped one token early,
            # so the guaranteed hit is the block-aligned shareable prefix
            shareable = min(len(shared) // ecfg.kv_block_size
                            * ecfg.kv_block_size, len(shared) - 1)
            assert hit.prefix_hit_tokens >= shareable, (
                hit.prefix_hit_tokens, shareable)
    deadline = args.deadline_ms if getattr(args, "deadline_ms", 0) else None
    for prompt, gen in trace:
        r = eng.submit(prompt, gen, deadline_ms=deadline)
        # bounded retry with exponential backoff for QUEUE-FULL rejections
        # only: a full queue is transient (steps drain it), every other
        # reject reason (capacity, validation) is permanent for this job
        attempt = 0
        while (r.state.value == "rejected"
               and (r.reject_reason or "").startswith("queue full")
               and attempt < args.submit_retries):
            for _ in range(2 ** attempt):  # backoff unit = one engine step
                eng.step()
            attempt += 1
            eng.metrics.requests_retried += 1
            r = eng.submit(prompt, gen, deadline_ms=deadline)
        if r.state.value == "rejected":
            print(f"  request {r.rid} rejected: {r.reject_reason}"
                  + (f" (after {attempt} retries)" if attempt else ""))
    finished = eng.run()
    snap = eng.metrics.snapshot()
    print(f"finished {len(finished)}/{len(trace)} requests, "
          f"{eng.compile_count()} compiled shapes")
    if getattr(args, "assert_acceptance", False):
        # the CI speculative smoke: the verifier must have accepted at
        # least one draft (acceptance_rate None means nothing was drafted)
        acc = snap.get("acceptance_rate")
        assert acc is not None and acc > 0, (
            f"speculative smoke expected acceptance > 0, got {acc!r} "
            f"(drafted={snap.get('drafted_tokens')})")
        print(f"  speculative: acceptance_rate={acc} "
              f"drafted={snap['drafted_tokens']} "
              f"accepted={snap['accepted_draft_tokens']}")
    if injector is not None:
        m = eng.metrics
        print(f"  faults: injected={m.faults_injected} "
              f"detected={m.faults_detected} quarantines={m.quarantines} "
              f"replays={m.quarantine_replays}")
        if injector.spec.surface == "step":
            # the no-corrupted-emission contract: every injected row was
            # caught, rolled back, and replayed on the exact pack
            assert m.faults_detected >= m.faults_injected, (
                m.faults_detected, m.faults_injected)
            assert m.quarantine_replays == m.faults_detected
            assert all(0 <= t < cfg.vocab for r in finished
                       for t in r.generated), "corrupted token emitted"
    if governor is not None:
        print(f"  governor: rung={eng.numerics} "
              f"switches={eng.metrics.governor_switches} "
              f"(escalate {eng.metrics.governor_escalations} / "
              f"relax {eng.metrics.governor_relaxes})")
        for d in governor.decisions:
            dd = d.to_dict()
            print(f"    window {dd['window']}: {dd['action']} "
                  f"{dd['from']} -> {dd['to']} [{dd['reason']}] "
                  f"err_var={dd['err_var']} "
                  f"power_delta={dd['power_delta_pct']}%"
                  + (f" layer={dd['layer']}" if dd.get("layer") else ""))
    verdict = eng.shadow_verdict() if shadow_params is not None else None
    if verdict is not None:
        print(f"  shadow A/B [{label} vs {shadow_label}]: "
              f"{verdict['verdict']} — match "
              f"{verdict['token_match_rate']:.3f} over "
              f"{verdict['tokens']} tokens "
              f"({verdict['sampled_requests']} replays), "
              f"logits_err_var={verdict['logits_err_var']:.3g}, "
              f"power_delta={verdict['power_delta_pct']:+.2f}pp "
              f"[{verdict['reason']}]")
    if getattr(args, "assert_shadow", False):
        # the CI shadow smoke: at least one finished request was replayed
        # through the shadow pack and a verdict was reached
        assert verdict is not None and verdict["sampled_requests"] >= 1, (
            f"shadow smoke expected >=1 sampled replay, got {verdict!r}")
    if getattr(args, "assert_layer_breach", None) is not None:
        pattern = args.assert_layer_breach or "*"
        breaches = [d.to_dict() for d in (governor.decisions if governor
                                          else [])
                    if d.to_dict().get("reason") == "layer_slo_breach"]
        named = [d for d in breaches
                 if fnmatch.fnmatch(d.get("layer") or "", pattern)]
        assert named, (
            f"no governor escalation with reason=layer_slo_breach matching "
            f"layer pattern {pattern!r} (breaches seen: "
            f"{[d.get('layer') for d in breaches]})")
        # ...and the breaching layer must be visible in the windowed
        # per-layer err-var time-series (the attribution surface)
        layer = named[0]["layer"]
        windows_with = [s for s in eng.metrics.timeseries
                        if layer in (s.get("probe_layers") or {})]
        assert windows_with, (
            f"breaching layer {layer!r} absent from all "
            f"{len(eng.metrics.timeseries)} metrics_window samples")
        print(f"  layer-SLO breach: {layer} escalated "
              f"{named[0]['from']} -> {named[0]['to']}, present in "
              f"{len(windows_with)} window sample(s)")
    print(json.dumps(snap, indent=2))
    if getattr(args, "prom_out", None):
        from repro.serving.prom import to_openmetrics
        with open(args.prom_out, "w") as f:
            f.write(to_openmetrics(snap, labels={"engine": eng.engine_id}))
        print(f"openmetrics: {args.prom_out}")
    if args.trace_out:
        eng.tracer.write(args.trace_out)
        print(f"trace: {len(eng.tracer)} spans "
              f"({eng.tracer.dropped} dropped) -> {args.trace_out}")
    for r in finished[:4]:
        print(f"  req {r.rid}: prompt {r.prompt_len:4d} -> gen "
              f"{len(r.generated):3d} [{r.finish_reason}] "
              f"sample {r.generated[:8]}")
    return snap


def _parse_tiers(items: list[str] | None) -> list:
    """``--tier SPEC=COUNT`` -> TierConfig list (tier name == spec name).

    SPEC is anything :func:`repro.numerics.ladder_spec` resolves — a
    preset name, ``float``, or a spec-JSON path.  Defaults to the
    two-tier deployment the docs describe: an exact-int8 latency tier
    and an approximate bulk tier, two replicas each."""
    from repro.serving import TierConfig

    items = items or ["int8=2", "serve-default=2"]
    tiers = []
    for item in items:
        spec, sep, cnt = item.partition("=")
        if not spec:
            raise SystemExit(f"--tier {item!r}: expected SPEC=COUNT")
        try:
            count = int(cnt) if sep else 1
        except ValueError:
            raise SystemExit(
                f"--tier {item!r}: COUNT must be an integer") from None
        tiers.append(TierConfig(name=spec, spec=spec, count=count))
    return tiers


def run_fleet(args) -> dict:
    """``--fleet``: heterogeneous-numerics replica tiers from ONE float
    init, behind the spec-aware router (repro.serving.fleet).

    Serves the same mixed trace as ``run_engine`` but classed: short
    chat turns are latency-sensitive (exact tiers only), long documents
    are bulk (approximate tiers, spilling into exact ones past
    ``--spill-threshold``).  The run asserts the routing contract —
    every latency request landed on an exact-tier replica — and
    ``--assert-prefix-share`` additionally asserts a cross-replica
    prefix-cache adoption (the CI fleet smoke)."""
    from repro.numerics import ladder_spec
    from repro.serving import build_fleet

    cfg = get_config(args.arch)
    tiers = _parse_tiers(args.tier)
    api = build_model(cfg)
    params_float = api.init(jax.random.PRNGKey(0))

    def pack(spec_name, _p=params_float, _cfg=cfg):
        label, spec = ladder_spec(spec_name)
        if spec is None:
            return _p, label, None
        return (build_serving_params(_p, _cfg, ServeConfig(spec=spec)),
                label, spec)

    ecfg = EngineConfig(slots=args.slots, max_len=args.max_len,
                        prefill_chunk=args.chunk,
                        cache_dtype=args.cache_dtype,
                        mixed_batches=not args.no_mixed,
                        kv_layout=args.kv_layout,
                        kv_block_size=args.block_size,
                        kv_blocks=args.kv_blocks,
                        prefix_cache=not args.no_prefix_cache,
                        trace=bool(args.trace_dir))
    fleet = build_fleet(cfg, params_float, tiers, ecfg, pack, api=api,
                        policy=args.route_policy,
                        spill_threshold=args.spill_threshold or None)
    by_id = {r.replica_id: r for r in fleet.replicas}
    print(f"arch={cfg.name} fleet replicas={len(fleet.replicas)} "
          f"policy={fleet.policy} spill_threshold={fleet.spill_threshold} "
          f"layout={ecfg.kv_layout}")
    for rep in fleet.replicas:
        print(f"  replica {rep.replica_id}: numerics={rep.engine.numerics} "
              f"exact={rep.exact}")

    if args.assert_prefix_share:
        # the CI fleet smoke: warm ONE replica of a multi-replica tier,
        # share, then prove a sibling replica serves the same prompt from
        # the imported blocks
        if ecfg.kv_layout != "paged" or not ecfg.prefix_cache:
            raise SystemExit("--assert-prefix-share needs --kv-layout "
                             "paged with the prefix cache enabled")
        pair = next((tuple(reps) for t in tiers
                     for reps in [[r for r in fleet.replicas
                                   if r.tier.name == t.name]]
                     if len(reps) >= 2), None)
        if pair is None:
            raise SystemExit("--assert-prefix-share needs a tier with "
                             ">= 2 replicas")
        warm_rep, cold_rep = pair[0], pair[1]
        rng = np.random.default_rng(17)
        shared = rng.integers(
            0, cfg.vocab,
            min(4 * ecfg.prefill_chunk, ecfg.max_len // 2)).tolist()
        warm_rep.engine.submit(shared, 2)
        warm_rep.engine.drain()
        imported = fleet.share_prefixes()
        hit = cold_rep.engine.submit(
            shared + rng.integers(0, cfg.vocab, 4).tolist(), 4)
        cold_rep.engine.drain()
        shareable = min(len(shared) // ecfg.kv_block_size
                        * ecfg.kv_block_size, len(shared) - 1)
        assert imported > 0, "share_prefixes imported nothing"
        assert hit.prefix_hit_tokens >= shareable, (
            hit.prefix_hit_tokens, shareable)
        print(f"  prefix share: {imported} blocks "
              f"{warm_rep.replica_id} -> fleet; {cold_rep.replica_id} "
              f"hit {hit.prefix_hit_tokens} tokens")

    trace = mixed_trace(cfg, args.requests, ecfg.max_len, ecfg.prefill_chunk)
    share_every = args.share_prefixes_every or None
    placed = []
    for i, (prompt, gen) in enumerate(trace):
        # mixed_trace makes every third request a long document — that is
        # the bulk/background traffic; chat turns are latency-sensitive
        klass = "bulk" if i % 3 == 2 else "latency"
        r = fleet.submit(prompt, gen, priority=0 if klass == "latency"
                         else 1, klass=klass)
        attempt = 0
        while (r.state.value == "rejected"
               and (r.reject_reason or "").startswith("queue full")
               and attempt < args.submit_retries):
            for _ in range(2 ** attempt):
                fleet.step()
            attempt += 1
            r = fleet.submit(prompt, gen, priority=0 if klass == "latency"
                             else 1, klass=klass)
        if r.state.value == "rejected":
            print(f"  request {r.rid} rejected: {r.reject_reason}")
        else:
            placed.append(r)
    finished = fleet.drain(share_every=share_every)

    # the routing contract: latency-class requests only on exact replicas
    for r in placed:
        if r.fleet_class == "latency" and fleet.policy == "spec-aware":
            assert by_id[r.fleet_replica].exact, (
                f"latency request {r.rid} on approximate replica "
                f"{r.fleet_replica}")
    snap = fleet.snapshot()
    print(f"finished {len(finished)}/{len(placed)} placed requests, "
          f"{fleet.compile_count()} compiled shapes across the fleet")
    for tname, ts in snap["tiers"].items():
        print(f"  tier {tname}: numerics={ts['numerics']} "
              f"engines={ts['engines']} finished={ts['requests_finished']} "
              f"gen_tok={ts['generated_tokens']} "
              f"prefix_imports={ts['prefix_imports']}")
    rt = snap["routing"]
    print(f"  routing: {rt['routed_by_class']} spills={rt['spills']}")
    print(json.dumps(snap["fleet"], indent=2))
    if args.trace_dir:
        paths = fleet.write_traces(args.trace_dir)
        print(f"traces: {len(paths)} replica files -> {args.trace_dir}")
    if getattr(args, "prom_out", None):
        from repro.serving.prom import to_openmetrics
        with open(args.prom_out, "w") as f:
            f.write(to_openmetrics(snap["fleet"], labels={"fleet": "all"}))
        print(f"openmetrics: {args.prom_out}")
    return snap


def run_legacy(args) -> None:
    cfg = get_config(args.arch)
    params, label, _, _ = _prepare_params(cfg, args)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)))
    max_len = args.prompt_len + args.gen

    prefill = jax.jit(make_prefill_step(cfg, max_len))
    decode = jax.jit(make_decode_step(cfg))

    t0 = time.time()
    logits, cache = prefill(params, {"tokens": prompt})
    tok = jnp.argmax(logits, -1)[:, None]
    outs = [tok]
    for _ in range(args.gen - 1):
        logits, cache = decode(params, tok, cache)
        tok = jnp.argmax(logits, -1)[:, None]
        outs.append(tok)
    gen = jnp.concatenate(outs, axis=1)
    dt = time.time() - t0
    print(f"arch={cfg.name} numerics={label}")
    print(f"generated {args.batch}x{args.gen} tokens in {dt:.2f}s "
          f"({args.batch*args.gen/dt:.1f} tok/s)")
    print("sample:", np.asarray(gen[0])[:16].tolist())


def _plan_diff(plan: PackPlan, params, ckpt_path: str) -> int:
    """Compare a resolved plan against the NumericsSpec a checkpoint was
    saved with (its ``numerics`` metadata), re-resolved over the same
    abstract parameters.  Prints per-layer drift rows; returns the number
    of drifted layers (the plan subcommand's exit code), so 0 == the
    checkpoint really will serve under the numerics the CLI describes."""
    from repro.checkpoint.manager import read_meta

    meta = read_meta(ckpt_path)
    nd = (meta or {}).get("numerics")
    if nd is None:
        raise SystemExit(f"{ckpt_path}: checkpoint metadata carries no "
                         "numerics spec (saved before numerics persistence, "
                         "or not via save_pytree/CheckpointManager?)")
    ck_spec = NumericsSpec.from_dict(nd)
    ck_plan = ck_spec.resolve(params)
    mine = {e.path: e.label for e in plan.entries}
    theirs = {e.path: e.label for e in ck_plan.entries}
    drift = [(p, mine.get(p), theirs.get(p))
             for p in sorted(set(mine) | set(theirs))
             if mine.get(p) != theirs.get(p)]
    print(f"checkpoint spec: {ck_spec.name!r} ({ckpt_path})")
    if not drift:
        print(f"plan matches checkpoint: {len(mine)} layers, no drift")
        return 0
    print(f"PLAN DRIFT: {len(drift)} layer(s) differ (cli vs checkpoint)")
    for path, a, b in drift:
        print(f"  {path}: {a or '<absent>'} != {b or '<absent>'}")
    return len(drift)


def run_plan(args) -> PackPlan:
    """`plan` subcommand: resolve and print the per-layer assignment table
    without packing — parameters are abstract (eval_shape), so this is
    instant and allocation-free."""
    cfg = get_config(args.arch)
    api = build_model(cfg)
    params = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0)))
    spec = _spec_from_args(args)
    if spec is None:
        raise SystemExit("nothing to plan for float serving (pick --preset, "
                         "--spec-json, or --mode/--m)")
    plan = spec.resolve(params)
    if args.json:
        print(plan.to_json(indent=2))
    else:
        print(f"arch={cfg.name} spec={spec.name}")
        print(plan.table())
    if getattr(args, "diff_checkpoint", None):
        drifted = _plan_diff(plan, params, args.diff_checkpoint)
        if drifted:
            raise SystemExit(drifted)
    return plan


def _add_numerics_flags(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--arch", default="olmo-1b-reduced")
    ap.add_argument("--mode", default="perforated",
                    choices=["exact", "perforated", "truncated", "recursive", "float"])
    ap.add_argument("--m", type=int, default=2)
    ap.add_argument("--no-cv", action="store_true")
    ap.add_argument("--preset", default=None,
                    help="named NumericsSpec preset (serve-default, int8, ...)")
    ap.add_argument("--spec-json", default=None, metavar="FILE",
                    help="serve under a NumericsSpec loaded from a JSON file")


def main(argv=None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)

    if argv and argv[0] == "plan":
        ap = argparse.ArgumentParser(prog="repro.launch.serve plan")
        _add_numerics_flags(ap)
        ap.add_argument("--json", action="store_true",
                        help="emit the PackPlan as JSON instead of a table")
        ap.add_argument("--diff-checkpoint", default=None, metavar="PATH",
                        help="also resolve the NumericsSpec persisted in "
                             "this checkpoint's metadata and exit nonzero "
                             "if any layer's assignment drifted from the "
                             "CLI spec")
        run_plan(ap.parse_args(argv[1:]))
        return

    ap = argparse.ArgumentParser()
    _add_numerics_flags(ap)
    # engine path (default)
    ap.add_argument("--engine", action="store_true",
                    help="continuous-batching engine (default path)")
    ap.add_argument("--legacy", action="store_true",
                    help="old lock-step rectangular batch loop")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=32)
    ap.add_argument("--cache-dtype", default="bfloat16",
                    choices=["bfloat16", "float32", "int8"])
    ap.add_argument("--no-mixed", action="store_true",
                    help="disable mixed prefill+decode batches (fall back "
                         "to whole-batch alternation)")
    ap.add_argument("--kv-layout", default="contiguous",
                    choices=["contiguous", "paged"],
                    help="KV memory model: contiguous max_len stripes, or "
                         "block-granular paged allocation with prefix reuse")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV block (paged layout)")
    ap.add_argument("--kv-blocks", type=int, default=0,
                    help="usable blocks in the shared pool (0 = capacity "
                         "parity with contiguous)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable the content-hash shared-prefix cache")
    ap.add_argument("--shared-prefix-pair", action="store_true",
                    help="prepend a warmed shared-prefix request pair and "
                         "report/assert the prefix hit (CI paged smoke)")
    # speculative decode (repro.serving.speculative)
    ap.add_argument("--speculative-k", type=int, default=0, metavar="K",
                    help="self-verifying speculative decode: draft up to K "
                         "greedy tokens per slot through the approximate "
                         "parameters, verify them in one exact-int8 chunk "
                         "call (0 disables); the numerics flags then "
                         "describe the DRAFT spec")
    ap.add_argument("--draft-spec", default=None, metavar="NAME_OR_FILE",
                    help="draft NumericsSpec: a preset name or a spec-JSON "
                         "file path (default: whatever --mode/--m/--preset "
                         "resolve to)")
    ap.add_argument("--assert-acceptance", action="store_true",
                    help="fail unless the verifier accepted at least one "
                         "draft token (CI speculative smoke)")
    # observability (repro.serving.telemetry / repro.quant.error_probe)
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="write the request-span trace here: *.jsonl for "
                         "JSONL, anything else for Chrome trace_event JSON "
                         "(opens in Perfetto); enables tracing")
    ap.add_argument("--metrics-window", type=float, default=0.0,
                    metavar="SECONDS",
                    help="windowed time-series sample interval "
                         "(0 disables; samples ride the trace as counters)")
    ap.add_argument("--error-probe-every", type=int, default=0, metavar="N",
                    help="every N engine steps re-run one scheduled batch "
                         "row through the exact-int8 path and record "
                         "approx-vs-exact error moments (0 disables)")
    # robustness (repro.serving.governor / repro.quant.faults)
    ap.add_argument("--governor", action="store_true",
                    help="attach the accuracy-SLO numerics governor: the "
                         "error probe's running variance estimate walks the "
                         "degradation ladder (CLI spec -> int8 -> float), "
                         "hot-swapping the live pack on breach and relaxing "
                         "back after clean windows")
    ap.add_argument("--slo-err-var", type=float, default=None, metavar="VAR",
                    help="accuracy SLO: max acceptable running logits "
                         "error variance (approx vs exact; required with "
                         "--governor)")
    ap.add_argument("--governor-window", type=int, default=4,
                    metavar="PROBES",
                    help="probe reports per governor window (count-based, "
                         "deterministic)")
    ap.add_argument("--governor-relax-after", type=int, default=3,
                    metavar="WINDOWS",
                    help="consecutive clean windows before relaxing one "
                         "rung back down")
    ap.add_argument("--layer-slo", action="append", metavar="PATTERN=VAR",
                    help="per-layer accuracy SLO for the governor: fnmatch "
                         "layer-path pattern -> max probe err-var ceiling "
                         "(e.g. 'blocks/0/*=1e-4'); first matching pattern "
                         "wins; repeatable; breaches escalate with reason "
                         "layer_slo_breach naming the layer")
    ap.add_argument("--inject-faults", default=None, metavar="SPEC",
                    help="deterministic fault injection, as "
                         "KIND@EVERY[@START-STOP][@LAYERS] with KIND in "
                         "nan|inf|spike|dense-noise (e.g. 'nan@8', "
                         "'dense-noise@2@10-50', "
                         "'dense-noise@1@blocks/0/*'); step-surface kinds "
                         "corrupt served logits and must be fully "
                         "quarantined (asserted), dense-noise corrupts the "
                         "probe's observation and drives the governor — "
                         "the optional fnmatch LAYERS segment confines it "
                         "to matching packed layers")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="fault injector RNG seed (same seed = same "
                         "injected steps and rows)")
    ap.add_argument("--detect-faults", action="store_true",
                    help="engine-side NaN/divergence detection + "
                         "quarantine even without an injector")
    # observability (repro.serving.shadow / repro.serving.prom)
    ap.add_argument("--shadow-spec", default=None, metavar="NAME_OR_FILE",
                    help="A/B shadow serving: replay a deterministic "
                         "sample of finished requests teacher-forced "
                         "through a second pack built under this spec "
                         "(preset name or spec-JSON path) and diff tokens/"
                         "logits/modeled power; incompatible with "
                         "--speculative-k and --governor")
    ap.add_argument("--shadow-fraction", type=float, default=0.25,
                    metavar="F",
                    help="fraction of finished requests replayed through "
                         "the shadow pack (deterministic every-Nth "
                         "sampling; default %(default)s)")
    ap.add_argument("--assert-shadow", action="store_true",
                    help="fail unless the shadow replayed >= 1 request "
                         "and reached a verdict (CI shadow smoke)")
    ap.add_argument("--assert-layer-breach", nargs="?", const="*",
                    default=None, metavar="PATTERN",
                    help="fail unless the governor escalated with reason "
                         "layer_slo_breach on a layer matching PATTERN "
                         "(default any) AND that layer appears in the "
                         "windowed per-layer err-var samples (CI "
                         "layer-SLO smoke; needs --governor --layer-slo "
                         "--metrics-window)")
    ap.add_argument("--prom-out", default=None, metavar="FILE",
                    help="write the final metrics snapshot (engine, or "
                         "merged fleet with --fleet) as OpenMetrics text "
                         "exposition to FILE")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request latency SLO in ms from submission "
                         "(0 = none); expired queued requests are purged, "
                         "running ones stop with finish_reason 'deadline'")
    ap.add_argument("--submit-retries", type=int, default=3, metavar="N",
                    help="bounded retry budget for queue-full submissions "
                         "(exponential backoff in engine steps: 1, 2, 4 "
                         "... steps drained between attempts)")
    # fleet serving (repro.serving.fleet)
    ap.add_argument("--fleet", action="store_true",
                    help="serve through heterogeneous-numerics replica "
                         "tiers behind the spec-aware router instead of "
                         "one engine; the numerics flags are ignored — "
                         "--tier chooses each tier's spec")
    ap.add_argument("--tier", action="append", metavar="SPEC=COUNT",
                    help="one fleet tier: COUNT replicas packed under "
                         "SPEC (a preset name, 'float', or a spec-JSON "
                         "path); repeatable (default: int8=2 "
                         "serve-default=2)")
    ap.add_argument("--route-policy", default="spec-aware",
                    choices=["spec-aware", "least-loaded", "round-robin"],
                    help="fleet routing policy (spec-aware: latency "
                         "class -> exact tiers, bulk -> approximate "
                         "tiers, least-loaded within each)")
    ap.add_argument("--spill-threshold", type=int, default=0, metavar="N",
                    help="bulk traffic spills from a saturated "
                         "approximate tier into the exact tiers once "
                         "the least-loaded bulk replica has >= N "
                         "pending requests (0 disables; latency "
                         "traffic never spills to approximate tiers)")
    ap.add_argument("--share-prefixes-every", type=int, default=4,
                    metavar="STEPS",
                    help="propagate prefix-cache blocks across each "
                         "tier's replicas every N fleet iterations "
                         "while draining (0 disables)")
    ap.add_argument("--assert-prefix-share", action="store_true",
                    help="warm one replica, share, and assert a sibling "
                         "replica's prefix-cache hit on the imported "
                         "blocks (CI fleet smoke; needs --kv-layout "
                         "paged and a tier with >= 2 replicas)")
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="fleet tracing: write one JSONL span trace per "
                         "replica into DIR (feed them all to "
                         "tools/trace_report.py --trace ...)")
    # legacy path knobs
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    print(f"device: platform={devices[0].platform} "
          f"kind={devices[0].device_kind} count={len(devices)}")
    if args.legacy:
        run_legacy(args)
    elif args.fleet:
        run_fleet(args)
    else:
        run_engine(args)


if __name__ == "__main__":
    main()
