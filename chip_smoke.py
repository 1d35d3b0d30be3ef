#!/usr/bin/env python3
"""Chip smoke: serve olmo-1b at its published widths on one TPU.

    python3 chip_smoke.py               # one chip, four numerics packs
    python3 chip_smoke.py --four-chips  # the fleet, one replica per chip

The one-chip run builds the continuous-batching engine the way the
``serve`` CLI does (``build_serving_params`` + ``ServingEngine``) for each
numerics in turn — float, int8, serve-default (perforated m=2 with the
control variate), serve-default without the control variate, and
serve-default on the Pallas kernels — one pack at a time, releasing the
previous engine.  Each serves the same requests of mixed prompt lengths, so
the run holds prefill, mixed and decode batches, and then serves each
request alone, which must give it the same tokens (the float pack is
reported, not held to it: its float32 projections feed a layernorm whose
row sums can round differently in a decode-shaped and a chunk-shaped
call on the TPU).  Every request must finish with at most two compiled
step shapes per pack.  The logits each engine produced are checked
against a plain float32 forward of the same weights, teacher-forced on
the tokens that engine generated:

* float (bf16 compute) within ``FLOAT_BOUND`` relative L2 error;
* int8 within ``INT8_BOUND``;
* serve-default with CV closer to the reference than without CV;
* the Pallas pack within ``PALLAS_BOUND`` of the jnp pack, in its tokens
  and logits (a miscompiled kernel shows up here), and layer by layer on
  the same activations.

``--four-chips`` runs only the in-process fleet: two int8 and two
serve-default replicas behind the spec-aware router, replica i on device
i, compared token for token with one engine per tier serving the whole
trace.

The weights are the model's random init from ``--seed`` (no checkpoint
ships with the repository).  Lines before the last are information:
compile and step times on the host clock and peak device memory, not
claims.  The last line is one JSON object naming the device.  With no TPU,
or when any phase fails, the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "olmo-1b"
#: relative L2 logit error, engine vs the float32 forward (see module doc)
FLOAT_BOUND = 0.05
INT8_BOUND = 0.25
#: Pallas vs jnp: relative difference of the logits, and per layer on the
#: same activations
PALLAS_BOUND = 1e-3

#: (prompt length, tokens to generate): five requests over four slots, so
#: one waits and later joins running decodes (mixed batches)
JOBS = ((7, 8), (45, 8), (100, 8), (19, 8), (70, 8))
ENGINE = dict(slots=4, max_len=256, prefill_chunk=32)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _prompts(cfg, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [(rng.integers(0, cfg.vocab, plen).tolist(), gen)
            for plen, gen in JOBS]


def _recording_engine_cls():
    """ServingEngine that keeps, per request, the logits of the last valid
    column of every step it took part in (keyed by absolute position), and
    the device time of each step."""
    import jax
    import numpy as np

    from repro.serving import ServingEngine

    class RecordingEngine(ServingEngine):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.logits_at: dict[int, dict[int, np.ndarray]] = {}
            self.step_s: dict[int, list[float]] = {}

        def _dispatch(self, params, batch, tables):
            before = self.pool.lengths()
            t0 = time.perf_counter()
            logits, cache = super()._dispatch(params, batch, tables)
            jax.block_until_ready(logits)
            width = batch.tokens.shape[1]
            self.step_s.setdefault(width, []).append(
                time.perf_counter() - t0)
            nv = np.asarray(batch.n_valid)
            cols = np.maximum(nv - 1, 0)
            rows = np.asarray(logits[np.arange(len(nv)), cols])
            for slot, req in self.active.items():
                if nv[slot] > 0:
                    pos = int(before[slot] + nv[slot] - 1)
                    self.logits_at.setdefault(req.rid, {})[pos] = rows[slot]
            return logits, cache

    return RecordingEngine


def _serve(cfg, api, params, label, jobs, batch_invariant=True):
    """Serve ``jobs`` on a fresh engine, then each job alone on it; returns
    the finished requests of the first run and the engine (its recorded
    logits and step times).  ``batch_invariant`` makes other tokens when
    served alone a failure."""
    from repro.configs.base import EngineConfig

    eng = _recording_engine_cls()(cfg, params, EngineConfig(**ENGINE),
                                  api=api, numerics=label)
    reqs = [eng.submit(p, g) for p, g in jobs]
    t0 = time.perf_counter()
    eng.run()
    wall = time.perf_counter() - t0
    snap = eng.metrics.snapshot()
    for r in reqs:
        check(r.finish_reason == "length"
              and len(r.generated) == r.max_new_tokens,
              f"{label}: request {r.rid} ended {r.state} "
              f"({r.finish_reason}, {len(r.generated)} tokens)")
    for kind in ("prefill_steps", "mixed_steps", "decode_steps"):
        check(snap[kind] > 0, f"{label}: no {kind.split('_')[0]} batch ran")
    # the same requests one at a time: a request's tokens must not depend
    # on the batches it rode in (mixed chunk-shaped calls above, decode-
    # shaped ones here)
    alone = []
    for p, g in jobs:
        alone.append(eng.submit(p, g))
        eng.run()
    differ = [r.rid for r, a in zip(reqs, alone) if r.generated != a.generated]
    if batch_invariant:
        check(not differ, f"{label}: requests {differ} get other tokens "
                          f"when served alone")
    else:
        print(f"  {label}: requests {differ} get other tokens when served "
              f"alone (known: see the module doc)", flush=True)
    check(eng.compile_count() <= 2,
          f"{label}: {eng.compile_count()} compiled step shapes (max 2)")
    steps = {w: sorted(v) for w, v in eng.step_s.items()}
    print(f"  {label}: served {len(reqs)} requests in {wall:.1f}s "
          f"(first steps compile; {eng.compile_count()} shapes), "
          f"prefill/mixed/decode steps {snap['prefill_steps']}/"
          f"{snap['mixed_steps']}/{snap['decode_steps']}, median step s "
          + ", ".join(f"C={w}: {v[len(v) // 2]:.4f}"
                      for w, v in sorted(steps.items())), flush=True)
    return reqs, eng


def _reference_logits(ref_forward, params, reqs):
    """float32 logits of every request's served sequence (prompt plus the
    generated tokens it fed back), right-padded into one batch."""
    import numpy as np

    seqs = [r.prompt + r.generated[:-1] for r in reqs]
    width = -(-max(map(len, seqs)) // 128) * 128
    toks = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    return np.asarray(ref_forward(params, {"tokens": toks}))


def _rel_err(pairs) -> float:
    import numpy as np

    num = sum(float(np.sum((a.astype(np.float64) - b) ** 2)) for a, b in pairs)
    den = sum(float(np.sum(b.astype(np.float64) ** 2)) for _, b in pairs)
    return (num / den) ** 0.5


def _layer_diffs(block_j, block_p, seed: int) -> dict[str, float]:
    """Relative difference of the Pallas pack against the jnp pack, layer by
    layer, for block 0's packed projections on the same activations at
    decode-shaped (4) and prefill-shaped (128) rows.  The activations span
    each layer's whole quantization range and a little beyond it, so the
    codes cover 0..255 (both int8 extremes after the kernel's shift) and
    the clip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.approx_linear import (QuantizedDense,
                                          QuantizedDenseGroup, dense,
                                          dense_group)

    def run(p, x):
        if isinstance(p, QuantizedDenseGroup):
            return jnp.concatenate(list(dense_group(p, x).values()), -1)
        return dense(p, x)

    out = {}
    is_pack = lambda v: isinstance(v, (QuantizedDense, QuantizedDenseGroup))
    flat_j = jax.tree_util.tree_flatten_with_path(block_j, is_leaf=is_pack)[0]
    flat_p = dict(jax.tree_util.tree_flatten_with_path(
        block_p, is_leaf=is_pack)[0])
    rng = np.random.default_rng(seed)
    for path, pj in flat_j:
        if not is_pack(pj):
            continue
        name = jax.tree_util.keystr(path)
        k = pj.pack.w_q.shape[-2]
        sa, za = float(pj.a_qp.scale), float(pj.a_qp.zero_point)
        lo, hi = (-2 - za) * sa, (257 - za) * sa
        for rows in (4, 128):
            x = jnp.asarray(rng.uniform(lo, hi, (rows, k)), jnp.bfloat16)
            yj = np.asarray(jax.jit(run)(pj, x), np.float32)
            yp = np.asarray(jax.jit(run)(flat_p[path], x), np.float32)
            out[f"{name}[M={rows}]"] = _rel_err([(yp, yj)])
    return out


def _pallas_vs_jnp(served) -> tuple[float, list[int]]:
    """Relative difference of the Pallas pack's logits from the jnp pack's
    over every recorded position, and the requests whose tokens differ."""
    (jreqs, jlog), (preqs, plog) = (served["serve-default"],
                                    served["serve-default-pallas"])
    pairs = [(plog[pr.rid][pos], jlog[jr.rid][pos])
             for jr, pr in zip(jreqs, preqs) for pos in sorted(jlog[jr.rid])]
    differ = [i for i, (jr, pr) in enumerate(zip(jreqs, preqs))
              if jr.generated != pr.generated]
    return _rel_err(pairs), differ


def _peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def smoke_one_chip(cfg, seed: int = 0) -> None:
    import jax

    from repro.core.policy import ApproxPolicy
    from repro.launch.serve import ServeConfig, build_serving_params
    from repro.models import build_model
    from repro.numerics import get_preset

    api = build_model(cfg)
    dev = jax.devices()[0]
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(api.init)(
        jax.random.PRNGKey(seed)))
    print(f"{cfg.name}: init {time.perf_counter() - t0:.1f}s, "
          f"{sum(x.size for x in jax.tree.leaves(params)):,} parameters",
          flush=True)
    ref_api = build_model(dataclasses.replace(cfg, compute_dtype="float32"))

    @jax.jit
    def ref_forward(p, batch):
        # a plain float32 forward: full-precision matmul passes on TPU
        with jax.default_matmul_precision("highest"):
            return ref_api.forward(p, batch)

    jobs = _prompts(cfg, seed)
    sd = ApproxPolicy("perforated", 2, use_cv=True)

    def serve_default(**kw):
        return get_preset("serve-default",
                          policy=dataclasses.replace(sd, **kw))

    packs = [
        ("float", None),
        ("int8", get_preset("int8")),
        ("serve-default", serve_default()),
        ("serve-default-nocv", serve_default(use_cv=False)),
        ("serve-default-pallas", serve_default(backend="pallas")),
    ]
    errs, served, block0 = {}, {}, {}
    for name, spec in packs:
        t0 = time.perf_counter()
        served_params = params if spec is None else jax.block_until_ready(
            build_serving_params(params, cfg, ServeConfig(spec=spec)))
        print(f"{name}: pack {time.perf_counter() - t0:.1f}s, peak bytes "
              f"{_peak_bytes(dev)}", flush=True)
        reqs, eng = _serve(cfg, api, served_params, name, jobs,
                           batch_invariant=spec is not None)
        ref = _reference_logits(ref_forward, params, reqs)
        errs[name] = _rel_err([(lg, ref[i, pos])
                               for i, r in enumerate(reqs)
                               for pos, lg in eng.logits_at[r.rid].items()])
        print(f"  {name}: logits rel err vs float32 forward "
              f"{errs[name]:.6f}", flush=True)
        if name in ("serve-default", "serve-default-pallas"):
            served[name] = (reqs, eng.logits_at)
            block0[name] = jax.tree.map(lambda a: a[0],
                                        served_params["blocks"])
        del eng, reqs, served_params, ref
        gc.collect()

    layer = _layer_diffs(block0["serve-default"],
                         block0["serve-default-pallas"], seed)
    for where, d in layer.items():
        print(f"  pallas vs jnp block 0 {where}: rel diff {d:.3e}",
              flush=True)
    failures = []
    if errs["float"] > FLOAT_BOUND:
        failures.append(f"float engine logits err {errs['float']:.6f} > "
                        f"{FLOAT_BOUND}")
    if errs["int8"] > INT8_BOUND:
        failures.append(f"int8 logits err {errs['int8']:.6f} > {INT8_BOUND}")
    if errs["serve-default"] >= errs["serve-default-nocv"]:
        failures.append(f"CV does not reduce the logits error: "
                        f"{errs['serve-default']:.6f} vs "
                        f"{errs['serve-default-nocv']:.6f}")
    bad_layers = {w: d for w, d in layer.items() if d > PALLAS_BOUND}
    if bad_layers:
        failures.append(f"Pallas layers differ from jnp: {bad_layers}")
    diff, differ = _pallas_vs_jnp(served)
    print(f"pallas vs jnp serve-default: logits rel diff {diff:.3e}, "
          f"logits err {errs['serve-default']:.6f} (jnp) vs "
          f"{errs['serve-default-pallas']:.6f} (pallas), requests with other "
          f"tokens {differ}", flush=True)
    if diff > PALLAS_BOUND or differ:
        failures.append(f"Pallas pack differs from the jnp pack: logits rel "
                        f"diff {diff:.3e} (max {PALLAS_BOUND}), requests with "
                        f"other tokens {differ}")
    print(f"peak bytes in use {_peak_bytes(dev)}", flush=True)
    check(not failures, "; ".join(failures))


def smoke_four_chips(cfg, seed: int = 0) -> None:
    import jax

    from repro.configs.base import EngineConfig
    from repro.launch.serve import ServeConfig, build_serving_params
    from repro.models import build_model
    from repro.numerics import get_preset
    from repro.serving import ServingEngine, TierConfig, build_fleet

    devices = jax.devices()
    check(len(devices) == 4, f"--four-chips needs 4 devices, "
                             f"found {len(devices)}")
    api = build_model(cfg)
    names = ("int8", "serve-default")
    t0 = time.perf_counter()
    params = jax.jit(api.init)(jax.random.PRNGKey(seed))
    packs = {n: jax.block_until_ready(build_serving_params(
        params, cfg, ServeConfig(spec=get_preset(n)))) for n in names}
    del params
    gc.collect()
    print(f"{cfg.name}: init + {len(packs)} packs "
          f"{time.perf_counter() - t0:.1f}s", flush=True)

    ecfg = EngineConfig(**ENGINE)
    fleet = build_fleet(cfg, None, [TierConfig(n, n, count=2) for n in names],
                        ecfg, pack=lambda n: (packs[n], n, get_preset(n)),
                        api=api)
    jobs = _prompts(cfg, seed) + _prompts(cfg, seed + 1)[:3]
    t0 = time.perf_counter()
    placed = [fleet.submit(p, g, klass="bulk" if i % 2 else "latency")
              for i, (p, g) in enumerate(jobs)]
    fleet.drain()
    print(f"fleet: served {len(placed)} requests in "
          f"{time.perf_counter() - t0:.1f}s (first steps compile)",
          flush=True)

    homes = []
    for rep in fleet.replicas:
        on = {d for tree in (rep.engine.params, rep.engine.pool.cache)
              for x in jax.tree.leaves(tree) for d in x.devices()}
        check(len(on) == 1, f"{rep.replica_id} spans devices {on}")
        homes.append(on.pop())
        check(rep.routed > 0, f"{rep.replica_id} served nothing")
        check(rep.engine.compile_count() <= 2,
              f"{rep.replica_id}: {rep.engine.compile_count()} compiled "
              f"step shapes (max 2)")
        print(f"  replica {rep.replica_id} on {homes[-1]}: "
              f"routed {rep.routed}, peak bytes "
              f"{_peak_bytes(homes[-1])}", flush=True)
    check(len(set(homes)) == 4, f"replicas share devices: {homes}")
    for r in placed:
        check(r.finish_reason == "length",
              f"fleet request {r.rid} ended {r.finish_reason}")

    # Reference: one engine per tier, on device 0, serves the whole trace;
    # every request the fleet placed on that tier must get the same tokens
    mismatched = []
    for n in names:
        eng = ServingEngine(cfg, packs[n], ecfg, api=api, numerics=n)
        solo = [eng.submit(p, g) for p, g in jobs]
        eng.run()
        mine = [i for i, r in enumerate(placed) if r.fleet_tier == n]
        mismatched += [f"{i} on {placed[i].fleet_replica}" for i in mine
                       if placed[i].generated != solo[i].generated]
        print(f"  one {n} engine fed the whole trace: "
              f"{sum(placed[i].generated == solo[i].generated for i in mine)}"
              f"/{len(mine)} of the tier's fleet requests token-identical",
              flush=True)
        del eng
        gc.collect()
    check(not mismatched, f"fleet requests differ from one engine of their "
                          f"tier: {mismatched}")
    print(f"fleet tokens match one engine per tier for all {len(placed)} "
          f"requests", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica fleet, one per chip")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random init and the prompts")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r} devices)",
              file=sys.stderr)
        return 2
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}; compile cache {enable_compile_cache()}",
          flush=True)
    try:
        if args.four_chips:
            smoke_four_chips(get_config(ARCH), seed=args.seed)
        else:
            smoke_one_chip(get_config(ARCH), seed=args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
