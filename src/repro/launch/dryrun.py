import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell and extract memory/cost/roofline analyses.

THE TWO LINES ABOVE MUST STAY FIRST — jax locks the device count at first
initialization, and the production meshes need 512 placeholder host devices.
This module is the ONLY place that flag is set (tests/benches see 1 device).

Per cell:
    with mesh:
        lowered  = jax.jit(step, in_shardings=..., out_shardings=...,
                           donate_argnums=...).lower(*abstract_inputs)
        compiled = lowered.compile()
        memory_analysis(), cost_analysis(), collective parse -> roofline

Cells: 10 archs x 4 shapes, minus the assigned skips (encoder-only decode,
full-attention long_500k) = 31 runnable cells, each on the single-pod
(16, 16) mesh (roofline table) AND the multi-pod (2, 16, 16) mesh (proves
the "pod" axis shards).  Results append to artifacts/dryrun/*.json so the
sweep is resumable.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b \
        --shape train_4k --multi-pod both
    PYTHONPATH=src python -m repro.launch.dryrun --all
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import get_config, list_archs
from repro.configs.base import ArchConfig
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import model_flops_estimate, roofline_from_compiled
from repro.launch.serve import ServeConfig, build_serving_params, make_decode_step, make_prefill_step
from repro.launch.train import TrainConfig, init_train_state, make_train_step, train_state_shardings
from repro.models import build_model
from repro.models.registry import SHAPES, input_specs, shape_applicable
from repro.parallel import batch_shardings, cache_shardings, param_shardings

ARTIFACT_DIR = os.path.normpath(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "..", "..", "..", "artifacts", "dryrun"))


def _arch_for_run(cfg: ArchConfig, mesh, kind: str) -> ArchConfig:
    """Launch-time overrides: EP MoE on the mesh; bf16 compute."""
    over = {}
    if cfg.mlp == "moe":
        over["moe_impl"] = "ep_psum"
    if kind == "train" and cfg.name in ("deepseek-67b",):
        pass  # fsdp flag handled in TrainConfig
    return dataclasses.replace(cfg, **over) if over else cfg


def _serving_abstract_params(cfg: ArchConfig, scfg: ServeConfig):
    """Abstract packed serving params via eval_shape (no allocation)."""
    api = build_model(cfg)

    def build():
        params = api.init(jax.random.PRNGKey(0))
        return build_serving_params(params, cfg, scfg)

    return jax.eval_shape(build)


def run_cell(arch: str, shape: str, multi_pod: bool, approx_mode: str = "perforated",
             approx_m: int = 2, overrides: dict | None = None) -> dict:
    """Lower + compile one cell; returns the record for EXPERIMENTS.md.

    ``overrides`` replaces ArchConfig fields (perf variants, e.g.
    sequence_parallel=True) — variant artifacts are kept separate from the
    baselines."""
    t_start = time.time()
    spec = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    base_cfg = get_config(arch)
    cfg = _arch_for_run(base_cfg, mesh, spec.kind)
    overrides = dict(overrides or {})
    microbatches = int(overrides.pop("microbatches", 1))
    moments_bf16 = bool(overrides.pop("moments_bf16", False))
    dp_only = bool(overrides.pop("dp_only", False))
    cache_dtype = overrides.pop("cache_dtype", "bfloat16")
    arch_overrides = overrides
    if arch_overrides:
        cfg = dataclasses.replace(cfg, **arch_overrides)

    record: dict = {
        "arch": arch, "shape": shape,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "multi_pod": multi_pod, "chips": int(n_chips), "kind": spec.kind,
        "overrides": {**arch_overrides, **({"microbatches": microbatches} if microbatches > 1 else {}), **({"moments_bf16": True} if moments_bf16 else {}), **({"dp_only": True} if dp_only else {}), **({"cache_dtype": cache_dtype} if cache_dtype != "bfloat16" else {})},
    }

    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        record.update(status="skip", reason=reason)
        return record

    with jax.set_mesh(mesh):
        if spec.kind == "train":
            fsdp = cfg.name in ("deepseek-67b", "granite-8b")
            from repro.optim import AdamWConfig

            tcfg = TrainConfig(
                fsdp=fsdp, microbatches=microbatches,
                optimizer=AdamWConfig(
                    moment_dtype="bfloat16" if moments_bf16 else "float32"))
            abstract_state = jax.eval_shape(
                lambda: init_train_state(cfg, tcfg, jax.random.PRNGKey(0)))
            state_sh = train_state_shardings(cfg, tcfg, mesh, dp_only=dp_only)
            step = make_train_step(cfg, tcfg, mesh=mesh,
                                   param_sh=state_sh["params"])
            batch_abs = input_specs(cfg, shape)["batch"]
            batch_sh = batch_shardings(batch_abs, mesh, dp_only=dp_only)
            jitted = jax.jit(
                step,
                in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None),
                donate_argnums=(0,),
            )
            lowered = jitted.lower(abstract_state, batch_abs)
        else:
            from repro.core.policy import ApproxPolicy
            from repro.numerics import get_preset

            num_spec = get_preset("serve-default",
                                  policy=ApproxPolicy(approx_mode, approx_m,
                                                      use_cv=True))
            scfg = ServeConfig(spec=num_spec, cache_dtype=cache_dtype)
            params_abs = _serving_abstract_params(cfg, scfg)
            params_sh = param_shardings(params_abs, mesh, cfg)
            if spec.kind == "prefill":
                step = make_prefill_step(cfg, max_len=spec.seq_len, mesh=mesh, scfg=scfg)
                batch_abs = input_specs(cfg, shape)["batch"]
                batch_sh = batch_shardings(batch_abs, mesh)
                api = build_model(cfg)
                cache_abs = jax.eval_shape(
                    lambda: api.init_cache(spec.global_batch, spec.seq_len, jnp.bfloat16))
                cache_sh = cache_shardings(cache_abs, mesh, cfg)
                jitted = jax.jit(
                    step,
                    in_shardings=(params_sh, batch_sh),
                    out_shardings=(None, cache_sh),
                )
                lowered = jitted.lower(params_abs, batch_abs)
            else:  # decode
                step = make_decode_step(cfg, mesh=mesh, scfg=scfg)
                specs = input_specs(cfg, shape)
                cache_abs = specs["cache"]
                if cache_dtype == "int8":
                    api = build_model(cfg)
                    cache_abs = jax.eval_shape(
                        lambda: api.init_cache(spec.global_batch, spec.seq_len,
                                               jnp.int8))
                cache_sh = cache_shardings(cache_abs, mesh, cfg)
                tok_abs = specs["tokens"]
                tok_sh = batch_shardings(tok_abs, mesh)
                jitted = jax.jit(
                    step,
                    in_shardings=(params_sh, tok_sh, cache_sh),
                    out_shardings=(None, cache_sh),
                    donate_argnums=(2,),
                )
                lowered = jitted.lower(params_abs, tok_abs, cache_abs)

        t_lower = time.time()
        compiled = lowered.compile()
        t_compile = time.time()

        mem = compiled.memory_analysis()
        terms = roofline_from_compiled(compiled)

    mf = model_flops_estimate(base_cfg, spec.kind, spec.seq_len, spec.global_batch)
    mf_per_chip = mf / n_chips
    record.update(
        status="ok",
        lower_s=round(t_lower - t_start, 1),
        compile_s=round(t_compile - t_lower, 1),
        memory={
            k: int(getattr(mem, k, 0))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
        },
        roofline=terms.as_dict(),
        model_flops_global=mf,
        model_flops_per_chip=mf_per_chip,
        useful_flops_ratio=(mf_per_chip / terms.flops) if terms.flops else None,
    )
    return record


def _out_path(arch: str, shape: str, multi_pod: bool, variant: str = "") -> str:
    base = ARTIFACT_DIR if not variant else os.path.join(
        os.path.dirname(ARTIFACT_DIR), "perf")
    os.makedirs(base, exist_ok=True)
    pod = "multipod" if multi_pod else "singlepod"
    suffix = f"__{variant}" if variant else ""
    return os.path.join(base, f"{arch}__{shape}__{pod}{suffix}.json")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=[None, *SHAPES])
    ap.add_argument("--multi-pod", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true", help="recompute cached cells")
    ap.add_argument("--approx-mode", default="perforated")
    ap.add_argument("--approx-m", type=int, default=2)
    ap.add_argument("--variant", default="", help="perf-variant artifact label")
    ap.add_argument("--set", action="append", default=[],
                    help="ArchConfig override, e.g. --set sequence_parallel=true")
    args = ap.parse_args(argv)

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        overrides[k] = {"true": True, "false": False}.get(
            v.lower(), int(v) if v.lstrip("-").isdigit() else v)

    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if args.shape is None else [args.shape]
    pods = {"single": [False], "multi": [True], "both": [False, True]}[args.multi_pod]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                path = _out_path(arch, shape, mp, args.variant)
                if os.path.exists(path) and not args.force:
                    print(f"[cached] {arch} {shape} multi_pod={mp}")
                    continue
                label = f"{arch} {shape} multi_pod={mp}"
                if args.variant:
                    label += f" variant={args.variant}"
                print(f"[run] {label} ...", flush=True)
                try:
                    rec = run_cell(arch, shape, mp,
                                   approx_mode=args.approx_mode, approx_m=args.approx_m,
                                   overrides=overrides or None)
                except Exception as e:  # a failure here is a bug in the system
                    rec = {
                        "arch": arch, "shape": shape, "multi_pod": mp,
                        "status": "error", "error": f"{type(e).__name__}: {e}",
                        "traceback": traceback.format_exc()[-4000:],
                    }
                    failures.append(label)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=2)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f" dominant={r['dominant']}"
                             f" compute={r['compute_s']:.2e}s"
                             f" mem={r['memory_s']:.2e}s coll={r['collective_s']:.2e}s"
                             f" compile={rec['compile_s']}s")
                elif status == "skip":
                    extra = f" ({rec['reason']})"
                print(f"[{status}] {label}{extra}", flush=True)
    if failures:
        print(f"\nFAILED cells ({len(failures)}):")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nall requested cells complete")


if __name__ == "__main__":
    main()
