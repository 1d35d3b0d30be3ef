"""The harness end to end at test size on the CPU (the look for a chip
skipped): it finds a cell, a mix, a per-layer metric and a model family
added as new files alone; it refuses to run without a TPU or on an unknown
chip, and a configuration that names no known family; and its comparison
with the reference comes out false under the control and under each fault
the serving path can have."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import jax.numpy as jnp
import pytest

from bench import run as R

BENCH = Path(__file__).resolve().parents[1]
FIXTURES = Path(__file__).resolve().parent / "fixtures"
SEED = 2**33 + 77


def _run(name, root=FIXTURES, trace=False, control=False, seconds=1.5,
         seed=SEED):
    cell = R.load_cell(name, root=root, benchmark=root / "BENCHMARK.json")
    return R.run_cell(cell, seed, seconds, trace, root=root,
                      require_tpu=False, control=control,
                      t_start=time.perf_counter())


def _over_limit(res) -> set:
    """The compared numbers of the cell that read above their limits."""
    limits = R.load_json(FIXTURES / "workloads" / f"{res['_cell']}.json")
    return {n for n, c in res["checks"].items() if n in limits["limits"]
            and c["value"] > c["limit"]}


def test_new_cell_config_mix_and_metric_are_files_alone(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(FIXTURES, root)
    (root / "metrics").mkdir()
    # a new configuration, a new mix, a new cell on them, a new per-layer
    # metric: new files, and new entries in BENCHMARK.json; no file the
    # harness had is edited
    config = json.loads((root / "configs" / "tiny-granite.json").read_text())
    config["model"]["n_layers"] = config["overrides"]["n_layers"] = 1
    config["name"] = "tiny-granite-1l"
    (root / "configs" / "tiny-granite-1l.json").write_text(json.dumps(config))
    (root / "traffic" / "tiny-short.json").write_text(json.dumps({
        "loop": "open", "max_total": 64,
        "prompt": {"dist": "uniform", "min": 4, "max": 12},
        "output": {"dist": "fixed", "value": 5, "min": 1, "max": 8}}))
    (root / "workloads" / "tiny-int8-short.json").write_text(json.dumps({
        "config": "tiny-granite-1l", "traffic": "tiny-short", "chips": 1,
        "rate_per_s": 8.0, "limits": {"worst_request_gap": 0.08}}))
    (root / "metrics" / "tokens_per_request.py").write_text(
        "def read(run):\n"
        "    done = [r for r in run.recs if r.tokens]\n"
        "    return sum(len(r.tokens) for r in done) / len(done)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-granite-1l", "file":
                             "bench/configs/tiny-granite-1l.json"})
    bench["workloads"].append({"name": "tiny-int8-short", "config":
                               "tiny-granite-1l", "traffic": "tiny-short",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p90_ms", "itl_p95_ms"):
            m["workloads"].append("tiny-int8-short")
    bench["per_layer"].append({
        "name": "tokens_per_request", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "ttft_p90_ms", "workloads": ["tiny-int8-short"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    res = _run("tiny-int8-short", root=root, trace=True)
    assert res["correct"], res["checks"]
    assert res["metrics"]["tokens_per_request"]["value"] == pytest.approx(5)
    res = _run("tiny-int8-short", root=root)
    assert set(res["metrics"]) == {"ttft_p90_ms", "itl_p95_ms", "setup_s"}


#: a family that is not in the benchmark: dense GQA with an untied head,
#: one leaf more than ``dense_gqa``; the head is served packed, so its
#: reference and counts are those of ``dense_gqa`` plus that layer
TOY_FAMILY = '''
import functools

import jax
import jax.numpy as jnp

from bench import counts
from bench import reference as ref
from bench.families import dense_gqa as base
from bench.weights import make_tree

MODEL_KEYS = base.MODEL_KEYS + ("qk_norm",)
matmul_params = base.matmul_params  # the untied head is the tied one's size
nominal_ops = base.nominal_ops


def shapes(model):
    return {**base.shapes(model),
            "lm_head": {"w": (model["d_model"], model["vocab"])}}


def make(model, seed, dtype="float32"):
    return make_tree(shapes(model), seed, dtype, base.leaf)


def _logits(w, tokens, model, bits):
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))
    x = w["embed"]["table"].astype(jnp.float32)[tokens]
    x, _ = jax.lax.scan(
        lambda x, lw: (base._layer(x, f32(lw), model, bits), None), x,
        w["blocks"])
    x = ref.norm(x, f32(w["final_norm"]).get("scale"), model["norm"])
    return ref.dense(x, w["lm_head"]["w"].astype(jnp.float32), bits)


@functools.partial(jax.jit, static_argnames=("model_items", "bits"))
def _full(w, tokens, model_items, bits):
    with jax.default_matmul_precision(ref.PRECISION):
        return _logits(w, tokens, dict(model_items), bits)


def forward(w, tokens, model, bits=None):
    return _full(w, jnp.asarray(tokens, jnp.int32),
                 tuple(sorted(model.items())), bits)


def readings(w, tokens, picks, model, bits=None):
    return ref.picked(forward(w, tokens, model, bits),
                      jnp.asarray(picks, jnp.int32))


def dense_work(model, rows, cv):
    ops, nbytes = base.dense_work(model, rows, cv)
    o, b = counts.layer_work(rows, model["d_model"], model["vocab"], cv)
    return ops + o, nbytes + b
'''


def _toy_family_root(tmp_path) -> Path:
    """The fixtures with a new family, a configuration naming it and a
    cell on that configuration: new files, and new entries in
    BENCHMARK.json; no file the harness had is edited."""
    root = tmp_path / "bench"
    shutil.copytree(FIXTURES, root)
    (root / "families").mkdir()
    (root / "families" / "dense_untied.py").write_text(TOY_FAMILY)
    config = json.loads((root / "configs" / "tiny-granite.json").read_text())
    config.update(name="tiny-granite-untied", family="dense_untied")
    config["model"].update(tie_embeddings=False, qk_norm=False)
    config["overrides"]["tie_embeddings"] = False
    (root / "configs" / "tiny-granite-untied.json").write_text(
        json.dumps(config))
    spec = json.loads((root / "workloads" / "tiny-int8-docs.json")
                      .read_text())
    # the packed head adds to the served gaps: at this size sound runs read
    # up to 0.064 / 0.159 and the int4 control 2.19 / 2.63 (CPU)
    spec.update(config="tiny-granite-untied",
                limits={"served_gap_mean": 0.3, "worst_request_gap": 0.6})
    (root / "workloads" / "tiny-untied-docs.json").write_text(
        json.dumps(spec))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-granite-untied", "file":
                             "bench/configs/tiny-granite-untied.json"})
    bench["workloads"].append({"name": "tiny-untied-docs", "config":
                               "tiny-granite-untied", "traffic": "tiny-docs",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny-int8-docs" in m.get("workloads", ()):
            m["workloads"].append("tiny-untied-docs")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_new_family_is_files_alone(tmp_path):
    from repro.models import build_model

    root = _toy_family_root(tmp_path)
    cell = R.load_cell("tiny-untied-docs", root=root,
                       benchmark=root / "BENCHMARK.json")
    assert Path(cell.family.__file__) == root / "families" / "dense_untied.py"
    cfg = R.arch_config(cell)
    R.check_layout(build_model(cfg), cell, cfg.param_dtype)
    # the program's tree has the toy's extra leaf, which dense GQA lacks
    dense = R.Cell(cell.name, cell.spec, cell.config, cell.mix,
                   cell.benchmark, family=R.load_family(
                       root, "tiny-granite-untied",
                       {**cell.config, "family": "dense_gqa"}))
    with pytest.raises(ValueError, match="lm_head"):
        R.check_layout(build_model(cfg), dense, cfg.param_dtype)
    # set-up, the window and the comparison with the toy's reference, and
    # its control: the toy's reference at int4
    res = _run("tiny-untied-docs", root=root)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"gen_tok_per_s", "setup_s"}
    assert res["_extra"]["served_tokens"] > 0
    res = _run("tiny-untied-docs", root=root, control=True)
    assert not res["correct"]
    assert res["checks"]["served_gap_mean"]["value"] > 0.3


def test_family_model_keys_are_checked_against_the_program(tmp_path):
    root = _toy_family_root(tmp_path)
    path = root / "configs" / "tiny-granite-untied.json"
    config = json.loads(path.read_text())
    config["model"]["qk_norm"] = True  # dense_gqa does not check it
    path.write_text(json.dumps(config))
    cell = R.load_cell("tiny-untied-docs", root=root,
                       benchmark=root / "BENCHMARK.json")
    with pytest.raises(ValueError, match="qk_norm"):
        R.arch_config(cell)


@pytest.mark.parametrize("family", [None, "no_such_family", "../run"],
                         ids=["missing", "unknown", "a_path"])
def test_configuration_without_a_known_family_is_refused(tmp_path, family):
    root = _toy_family_root(tmp_path)
    path = root / "configs" / "tiny-granite-untied.json"
    config = json.loads(path.read_text())
    if family is None:
        del config["family"]
    else:
        config["family"] = family
    path.write_text(json.dumps(config))
    with pytest.raises(ValueError, match="tiny-granite-untied") as err:
        R.load_cell("tiny-untied-docs", root=root,
                    benchmark=root / "BENCHMARK.json")
    assert family is None or family in str(err.value)


def test_sound_run_reports_every_metric():
    t = time.perf_counter()
    res = _run("tiny-sd-chat")
    wall = time.perf_counter() - t
    assert res["correct"], res["checks"]
    # set-up ends at the first request due: the lead-in and the window
    # come after it
    lead = res["_extra"]["lead_in_s"]
    assert lead >= 0.5
    assert res["metrics"]["setup_s"]["value"] <= wall - lead - 1.5
    assert set(res["metrics"]) == {"ttft_p90_ms", "itl_p95_ms", "setup_s"}
    assert res["attempted"] >= 5 and res["failed"] == 0
    assert list(res)[-2:] == ["checks", "_extra"]
    assert res["checks"]["compiles_in_window"]["value"] == 0


def test_no_tpu_exits_nonzero_without_a_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "olmo1b-sd-chat", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_unknown_chip_kind_is_refused(monkeypatch):
    import jax

    fake = SimpleNamespace(platform="tpu", device_kind="TPU v99",
                           memory_stats=lambda: {})
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    with pytest.raises(R.NoChip, match="not in peaks.json"):
        R.check_devices(1)


def test_too_few_chips_is_refused(monkeypatch):
    import jax

    fake = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite",
                           memory_stats=lambda: {})
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    with pytest.raises(R.NoChip, match="needs 4 chips"):
        R.check_devices(4)


@pytest.mark.parametrize("name", ["tiny-int8-docs", "tiny-sd-chat"])
def test_control_is_not_correct(name):
    """The configuration's control in the program's place, judged like any
    run: for the int8 cell the reference at int4, its first choices read
    at the served positions; for the serve-default cell the program's
    perforated product without the CV, served.  ``correct`` comes out
    false."""
    res = _run(name, control=True)
    assert not res["correct"]
    res["_cell"] = name
    assert _over_limit(res)
    assert res["checks"]["compiles_in_window"]["value"] == 0


def _token_altered(orig):
    def dispatch(self, params, batch, tables):
        logits, cache = orig(self, params, batch, tables)
        return jnp.roll(logits, 1, axis=-1), cache
    return dispatch


def _half_batch_altered(orig):
    """Every other slot's tokens come from altered logits: half of the
    batch is wrong and the other half sound."""
    def dispatch(self, params, batch, tables):
        logits, cache = orig(self, params, batch, tables)
        return logits.at[::2].set(jnp.roll(logits[::2], 1, axis=-1)), cache
    return dispatch


def _state_unchanged(orig):
    def dispatch(self, params, batch, tables):
        logits, _ = orig(self, params, batch, tables)
        return logits, self.pool.cache
    return dispatch


@pytest.mark.parametrize(
    "fault", [_token_altered, _half_batch_altered, _state_unchanged],
    ids=["token_altered", "half_batch_altered", "state_unchanged"])
def test_fault_in_the_timed_path_is_not_correct(monkeypatch, fault):
    from repro.serving.engine import ServingEngine

    monkeypatch.setattr(ServingEngine, "_dispatch",
                        fault(ServingEngine._dispatch))
    res = _run("tiny-int8-docs")
    assert not res["correct"]
    res["_cell"] = "tiny-int8-docs"
    over = _over_limit(res)
    assert over
    if fault is _half_batch_altered:
        # the sound half keeps the pooled mean down; the wrong requests
        # are caught one by one
        assert "worst_request_gap" in over


def test_window_holds_the_same_requests_for_every_seed():
    cell = R.load_cell("olmo1b-sd-chat")
    lead = cell.spec["lead_in_s"]
    sizes = []
    for seed in (SEED, SEED + 1):
        jobs = R.make_jobs(cell, seed, 30.0)
        win = [j for j in jobs if lead <= j.due < lead + 30.0]
        assert len(win) == round(cell.spec["rate_per_s"] * 30.0)
        sizes.append(sorted((len(j.prompt), j.max_new) for j in win))
    assert sizes[0] == sizes[1]


def test_ttft_is_waited_for_past_the_close():
    """A request due just before the close counts its whole wait for the
    first token, not the wait up to the close."""
    job = SimpleNamespace(prompt=[1, 2, 3])
    late = R.Rec(job=job, due=9.9, tokens=[11.0, 11.1])
    never = R.Rec(job=job, due=9.0)
    win = R.Window(t_lead=-1.0, t0=0.0, t1=10.0, t_drained=12.0,
                   recs=[late, never], all_recs=[late, never], steps=[],
                   compiles=0)
    run = R.Run(cell=None, window=win, setup_s=0.0, peaks={}, cv=False)
    assert run.ttfts() == pytest.approx([1.1, 3.0])
    # the gaps and the tokens counted stay inside the window
    assert run.itls() == [] and run.generated_in_window() == 0
