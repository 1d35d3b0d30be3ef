"""The MLA + MoE family (DeepSeek-V2's layout) at test size on the CPU:
its YaRN constants at the published dims, its tree against the program's,
its reference against the program's prefill and slot-cache decode, its
counts, a whole cell of it run through the harness (sound, and its control
not), and the readers of its new metrics with nothing to read."""

import dataclasses
import json
import shutil
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import moe_trace
from bench import run as R
from bench.families import mla_moe

BENCH = Path(__file__).resolve().parents[1]
FIXTURES = Path(__file__).resolve().parent / "fixtures"
CELL = "tiny-dsv2-docs"
SEED = 2**33 + 77


def _published() -> dict:
    return R.load_json(BENCH / "configs" / "deepseek-v2-lite-ep8.json")


def _root(tmp_path) -> Path:
    """The fixtures with the tiny cell of this family entered in a copy of
    their BENCHMARK.json, beside the fixture docs cell's metrics."""
    root = tmp_path / "bench"
    shutil.copytree(FIXTURES, root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": CELL, "config": "tiny-dsv2",
                               "traffic": "tiny-docs", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if ("tiny-int8-docs" in m.get("workloads", ())
                and m["name"] != "dense_roofline.docs"):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _cell(root):
    return R.load_cell(CELL, root=root, benchmark=root / "BENCHMARK.json")


def _run(root, control=False):
    return R.run_cell(_cell(root), SEED, 1.5, False, root=root,
                      require_tpu=False, control=control,
                      t_start=time.perf_counter())


def test_yarn_constants_at_published_dims():
    model = _published()["model"]
    yarn = model["rope_scaling"]
    assert mla_moe.yarn_range(model["qk_rope_dim"], model["rope_theta"],
                              yarn) == (10, 23)
    assert mla_moe.softmax_scale(model) == pytest.approx(0.114721, abs=1e-6)


def test_configuration_holds_the_published_numbers():
    """The catalog's config.json numbers under their own keys, the model
    at published widths, and the one cut: the experts held."""
    conf = _published()
    model = conf["model"]
    assert conf["n_routed_experts"] == model["n_experts"] == 64
    assert conf["num_experts_per_tok"] == model["top_k"] == 6
    assert conf["moe_intermediate_size"] == model["d_ff_expert"] == 1408
    assert conf["num_hidden_layers"] == model["n_layers"] == 27
    assert conf["rope_scaling"] == model["rope_scaling"]
    assert conf["norm_topk_prob"] is model["norm_topk_prob"] is False
    assert (model["experts_held"], model["expert_offset"]) == (8, 0)
    assert conf["reduced"] == ["experts_held"]
    assert mla_moe.routed_share(model) == 0.75


def test_program_config_matches_and_a_wrong_key_raises(tmp_path):
    from repro.models import build_model

    root = _root(tmp_path)
    cell = _cell(root)
    cfg = R.arch_config(cell)
    R.check_layout(build_model(cfg), cell, cfg.param_dtype)
    for key, wrong in (("experts_held", 8), ("norm_topk_prob", True),
                       ("rope_scaling", {**cell.model["rope_scaling"],
                                         "factor": 4})):
        path = root / "configs" / "tiny-dsv2.json"
        conf = json.loads(path.read_text())
        saved = conf["model"][key]
        conf["model"][key] = wrong
        path.write_text(json.dumps(conf))
        with pytest.raises(ValueError, match=key):
            R.arch_config(_cell(root))
        conf["model"][key] = saved
        path.write_text(json.dumps(conf))


def test_published_tree_matches_the_program():
    """At published widths, the family's tree has the program's shapes
    (shapes only: nothing is made)."""
    from repro.models import build_model

    cell = R.load_cell("dsv2lite-ep8-docs")
    cfg = R.arch_config(cell)
    R.check_layout(build_model(cfg), cell, cfg.param_dtype)
    shp = mla_moe.shapes(cell.model)
    assert shp["blocks"]["mlp"]["experts"]["gate"]["w"] == (26, 8, 2048, 1408)
    assert shp["blocks"]["mlp"]["router"]["w"] == (26, 2048, 64)
    assert len(shp["dense_blocks"]) == 1


def _served_logits(compute: str, pack: str | None, cache: str,
                   n: int = 48, chunk: int = 16):
    """Logits of the program's slot step over one sequence: prefill in
    chunks of ``chunk``, then one token per call up to ``n`` positions;
    and the reference's over the same tokens."""
    from repro.configs import get_config
    from repro.launch.serve import ServeConfig, build_serving_params
    from repro.models import build_model
    from repro.numerics import get_preset

    conf = R.load_json(FIXTURES / "configs" / "tiny-dsv2.json")
    model = conf["model"]
    cfg = dataclasses.replace(get_config(conf["arch"]), **{
        **conf["overrides"], "param_dtype": "float32",
        "compute_dtype": compute})
    api = build_model(cfg)
    w = mla_moe.make(model, 11, "float32")
    params = w if pack is None else build_serving_params(
        w, cfg, ServeConfig(spec=get_preset(pack)))
    toks = np.random.default_rng(0).integers(0, model["vocab"], 256)
    c = api.init_slot_cache(1, 256, jnp.dtype(cache))
    step = jax.jit(api.decode_slots)
    out, pos = [], 0
    while pos < n:
        width = chunk if pos < 32 else 1
        logits, c = step(params, jnp.asarray(toks[None, pos:pos + width]), c,
                         jnp.asarray([width]))
        out.append(np.asarray(logits[0]))
        pos += width
    ref = np.asarray(mla_moe.forward(w, toks, model))[:n]
    return np.concatenate(out)[:n], ref, (w, toks, model)


def test_slot_decode_matches_the_reference_at_float():
    """Float32 weights, compute and cache: prefill in chunks, then decode
    through the slot cache, the absorbed latent attention against the
    reference's decompressed one.  The tolerance, 1e-4, is float32
    rounding over 3 layers with logits of magnitude 4 (measured 5e-6);
    computing in bfloat16 where the configuration states float32 reads
    0.24 and fails it."""
    with jax.default_matmul_precision("highest"):
        got, ref, _ = _served_logits("float32", None, "float32")
        np.testing.assert_allclose(got, ref, atol=1e-4, rtol=0)
        low, ref, _ = _served_logits("bfloat16", None, "float32")
    assert np.abs(low - ref).max() > 1e-4


def test_slot_decode_matches_the_reference_under_int8():
    """The int8 pack served (bfloat16 compute and cache, as the engine
    serves it): its logits lie within 25% of the reference's norm.  Int8
    at this size costs about 15% (measured 0.145; the reference's own
    int8 fake quantization reads 0.147); the reference at int4 reads 1.23
    and fails it."""
    with jax.default_matmul_precision("highest"):
        got, ref, (w, toks, model) = _served_logits("bfloat16", "int8",
                                                    "bfloat16")
        ctl = np.asarray(mla_moe.forward(w, toks, model, bits=4))[:len(ref)]

    def rel(x):
        return np.linalg.norm(x - ref) / np.linalg.norm(ref)

    assert rel(got) < 0.25
    assert rel(ctl) > 0.25


def test_readings_agree_with_the_full_logits():
    conf = R.load_json(FIXTURES / "configs" / "tiny-dsv2.json")
    model = conf["model"]
    w = mla_moe.make(model, 3, "float32")
    toks = np.random.default_rng(1).integers(0, model["vocab"], 256)
    picks = np.random.default_rng(2).integers(0, model["vocab"], (2, 256))
    full = np.asarray(mla_moe.forward(w, toks, model))
    best, at, top = (np.asarray(x) for x in
                     mla_moe.readings(w, toks, picks, model))
    np.testing.assert_allclose(best, full.max(-1), rtol=1e-6)
    np.testing.assert_allclose(at, np.take_along_axis(
        full[None], picks[..., None], -1)[..., 0], rtol=1e-6)
    assert np.array_equal(top, full.argmax(-1))


def test_tiny_cell_is_correct_and_its_control_is_not(tmp_path):
    root = _root(tmp_path)
    res = _run(root)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"gen_tok_per_s", "setup_s"}
    assert res["_extra"]["served_tokens"] > 0
    res = _run(root, control=True)
    assert not res["correct"]
    limits = R.load_json(root / "workloads" / f"{CELL}.json")["limits"]
    assert any(res["checks"][n]["value"] > v for n, v in limits.items())


def test_counts_at_published_widths():
    model = _published()["model"]
    d, fe, L = 2048, 1408, 27
    # the absorbed attention: 2 x 16 x (512 + 64 + 512) per row, layer and
    # live position; the latent and rope stripes 1152 bytes a position
    assert mla_moe.attn_ops_per_position(model) == 2 * 16 * 1088
    assert mla_moe.latent_bytes_per_position(model) == 1152
    # routed experts counted at 0.75 of a token's rows (the products, and
    # layer_work's row sums and zero-point terms beside them)
    ops, _ = mla_moe.dense_work(model, 1000, False)
    no_experts = dict(model, top_k=0)
    ops0, _ = mla_moe.dense_work(no_experts, 1000, False)
    rows = 1000 * 0.75 * (L - 1)
    assert ops - ops0 == pytest.approx(
        rows * (3 * 2 * d * fe + 2 * d + fe + 3 * (2 * fe + d)))
    # the expert products: weights once per group, rows per pair
    o, b = mla_moe.expert_work(model, 192, 64, False)
    assert o == pytest.approx(2 * 192 * 3 * d * fe, rel=1e-2)
    weights = 3 * d * fe + 4 * (2 * fe + d)  # codes and column sums
    assert b == 64 * weights + 2 * 192 * 3 * (d + fe)


def test_moe_scopes_read_from_op_names():
    assert moe_trace.scope_of(
        "jit(engine_chunk_step)/layer_stack/while/body/mlp_in/experts/"
        "ragged_dot") == "experts"
    assert moe_trace.scope_of(
        "jit(engine_chunk_step)/layer_stack/while/body/mlp_in/"
        "shared_expert/mlp_in/dot_general") == "shared_expert"
    assert moe_trace.scope_of("jit(f)/attn/dot_general") == ""
    rec = {"scope_ns": {"router": 10, "experts": 30}, "program_ns": 200}
    assert moe_trace.share(rec, moe_trace.SCOPES) == 20.0
    assert moe_trace.share({"scope_ns": {"": 5}, "program_ns": 9},
                           moe_trace.SCOPES) is None


@pytest.mark.parametrize("name", ["expert_roofline.docs",
                                  "latent_attn_roofline.docs",
                                  "moe_share.docs"])
def test_new_metrics_read_nothing_without_a_trace(tmp_path, name):
    root = _root(tmp_path)
    run = R.Run(cell=_cell(root), window=None, setup_s=0.0, peaks={},
                cv=False)
    assert R.load_reader(BENCH, name)(run) is None
