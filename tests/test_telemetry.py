"""Telemetry layer: span tracing, windowed metrics, fleet merge, and the
approximation-error probe.

Unit coverage (no model): percentile interpolation, reservoir sampling,
tracer ring-buffer eviction, Chrome-trace schema, merge() associativity.
Integration coverage (reduced model): a traced engine run emits ordered,
monotonic lifecycle spans plus windowed samples, and the error probe
reports ~0 error under exact-int8 but strictly larger error for
perforated-m2 without the control variate than with it — the paper's
CV claim, observable from the serving path.
"""

import dataclasses
import glob
import json
import math
import os
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import EngineConfig
from repro.core.policy import ApproxPolicy
from repro.launch.serve import ServeConfig, build_serving_params
from repro.models import build_model
from repro.serving import EngineMetrics, ServingEngine, SpanTracer
from repro.serving.metrics import Reservoir, _merge_moments, _percentile
from repro.serving import telemetry
from repro.serving.telemetry import LIFECYCLE_KINDS, PHASE_KINDS

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import trace_report  # noqa: E402

# ---------------------------------------------------------------------------
# metrics units (no model)
# ---------------------------------------------------------------------------


def test_percentile_linear_interpolation():
    xs = [1.0, 2.0, 3.0, 4.0]
    # numpy's default (linear) method is the contract
    for q in (0.0, 0.25, 0.5, 0.733, 0.95, 1.0):
        assert _percentile(xs, q) == pytest.approx(
            float(np.percentile(xs, q * 100)))
    assert _percentile([5.0], 0.5) == 5.0
    assert _percentile([], 0.5) == 0.0


def test_reservoir_exact_stats_under_cap():
    r = Reservoir(cap=8)
    for x in [3.0, 1.0, 4.0, 1.0, 5.0]:
        r.push(x)
    assert len(r) == 5 and r.capped == 0
    assert r.mean == pytest.approx(2.8)
    assert r.max == 5.0
    assert r.percentile(1.0) == 5.0


def test_reservoir_caps_but_keeps_exact_moments():
    r = Reservoir(cap=16)
    xs = [float(i) for i in range(1000)]
    for x in xs:
        r.push(x)
    # sample bounded, but n/mean/max stay exact over the full stream
    assert len(r) == 1000 and r.n == 1000
    assert len(r.samples) == 16 and r.capped == 984
    assert r.mean == pytest.approx(np.mean(xs))
    assert r.max == 999.0
    # the retained sample is a uniform draw: its median should land
    # well inside the stream's bulk, not at an extreme
    assert 100.0 < r.percentile(0.5) < 900.0


def test_merge_moments_matches_pooled():
    rng = np.random.default_rng(0)
    a, b = rng.normal(size=100), rng.normal(loc=2.0, size=37)
    stat = lambda x: (len(x), float(np.mean(x)), float(np.var(x)))
    n, mean, var = _merge_moments(stat(a), stat(b))
    pooled = np.concatenate([a, b])
    assert n == len(pooled)
    assert mean == pytest.approx(float(np.mean(pooled)))
    assert var == pytest.approx(float(np.var(pooled)))


def _fake_metrics(seed, steps=50, numerics="serve-default"):
    rng = np.random.default_rng(seed)
    m = EngineMetrics(numerics=numerics)
    m.start_clock()
    m.prompt_tokens = int(rng.integers(100, 1000))
    m.generated_tokens = int(rng.integers(100, 1000))
    m.finished = int(rng.integers(1, 20))
    for _ in range(steps):
        m.record_step("decode", float(rng.random()), int(rng.integers(0, 5)),
                      generated_tokens=1)
        m.ttfts.push(float(rng.random()))
        m.itls.push(float(rng.random() * 0.01))
        m.latencies.push(float(rng.random() * 2))
    m.record_probe({"layers": {"blocks/0/q": {"n": 4, "mean": 0.1 * seed,
                                              "var": 0.01 * (seed + 1)}},
                    "logits": {"n": 4, "mean": 0.2, "var": 0.02}})
    return m.snapshot()


def test_merge_is_associative():
    a, b, c = _fake_metrics(1), _fake_metrics(2), _fake_metrics(3)
    left = EngineMetrics.merge([EngineMetrics.merge([a, b]), c])
    right = EngineMetrics.merge([a, EngineMetrics.merge([b, c])])
    flat = EngineMetrics.merge([a, b, c])
    assert left["engines"] == right["engines"] == flat["engines"] == 3
    for key in ("requests_finished", "generated_tokens", "ttft_samples",
                "step_samples"):
        assert left[key] == right[key] == flat[key]
    for key in ("elapsed_s", "ttft_mean_s", "itl_p50_s",
                "mean_slot_occupancy", "gen_tok_per_s"):
        assert left[key] == pytest.approx(right[key], rel=1e-9)
        assert left[key] == pytest.approx(flat[key], rel=1e-9)
    for m in (left, right, flat):
        p = m["error_probe"]
        assert p["runs"] == 3 and p["logits_err_n"] == 12
        assert p["layers"]["blocks/0/q"]["n"] == 12
    assert left["error_probe"]["logits_err_var"] == pytest.approx(
        right["error_probe"]["logits_err_var"], rel=1e-9)


def test_merge_mixed_numerics_flagged():
    a = _fake_metrics(1, numerics="int8")
    b = _fake_metrics(2, numerics="serve-default")
    merged = EngineMetrics.merge([a, b])
    assert merged["numerics"] == "mixed"
    assert EngineMetrics.merge([a])["numerics"] == "int8"


# ---------------------------------------------------------------------------
# span tracer units
# ---------------------------------------------------------------------------


def test_tracer_rejects_unknown_kind():
    tr = SpanTracer(capacity=4)
    with pytest.raises(ValueError, match="unknown span kind"):
        tr.record("not-a-kind")


def test_tracer_ring_eviction():
    tr = SpanTracer(capacity=8)
    for i in range(20):
        tr.record("decode_step", rid=i)
    assert len(tr) == 8 and tr.dropped == 12
    # oldest evicted first: the survivors are the 8 newest
    assert [e.rid for e in tr.events()] == list(range(12, 20))
    assert tr.chrome_trace()["otherData"]["dropped_events"] == 12
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_chrome_trace_schema():
    tr = SpanTracer(capacity=64, engine="eng0")
    tr.record("queued", rid=3, prompt_len=7)
    tr.record("prefill_chunk", rid=3, dur=0.004, n_valid=7)
    tr.record("metrics_window", gen_tok_per_s=123.4, numerics="int8",
              steps=9)
    doc = tr.chrome_trace()
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and doc["displayTimeUnit"] == "ms"
    for e in evs:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        if e["ph"] != "M":
            assert isinstance(e["ts"], float) and e["ts"] >= 0
    by_name = {e["name"]: e for e in evs if e["ph"] != "M"}
    assert by_name["queued"]["ph"] == "i"
    assert by_name["queued"]["tid"] == 4  # rid + 1
    assert by_name["queued"]["args"]["rid"] == 3
    assert by_name["prefill_chunk"]["ph"] == "X"
    assert by_name["prefill_chunk"]["dur"] == pytest.approx(4000, rel=1e-3)
    # counter events keep only numeric args (Perfetto plots them)
    cnt = by_name["metrics_window"]
    assert cnt["ph"] == "C" and cnt["tid"] == 0
    assert "numerics" not in cnt["args"] and cnt["args"]["steps"] == 9
    json.dumps(doc)  # must be serializable as-is


def test_write_and_report_loader_roundtrip(tmp_path):
    tr = SpanTracer(capacity=64, engine="eng0")
    tr.record("queued", rid=0, prompt_len=5)
    tr.record("admit", rid=0, slot=1, queue_wait_s=0.001)
    tr.record("prefill_chunk", rid=0, dur=0.002, n_valid=5)
    tr.record("decode_step", rid=0, dur=0.001)
    tr.record("finished", rid=0, reason="length", generated=1)
    chrome, jsonl = tmp_path / "t.json", tmp_path / "t.jsonl"
    tr.write(str(chrome))
    tr.write(str(jsonl))
    ea = trace_report.load_events(str(chrome))
    eb = trace_report.load_events(str(jsonl))
    assert [e["kind"] for e in ea] == [e["kind"] for e in eb]
    assert all(e["rid"] == 0 for e in ea)
    for x, y in zip(ea, eb):
        assert x["t"] == pytest.approx(y["t"], abs=1e-5)
        assert x["dur"] == pytest.approx(y["dur"], abs=1e-5)
    rep = trace_report.report(ea)
    assert rep["requests"][0]["finish_reason"] == "length"
    assert rep["requests"][0]["prefill_chunks"] == 1
    assert not [k for k in trace_report.LIFECYCLE if not rep["kinds"].get(k)]


# ---------------------------------------------------------------------------
# engine integration (reduced model)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def model_and_params():
    cfg = dataclasses.replace(get_config("olmo-1b-reduced"),
                              compute_dtype="float32")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    return cfg, api, params


def _requests(vocab, n=4, seed=3):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, vocab, 20).tolist(), 8) for _ in range(n)]


def test_traced_engine_lifecycle_spans(model_and_params, tmp_path):
    cfg, _, params = model_and_params
    eng = ServingEngine(cfg, params,
                        EngineConfig(slots=3, max_len=64, prefill_chunk=16,
                                     cache_dtype="float32", trace=True,
                                     metrics_window_s=0.02))
    for p, g in _requests(cfg.vocab, n=5):
        eng.submit(p, g)
    eng.run()

    events = eng.tracer.events()
    kinds = {e.kind for e in events}
    assert set(LIFECYCLE_KINDS) <= kinds

    # per-request lifecycle ordering on the shared monotonic clock
    for rid in {e.rid for e in events if e.rid is not None}:
        t = {k: [e.t for e in events if e.rid == rid and e.kind == k]
             for k in LIFECYCLE_KINDS}
        if not t["finished"]:
            continue
        assert t["queued"][0] <= t["admit"][0]
        assert t["admit"][0] <= min(t["prefill_chunk"])
        assert min(t["prefill_chunk"]) <= t["finished"][0]
        if t["decode_step"]:
            assert min(t["prefill_chunk"]) <= min(t["decode_step"])
    # export timestamps are monotone non-decreasing per export order
    ts = [e["ts"] for e in eng.tracer.chrome_trace()["traceEvents"]
          if e["ph"] != "M"]
    assert ts == sorted(ts)

    # windowed samples rolled and bridged into the trace
    snap = eng.metrics.snapshot()
    assert snap["metrics_window_s"] == 0.02
    assert snap["timeseries_samples"] == len(eng.metrics.timeseries)
    if snap["timeseries_samples"]:
        sample = eng.metrics.timeseries[0]
        assert {"t", "dur_s", "gen_tok_per_s", "steps"} <= set(sample)
        assert "metrics_window" in kinds

    # the report tool accepts the written trace and finds all stages
    out = tmp_path / "trace.json"
    eng.tracer.write(str(out))
    assert trace_report.main([str(out), "--assert-lifecycle"]) == 0


@pytest.mark.parametrize("fmt", ["json", "jsonl"])
def test_trace_report_formats_on_engine_trace(model_and_params, tmp_path,
                                              fmt, capsys):
    cfg, _, params = model_and_params
    eng = ServingEngine(cfg, params,
                        EngineConfig(slots=2, max_len=64, prefill_chunk=16,
                                     cache_dtype="float32", trace=True))
    for p, g in _requests(cfg.vocab, n=2):
        eng.submit(p, g)
    eng.run()
    out = tmp_path / f"trace.{fmt}"
    eng.tracer.write(str(out))
    assert trace_report.main([str(out), "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["events"] == len(eng.tracer)
    assert len(rep["requests"]) == 2


def test_span_without_ring_records_nothing():
    tr = SpanTracer()
    with telemetry.span("fetch", rows=3) as sp:
        sp.set(waited=1)
    telemetry.instant(None, "admit", rid=4, queue_wait_s=0.1)
    assert len(tr) == 0 and sp.dur >= 0.0
    # the same calls with the ring: one event each, arguments kept
    with tr.span("fetch", rows=2):
        pass
    with tr.span("step", step=7) as st:
        st.set(shape="decode")
    telemetry.instant(tr, "admit", rid=4, queue_wait_s=0.1)
    got = [(e.kind, e.rid, e.data) for e in tr.events()]
    assert got == [("fetch", None, {"rows": 2}),
                   ("step", None, {"step_num": 7, "shape": "decode"}),
                   ("admit", 4, {"queue_wait_s": 0.1})]


def _profiled_engine_spans(trace_dir) -> list:
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    pd = ProfileData.from_file(path[-1])
    return sorted([int(ev.start_ns), int(ev.duration_ns), ev.name,
                   dict(ev.stats)]
                  for plane in pd.planes if plane.name.startswith("/host:")
                  for line in plane.lines for ev in line.events
                  if ev.name.startswith(telemetry.PREFIX))


def test_engine_phases_on_the_profilers_clock(model_and_params, tmp_path):
    cfg, _, params = model_and_params
    eng = ServingEngine(cfg, params,
                        EngineConfig(slots=2, max_len=64, prefill_chunk=16,
                                     cache_dtype="float32", trace=True))
    reqs = _requests(cfg.vocab, n=3)
    eng.submit(*reqs[0])
    eng.run()  # both shapes compile outside the trace
    eng.tracer.clear()
    jax.profiler.start_trace(str(tmp_path))
    for p, g in reqs[1:]:
        eng.submit(p, g)
    eng.run()
    jax.profiler.stop_trace()
    spans = _profiled_engine_spans(tmp_path)
    steps = [sp for sp in spans if sp[2] == "engine.step"]
    assert steps and all("shape" in sp[3] for sp in steps)
    for s, d, _, args in steps:
        kids = {n for a, b, n, _ in spans if s <= a and a + b <= s + d}
        assert {"engine.schedule", "engine.dispatch", "engine.emit",
                "engine.account"} <= kids
        assert args["shape"] in ("decode", "chunk")
    assert {sp[3]["step_num"] for sp in steps} == set(range(
        steps[0][3]["step_num"], steps[0][3]["step_num"] + len(steps)))
    assert sum(sp[2] == "engine.fetch" for sp in spans) >= len(steps) - 2
    assert sum(sp[2] == "engine.admit" for sp in spans) == 2
    # the anchor puts the ring's records of the same phases on the trace
    offset = steps[0][0] - steps[0][3]["clock_ns"]
    for kind in PHASE_KINDS:
        mine = sorted((e.t, e.dur) for e in eng.tracer.events()
                      if e.kind == kind)
        theirs = [sp for sp in spans if sp[2] == telemetry.PREFIX + kind]
        assert len(mine) == len(theirs), kind
        for (t, dur), (s, d, _, _) in zip(mine, theirs):
            assert abs(t * 1e9 + offset - s) < 1e6
            assert abs(dur * 1e9 - d) < 1e6


def _probe_logits_var(cfg, params, policy):
    qparams = build_serving_params(params, cfg, ServeConfig(policy=policy))
    eng = ServingEngine(cfg, qparams,
                        EngineConfig(slots=3, max_len=64, prefill_chunk=16,
                                     cache_dtype="float32",
                                     error_probe_every=1))
    for p, g in _requests(cfg.vocab, n=3):
        eng.submit(p, g)
    eng.run()
    probe = eng.metrics.snapshot()["error_probe"]
    assert probe is not None and probe["runs"] > 0
    assert probe["layers"], "probe must record per-layer moments"
    return probe


def test_probe_exact_int8_error_is_zero(model_and_params):
    """quantized_linear in exact mode IS the integer reference, so the
    probe's approximate-vs-exact delta must be numerically nil."""
    cfg, _, params = model_and_params
    probe = _probe_logits_var(cfg, params, ApproxPolicy("exact", 0))
    # the only residual is float dequant accumulation order between the
    # fused serving path and the eager reference — orders of magnitude
    # below any perforation error (compare ~1e-3 in the CV test below)
    assert probe["logits_err_var"] == pytest.approx(0.0, abs=1e-6)
    assert probe["mean_layer_err_var"] == pytest.approx(0.0, abs=1e-6)


def test_probe_cv_reduces_perforation_error(model_and_params):
    """The paper's claim, measured in-engine: perforated multipliers
    without the control variate show strictly larger per-layer and
    logits error variance than with it."""
    cfg, _, params = model_and_params
    with_cv = _probe_logits_var(
        cfg, params, ApproxPolicy("perforated", 2, use_cv=True))
    no_cv = _probe_logits_var(
        cfg, params, ApproxPolicy("perforated", 2, use_cv=False))
    assert with_cv["logits_err_var"] > 0
    assert no_cv["logits_err_var"] > with_cv["logits_err_var"]
    assert no_cv["mean_layer_err_var"] > with_cv["mean_layer_err_var"]
    for p in (with_cv, no_cv):
        assert all(math.isfinite(st["err_var"])
                   for st in p["layers"].values())
