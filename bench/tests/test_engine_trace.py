"""The engine's spans and the step's scopes as the benchmark reads them:
the scopes in the compiled step at test size, the named step programs, the
op-name reader on a hand-encoded xplane, the reductions on a synthetic
record worked by hand and on a small one recorded on a TPU v5e, and the
earlier readers unchanged on the recorded trace they were checked on."""

import contextlib
import gzip
import json
import re
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from bench import engine_trace as et
from bench import readings
from bench import run as R
from bench import trace, weights

FIXTURES = Path(__file__).resolve().parent / "fixtures"
BENCH = Path(__file__).resolve().parents[1]
NEW_METRICS = ("host_ms_per_step.chat", "host_ms_per_step.docs",
               "kv_stack_share.chat", "kv_stack_share.docs")


# ---------------------------------------------------------------------------
# the scopes and the step programs at test size (CPU)
# ---------------------------------------------------------------------------


def _engine(config_name: str, numerics: str):
    """An engine on a fixture configuration under ``numerics``, packed
    without folds: at test size every fan-in is under 258, where the packs
    would fold the CV into their operands; at published widths no olmo or
    granite projection folds, and this is the path they serve."""
    from repro.configs.base import EngineConfig
    from repro.launch.serve import ServeConfig, build_serving_params
    from repro.models import build_model
    from repro.numerics import get_preset
    from repro.serving import ServingEngine

    config = R.load_json(FIXTURES / "configs" / f"{config_name}.json")
    cell = R.Cell(config_name, {}, config, {}, {})
    cfg = R.arch_config(cell)
    api = build_model(cfg)
    w = weights.make(cell.model, 3, cfg.param_dtype)
    served = build_serving_params(
        w, cfg, ServeConfig(spec=get_preset(numerics), fold=False))
    return ServingEngine(cfg, served, EngineConfig(**config["engine"]),
                         api=api), config["engine"]


def _compiled(eng, ecfg: dict, shape: str) -> str:
    c = 1 if shape == "decode" else ecfg["prefill_chunk"]
    slots = ecfg["slots"]
    return eng._step_fns[shape].lower(
        eng.params, jnp.zeros((slots, c), jnp.int32), eng.pool.cache,
        jnp.zeros((slots,), jnp.int32)).compile().as_text()


def _scopes(hlo: str) -> set[str]:
    return {et.scope_of(n) for n in re.findall(r'op_name="([^"]*)"', hlo)}


@pytest.mark.parametrize("config_name", ["tiny-olmo", "tiny-granite"])
@pytest.mark.parametrize("numerics", ["serve-default", "int8"])
def test_step_carries_every_scope(config_name, numerics):
    from repro.models import lm

    assert et.SCOPES == lm.STEP_SCOPES
    eng, ecfg = _engine(config_name, numerics)
    for shape in ("decode", "chunk"):
        hlo = _compiled(eng, ecfg, shape)
        assert hlo.startswith(f"HloModule jit_engine_{shape}_step")
        got = _scopes(hlo)
        want = set(et.SCOPES) - {"cv"}
        assert want <= got, (shape, want - got)
        # the CV is served under serve-default (perforated m=2) alone
        assert ("cv" in got) == (numerics == "serve-default")


def test_scopes_change_metadata_only(monkeypatch):
    eng, ecfg = _engine("tiny-granite", "serve-default")
    scoped = _compiled(eng, ecfg, "chunk")
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    eng, ecfg = _engine("tiny-granite", "serve-default")
    plain = _compiled(eng, ecfg, "chunk")

    def body(hlo):
        hlo = re.sub(r",? metadata=\{[^}]*\}", "", hlo)
        return [ln for ln in hlo.splitlines()
                if ln.startswith((" ", "%", "ENTRY", "ROOT"))]

    assert "layer_stack" in scoped and "layer_stack" not in plain
    assert body(scoped) == body(plain)


def test_two_named_step_programs_after_warm_up():
    eng, ecfg = _engine("tiny-olmo", "int8")
    R.warm_up(eng, ecfg, 256)
    assert eng.compile_count() == 2
    assert {name: fn._cache_size() for name, fn in eng._step_fns.items()} \
        == {"decode": 1, "chunk": 1}
    for shape in ("decode", "chunk"):
        assert et.STEP_PROGRAM.match(
            f"jit_engine_{shape}_step(7)").group(1) == shape


# ---------------------------------------------------------------------------
# the op names of a TPU op's event metadata
# ---------------------------------------------------------------------------


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, payload) -> bytes:
    if isinstance(payload, int):
        return _varint(num << 3) + _varint(payload)
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _plane(name: str, ops: dict, by_ref: bool = False) -> bytes:
    """An XPlane with one ``tf_op`` stat per op's event metadata (a string,
    or a reference to a stat metadata named by the string)."""
    stats = {5: "tf_op", 6: "flops"}
    if by_ref:
        stats.update({100 + i: v for i, v in enumerate(ops.values())})
    out = _field(2, name)
    for sid, sname in stats.items():
        out += _field(5, _field(1, sid) + _field(2, _field(1, sid)
                                                 + _field(2, sname)))
    for i, (ev, op_name) in enumerate(ops.items()):
        stat = _field(1, 5) + (_field(7, 100 + i) if by_ref
                               else _field(5, op_name))
        other = _field(1, 6) + _field(4, 12)
        meta = (_field(1, i + 1) + _field(2, ev) + _field(5, other)
                + _field(5, stat))
        out += _field(4, _field(1, i + 1) + _field(2, meta))
    out += _field(3, _field(2, "XLA Ops"))  # a line: skipped whole
    return _field(1, out)


def test_op_names_from_event_metadata(tmp_path):
    tpu = {"%fusion.1 = bf16[4] fusion(...)":
           "jit(engine_decode_step)/layer_stack/while/body/qkv/cv/mul",
           "%copy.2 = bf16[4] copy(...)":
           "jit(engine_decode_step)/layer_stack/while/body/dynamic_slice"}
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_plane("/host:CPU", {"engine.step": "x/attn/y"})
                     + _plane("/device:TPU:0", tpu)
                     + _plane("/device:TPU:1", {"%a.1 = f32[] add(...)":
                                                "jit(f)/head/add"},
                              by_ref=True))
    names = et.op_names(str(path))
    assert names == {**tpu, "%a.1 = f32[] add(...)": "jit(f)/head/add"}
    assert [et.scope_of(names[k]) for k in tpu] == ["cv", "layer_stack"]
    assert et.scope_of("jit(f)/attn") == ""  # the op itself is no scope
    assert et.scope_of("") == ""


# ---------------------------------------------------------------------------
# reductions on a record worked by hand
# ---------------------------------------------------------------------------

SYNTH = {
    "spans": [
        [100, 1000, "engine.step", {"shape": "chunk", "step_num": 1}],
        [110, 40, "engine.schedule", {}],
        [150, 100, "engine.dispatch", {}],
        [250, 700, "engine.fetch", {}],
        [950, 100, "engine.emit", {}],
        [1200, 500, "engine.step", {"shape": "decode", "step_num": 2}],
        [1250, 100, "engine.dispatch", {}],
        [1350, 300, "engine.fetch", {}],
        [1800, 50, "engine.step", {"step_num": 3}],  # ran no batch
    ],
    "programs": [
        [200, 900, "jit_engine_chunk_step(1)",
         {"layer_stack": 100, "kv_write": 150, "mlp_in": 300, "cv": 50,
          "": 100}],
        [1300, 400, "jit_engine_decode_step(2)",
         {"layer_stack": 100, "attn": 200, "mlp_out": 100}]],
}
EMPTY = {"spans": [], "programs": []}


def test_host_time_per_step():
    # (1000 - 700) and (500 - 300) ns; the step without a batch is left out
    assert et.host_ms_per_step(SYNTH) == pytest.approx(250e-6)
    assert et.host_ms_per_step(EMPTY) is None


def test_scope_shares():
    assert et.program_ns(SYNTH) == 1300
    assert et.scope_share(SYNTH, et.KV_STACK) == pytest.approx(
        100 * 350 / 1300)
    assert et.scope_share(SYNTH, ("cv",)) == pytest.approx(100 * 50 / 1300)
    assert et.scope_ns(SYNTH) == {"layer_stack": 200, "kv_write": 150,
                                  "mlp_in": 300, "cv": 50, "": 100,
                                  "attn": 200, "mlp_out": 100}
    assert et.scope_share(SYNTH, ("embed",)) is None


def _run(rec: dict, devices=None) -> SimpleNamespace:
    return SimpleNamespace(trace={
        "devices": devices or {"/device:TPU:0": {"ops": [], "modules": []}},
        "host": [[0, 2000, "bench.window"], [90, 1020, "bench.step"]],
        "engine": rec})


def test_new_readers_on_the_synthetic_record():
    run = _run(SYNTH)
    got = {m: R.load_reader(BENCH, m)(run) for m in NEW_METRICS}
    assert got == pytest.approx({
        "host_ms_per_step.chat": 250e-6, "host_ms_per_step.docs": 250e-6,
        "kv_stack_share.chat": 100 * 350 / 1300,
        "kv_stack_share.docs": 100 * 350 / 1300})
    # an engine without the spans and programs: nothing to report
    assert all(R.load_reader(BENCH, m)(_run(dict(EMPTY))) is None
               for m in got)
    assert all(R.load_reader(BENCH, m)(SimpleNamespace(trace=None)) is None
               for m in got)


def test_idle_gaps_named_by_engine_phases():
    ops = [[100, 50, "fusion.1", 0], [300, 200, "fusion.2", 0],
           [1300, 400, "fusion.3", 0]]
    run = _run(SYNTH, {"/device:TPU:0": {"ops": ops, "modules": []}})
    # gaps 500-1300 (middle 900: the first step's fetch, inside the step),
    # 1700-2000 (1850: no span open), 150-300 (225: the dispatch)
    assert et.longest_gaps(run, n=3) == [
        ["engine.fetch", pytest.approx(800e-9)],
        ["outside bench spans", pytest.approx(300e-9)],
        ["engine.dispatch", pytest.approx(150e-9)]]


# ---------------------------------------------------------------------------
# a record of three steps of olmo1b-sd-chat on a TPU v5e
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    """Two chunk-shaped steps and a decode-shaped one: the benchmark's
    record of them with the engine's under ``engine``."""
    return json.load(gzip.open(FIXTURES / "engine_small.json.gz", "rt"))


def test_new_readers_on_the_recorded_trace(recorded):
    run = SimpleNamespace(trace=recorded)
    got = {m: R.load_reader(BENCH, m)(run) for m in NEW_METRICS}
    assert all(v is not None for v in got.values()), got
    assert 1.0 < got["host_ms_per_step.chat"] < 4.0
    assert 40.0 < got["kv_stack_share.chat"] < 65.0
    rec = recorded["engine"]
    # every step ran a named program of its shape, in order
    shapes = [sp[3]["shape"] for sp in rec["spans"]
              if sp[2] == "engine.step"]
    assert shapes == ["chunk", "chunk", "decode"]
    assert [et.STEP_PROGRAM.match(p[2]).group(1)
            for p in rec["programs"]] == shapes
    assert [(a, d) for a, d in trace.step_programs(recorded, shapes)] == \
        [(p[0], p[1]) for p in rec["programs"]]
    # nearly all of the step programs' time falls under a scope
    by = et.scope_ns(rec)
    assert by.get("", 0) < 0.05 * et.program_ns(rec)
    assert set(by) - {""} <= set(et.SCOPES)
    assert {"qkv", "attn", "kv_write", "mlp_in", "mlp_out", "layer_stack",
            "head"} <= set(by)
    # each step's phases, and the device idle while the host fetches
    for sp in rec["spans"]:
        if sp[2] == "engine.step":
            kids = {c[2] for c in rec["spans"]
                    if sp[0] <= c[0] < sp[0] + sp[1]} - {"engine.step"}
            assert {"engine.schedule", "engine.dispatch", "engine.fetch",
                    "engine.emit", "engine.account"} <= kids
    gaps = et.longest_gaps(run, n=3)
    assert len(gaps) == 3 and all(g[0].startswith("engine.") for g in gaps)


# ---------------------------------------------------------------------------
# the earlier readers on the recorded trace they were checked on
# ---------------------------------------------------------------------------


def test_earlier_readers_unchanged_on_the_recorded_trace():
    """The readings ``trace_small.json.gz`` gave when the engine's spans
    and scopes came: they read the benchmark's record alone, which the
    engine's record (``run.trace["engine"]``) leaves as it was."""
    rec = json.load(gzip.open(FIXTURES / "trace_small.json.gz", "rt"))
    steps = [R.Step(0.0, 0.07, "decode", 0, 32, 32 * 400.0, True),
             R.Step(0.07, 0.2, "chunk", 768, 8, 768 * 300.0, True)]
    win = R.Window(t_lead=0.0, t0=0.0, t1=1.0, t_drained=1.0, recs=[],
                   all_recs=[], steps=steps, compiles=0)
    peaks = R.load_json(BENCH / "peaks.json")["devices"]["TPU v5 lite"]
    run = R.Run(cell=R.load_cell("olmo1b-sd-chat"), window=win, setup_s=0.0,
                peaks=peaks, cv=True, trace=rec)
    assert readings.step_ms(run, "decode") == pytest.approx(65.584443)
    assert readings.step_ms(run, "chunk") == pytest.approx(119.960776)
    assert readings.dense_roofline(run) == pytest.approx(12.777114960626095)
    assert readings.step_mfu(run) == pytest.approx(2.6516008434580036)
    assert readings.idle_share(run) == pytest.approx(4.961788264464639)
    assert trace.top_ops(rec, 3) == [
        ["fusion.200 bf16[32,32,16384]", pytest.approx(0.027865775)],
        ["constant_dynamic-slice_fusion.38 bf16[1,32,16,1024,128]",
         pytest.approx(0.013123455)],
        ["constant_dynamic-slice_fusion.37 bf16[1,32,16,1024,128]",
         pytest.approx(0.013090091)]]
    # a record without the engine's spans: the readers find nothing, and
    # the benchmark's own readings stay as they were
    rec["engine"] = dict(EMPTY)
    assert R.load_reader(BENCH, "kv_stack_share.chat")(run) is None
    assert readings.step_ms(run, "chunk") == pytest.approx(119.960776)
