"""The trace reduction: busy union, idle share, the split of steps by
shape, the dense-product rule and the idle gaps' naming, on a synthetic
record worked by hand and on a small recorded one from a TPU v5e."""

import gzip
import json
from pathlib import Path

import pytest

from bench import trace

FIXTURES = Path(__file__).resolve().parent / "fixtures"

# device ops: [start, dur, name, category]; host spans: [start, dur, name]
SYNTH = {
    "devices": {"/device:TPU:0": {
        "ops": [[100, 50, "fusion.1 bf16[32,32,2048]", trace.DENSE],
                [140, 30, "fusion.2 bf16[8]", trace.OTHER],  # overlaps
                [300, 100, "fusion.7 bf16[32,1,2048]", trace.DENSE],
                [600, 100, "while.3 (s32[])", trace.CONTAINER]],
        "modules": [[100, 80, "jit_step(1)"], [190, 5, "jit_argmax(2)"],
                    [300, 110, "jit_step(1)"], [600, 100, "jit_step(3)"]]}},
    "host": [[0, 1000, "bench.window"], [90, 200, "bench.step"],
             [290, 150, "bench.step"], [450, 100, "bench.wait"],
             [590, 200, "bench.step"], [700, 50, "bench.on_token"]],
}


def test_union_and_idle_share():
    ops = SYNTH["devices"]["/device:TPU:0"]["ops"]
    assert trace.union_ns(ops, 0, 1000) == 70 + 100 + 100
    assert trace.union_ns(ops, 120, 350) == 50 + 50
    busy_s, window_s, idle = trace.idle_share(SYNTH)
    assert busy_s == pytest.approx(270e-9)
    assert window_s == pytest.approx(1000e-9)
    assert idle == pytest.approx(1 - 270 / 1000)


def test_idle_gaps_are_named_by_the_host():
    gaps = trace.busy_gaps(SYNTH["devices"]["/device:TPU:0"]["ops"], 0, 1000)
    assert gaps == [(0, 100), (170, 300), (400, 600), (700, 1000)]
    named = trace.longest_gaps(SYNTH, n=2)
    assert named[0] == ["outside bench spans", pytest.approx(300e-9)]
    assert named[1][0] == "bench.wait"  # the middle of 400-600 is 500


def test_step_programs_follow_the_steps_in_order():
    kinds = ["chunk", "chunk", "decode"]
    # the two step programs (one per shape) with the most device time;
    # the token pick is left out
    assert trace.step_programs(SYNTH, kinds) == [(100, 80), (300, 110),
                                                 (600, 100)]
    # a program the step before the window dispatched starts inside it:
    # it is skipped, so that each program matches one shape
    leak = json.loads(json.dumps(SYNTH))
    leak["devices"]["/device:TPU:0"]["modules"].insert(0, [5, 60,
                                                           "jit_step(3)"])
    assert trace.step_programs(leak, kinds) == [(100, 80), (300, 110),
                                                (600, 100)]
    # a step that emitted no token returned before its program ran: the
    # program lies outside its span and is still the step's
    late = json.loads(json.dumps(SYNTH))
    late["devices"]["/device:TPU:0"]["modules"][2] = [460, 110, "jit_step(1)"]
    assert trace.step_programs(late, kinds)[1] == (460, 110)


def test_dense_time_and_top_ops():
    progs = [(a, a + d) for a, d in trace.step_programs(
        SYNTH, ["chunk", "chunk", "decode"])]
    assert trace.dense_ns(SYNTH, progs) == 150
    assert trace.dense_ns(SYNTH, progs[:1]) == 50
    top = trace.top_ops(SYNTH)
    assert top[0] == ["fusion.7 bf16[32,1,2048]", pytest.approx(100e-9)]
    assert all(not name.startswith("while") for name, _ in top)


# the distinct operations of a traced olmo-1b serve-default run on a TPU
# v5e (both step programs and the token pick), as the profiler names them
RECORDED_DENSE = {
    "convert_reduce_fusion.6 (f32[32,32], bf16[32,32,2048])",  # o, chunk
    "convert_reduce_fusion.6 (f32[32], bf16[32,1,2048])",  # o, decode
    "fusion.185 bf16[32,32,6144]",  # q|k|v, chunk
    "fusion.200 bf16[32,32,16384]",  # gate|up, chunk
    "fusion.202 bf16[32,32,2048]",  # down, chunk
    "fusion.227 bf16[32,1,2048]",  # down, decode
    "multiply_convert_fusion.5 bf16[32,1,6144]",  # q|k|v, decode
    "multiply_convert_fusion.6 bf16[32,1,16384]",  # gate|up, decode
}


def test_dense_rule_on_recorded_operations():
    ops = json.load(gzip.open(FIXTURES / "hlo_ops.json.gz", "rt"))
    kinds = [trace.op_kind(o) for o in ops]
    assert {n for n, k in kinds if k == trace.DENSE} == RECORDED_DENSE
    loops = [n for n, k in kinds if k == trace.CONTAINER]
    assert loops and all(n.startswith("while.") for n in loops)


@pytest.fixture(scope="module")
def recorded():
    """One decode-shaped and one chunk-shaped step of that run."""
    return json.load(gzip.open(FIXTURES / "trace_small.json.gz", "rt"))


def test_recorded_steps_split_by_shape(recorded):
    progs = trace.step_programs(recorded, ["decode", "chunk"])
    assert len(progs) == 2
    decode_ms, chunk_ms = (d / 1e6 for _, d in progs)
    assert 50 < decode_ms < 80 and 100 < chunk_ms < 140
    # each step program is one of the two compiled step shapes
    names = {m[2] for m in recorded["devices"]["/device:TPU:0"]["modules"]
             if (m[0], m[1]) in progs}
    assert len(names) == 2 and all("lambda" in n for n in names)


def test_recorded_dense_time(recorded):
    progs = [(a, a + d) for a, d in trace.step_programs(
        recorded, ["decode", "chunk"])]
    ops = recorded["devices"]["/device:TPU:0"]["ops"]
    # four packed products per layer, sixteen layers, in each step
    for a, b in progs:
        n = sum(1 for s, _, _, k in ops if k == trace.DENSE and a <= s < b)
        assert n == 4 * 16
    dense = [trace.dense_ns(recorded, [p]) / 1e6 for p in progs]
    assert dense[0] < 10 < 30 < dense[1]  # decode, chunk (ms)


def test_recorded_busy_and_idle(recorded):
    busy_s, window_s, idle = trace.idle_share(recorded)
    ops = recorded["devices"]["/device:TPU:0"]["ops"]
    lo, hi = trace.window(recorded)
    assert busy_s * 1e9 <= sum(d for _, d, _, _ in ops)
    assert 0 < idle < 0.2 and window_s == pytest.approx((hi - lo) / 1e9)
    gaps = trace.longest_gaps(recorded, n=3)
    assert len(gaps) == 3 and gaps[0][1] >= gaps[1][1] >= gaps[2][1]
