"""The served step updates the stacked KV cache in place.

``decode_slots`` on the contiguous layout carries the layer-stacked cache
through its layer scan and writes each slot's new columns into it; with
``unroll_layers`` it takes each layer's stripes in and gives them back.
Both must give the same bits.  The engine donates the cache to the step
unless the error probe holds the pre-step cache across it.  (That the
donated programs move no stack- or stripe-shaped data is checked on the
v5e's compiler, in ``test_tpu_compile.py``.)
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import EngineConfig
from repro.launch.serve import ServeConfig, build_serving_params
from repro.models import build_model, lm
from repro.numerics import get_preset
from repro.quant.error_probe import ErrorProbe
from repro.serving import ServingEngine

S, CH, SLOTS = 32, 8, 3

#: one config per attention kind: GQA, and MLA with a dense first block
ARCHS = {"gqa": "olmo-1b-reduced", "mla": "deepseek-v2-lite-16b-reduced"}

#: calls as (columns, n_valid per slot), run in order from an empty cache
SCENARIOS = {
    "chunk": [(CH, (8, 5, 0)), (CH, (8, 8, 3))],
    "decode": [(CH, (8, 4, 0)), (1, (1, 1, 0)), (1, (1, 0, 1))],
    # decode rows riding chunk-shaped calls beside prompt chunks
    "mixed": [(CH, (8, 8, 0)), (CH, (1, 8, 2)), (CH, (1, 1, 8))],
    # slot 0's cursor passes S - CH, then a decode row and a padding row
    # of it ride chunk-shaped calls, whose writes would clamp
    "clamp": [(CH, (8, 0, 0))] * 3 + [(1, (1, 0, 0))] * 3
    + [(CH, (1, 8, 0)), (CH, (0, 8, 0))],
    "padding": [(CH, (8, 0, 8)), (CH, (0, 8, 0)), (1, (0, 0, 1))],
}


@pytest.fixture(scope="module")
def models():
    out = {}
    for kind, arch in ARCHS.items():
        cfg = dataclasses.replace(get_config(arch), compute_dtype="float32")
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        in_place = jax.jit(lambda p, t, c, nv, cfg=cfg:
                           lm.decode_slots(p, t, c, cfg, nv))
        per_stripe = jax.jit(lambda p, t, c, nv, cfg=cfg:
                             lm.decode_slots(p, t, c, cfg, nv,
                                             unroll_layers=True))
        out[kind] = (cfg, params, in_place, per_stripe)
    return out


@pytest.mark.parametrize("scenario", list(SCENARIOS))
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("kind", list(ARCHS))
def test_in_place_step_matches_per_stripe_step(models, kind, kv_dtype,
                                               scenario):
    cfg, params, in_place, per_stripe = models[kind]
    cache = lm.init_slot_cache(cfg, SLOTS, S, jnp.dtype(kv_dtype))
    rng = np.random.default_rng(7)
    for i, (c, nv) in enumerate(SCENARIOS[scenario]):
        toks = jnp.asarray(rng.integers(0, cfg.vocab, (SLOTS, c)), jnp.int32)
        nv = jnp.asarray(nv, jnp.int32)
        if scenario == "clamp" and i >= 6:
            assert int(cache["lengths"][0]) > S - CH
        want_logits, want = per_stripe(params, toks, cache, nv)
        got_logits, got = in_place(params, toks, cache, nv)
        assert np.array_equal(np.asarray(got_logits),
                              np.asarray(want_logits)), i
        assert set(got) == set(want)
        for k in want:
            assert np.array_equal(np.asarray(got[k]), np.asarray(want[k])), \
                (i, k)
        cache = want


# ---------------------------------------------------------------------------
# the engine's donated step
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    cfg = dataclasses.replace(get_config("olmo-1b-reduced"),
                              compute_dtype="float32")
    api = build_model(cfg)
    params = build_serving_params(api.init(jax.random.PRNGKey(0)), cfg,
                                  ServeConfig(spec=get_preset("serve-default")))
    return cfg, api, params


def _engine(served, probe: bool, trace: bool = False) -> ServingEngine:
    cfg, api, params = served
    return ServingEngine(cfg, params,
                         EngineConfig(slots=2, max_len=64, prefill_chunk=16,
                                      cache_dtype="float32",
                                      error_probe_every=1 if probe else 0,
                                      trace=trace),
                         api=api, numerics="serve-default")


def _serve(eng: ServingEngine, vocab: int) -> list[list[int]]:
    rng = np.random.default_rng(3)
    reqs = [eng.submit(rng.integers(0, vocab, n).tolist(), g)
            for n, g in ((20, 6), (9, 8), (30, 4))]
    eng.run()
    assert all(r.finished for r in reqs)
    return [r.generated for r in reqs]


@pytest.mark.parametrize("probe", [False, True], ids=["no_probe", "probe"])
def test_step_donates_the_cache_unless_the_probe_holds_it(served, probe):
    eng = _engine(served, probe)
    assert eng.kv_in_place == (not probe)
    for shape, c in (("decode", 1), ("chunk", 16)):
        head = eng._step_fns[shape].lower(
            eng.params, jnp.zeros((2, c), jnp.int32), eng.pool.cache,
            jnp.zeros((2,), jnp.int32)).compile().as_text().splitlines()[0]
        aliased = re.findall(r"\{\d+\}: \((\d+), \{\}, may-alias\)", head)
        # every cache leaf (k, v and the cursors) is an aliased input
        assert len(aliased) == (0 if probe else len(eng.pool.cache)), shape


def test_donated_engine_serves_the_undonated_tokens(served):
    cfg = served[0]
    donated = _engine(served, probe=False, trace=True)
    kept = _engine(served, probe=True)
    assert _serve(donated, cfg.vocab) == _serve(kept, cfg.vocab)
    snap = donated.metrics.snapshot()
    steps = snap["prefill_steps"] + snap["decode_steps"] + snap["mixed_steps"]
    assert steps > 0 and snap["kv_in_place_steps"] == steps
    assert kept.metrics.snapshot()["kv_in_place_steps"] == 0
    dispatches = [e.data for e in donated.tracer.events()
                  if e.kind == "dispatch"]
    assert len(dispatches) == steps
    assert all(d["in_place"] is True for d in dispatches)


def test_probe_reports_on_the_pre_step_cache(served, monkeypatch):
    """With the probe on, the step keeps its input: every probe report
    equals a fresh probe's on a copy of the cache taken before the step."""
    cfg, api, params = served
    eng = _engine(served, probe=True)
    before, runs = [], []
    dispatch = ServingEngine._dispatch

    def keep_cache(self, p, batch, tables):
        before.append(jax.tree.map(jnp.copy, self.pool.cache))
        return dispatch(self, p, batch, tables)

    probe_run = eng._probe.run

    def record(p, tokens, n_valid, cache, block_tables=None):
        report = probe_run(p, tokens, n_valid, cache,
                           block_tables=block_tables)
        runs.append((len(before) - 1, np.array(tokens), np.array(n_valid),
                     report))
        return report

    monkeypatch.setattr(ServingEngine, "_dispatch", keep_cache)
    monkeypatch.setattr(eng._probe, "run", record)
    _serve(eng, cfg.vocab)
    assert runs and eng.metrics.snapshot()["error_probe"]["runs"] == len(runs)
    fresh = ErrorProbe(api.decode_slots)
    for step, tokens, n_valid, report in runs:
        assert report == fresh.run(params, tokens, n_valid, before[step])
