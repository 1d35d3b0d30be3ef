"""Serving correctness: prefill + decode must reproduce the training-path
forward logits token by token, for every decode-capable architecture."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_archs
from repro.models import build_model

DECODE_ARCHS = [a for a in list_archs() if get_config(a).has_decode]


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_prefill_decode_match_forward(arch):
    cfg = dataclasses.replace(get_config(arch + "-reduced"), compute_dtype="float32")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    b, t = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, t), 0, cfg.vocab)
    full = api.forward(params, {"tokens": toks})

    logits, cache = api.prefill(params, {"tokens": toks[:, : t - 2]}, max_len=t + 4,
                                cache_dtype=jnp.float32)
    assert float(jnp.abs(logits - full[:, t - 3]).max()) < 1e-3
    for i in (t - 2, t - 1):
        logits, cache = api.decode_step(params, toks[:, i : i + 1], cache)
        assert float(jnp.abs(logits - full[:, i]).max()) < 1e-3, (arch, i)


def test_ring_cache_equals_full_window_decode():
    """hymba's ring cache (len W) decodes identically to masked full attention."""
    cfg = dataclasses.replace(get_config("hymba-1.5b-reduced"),
                              compute_dtype="float32")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    b = 2
    t_total = 20  # window is 8 in the reduced config: exercises wraparound
    toks = jax.random.randint(jax.random.PRNGKey(2), (b, t_total), 0, cfg.vocab)
    full = api.forward(params, {"tokens": toks})
    _, cache = api.prefill(params, {"tokens": toks[:, :4]}, max_len=t_total,
                           cache_dtype=jnp.float32)
    for i in range(4, t_total):
        logits, cache = api.decode_step(params, toks[:, i : i + 1], cache)
        err = float(jnp.abs(logits - full[:, i]).max())
        assert err < 2e-3, (i, err)


def test_greedy_generation_runs_jitted():
    cfg = get_config("olmo-1b-reduced")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0, cfg.vocab)
    prefill = jax.jit(lambda p, b: api.prefill(p, b, max_len=24))
    decode = jax.jit(api.decode_step)
    logits, cache = prefill(params, {"tokens": prompt})
    tok = jnp.argmax(logits, -1)[:, None]
    for _ in range(8):
        logits, cache = decode(params, tok, cache)
        tok = jnp.argmax(logits, -1)[:, None]
        assert bool(jnp.isfinite(logits).all())
    assert int(cache["pos"]) == 8 + 8


@pytest.mark.parametrize("kv_layout", ["contiguous", "paged"])
def test_decode_slots_single_device_mesh_token_identity(kv_layout):
    """The ``decode_slots(..., mesh=)`` plumb-through: a single-device
    mesh (what every fleet replica gets, repro.serving.fleet.replica_mesh)
    must generate token-identically to the mesh-less path, on both KV
    layouts.  This is the no-op anchor the multi-host fleet placement
    builds on — if a trivial mesh perturbs tokens, a sharded one hides
    real divergence."""
    from repro.configs.base import EngineConfig
    from repro.serving import ServingEngine
    from repro.serving.fleet import replica_mesh

    cfg = get_config("olmo-1b-reduced")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    ecfg = EngineConfig(slots=2, max_len=48, prefill_chunk=16,
                        kv_layout=kv_layout)
    rng = np.random.default_rng(5)
    # one short prompt, one crossing a paged block boundary
    jobs = [(rng.integers(0, cfg.vocab, 10).tolist(), 6),
            (rng.integers(0, cfg.vocab, 20).tolist(), 6)]
    outs = []
    for mesh in (None, replica_mesh()):
        eng = ServingEngine(cfg, params, ecfg, api=api, mesh=mesh)
        reqs = [eng.submit(p, g) for p, g in jobs]
        eng.run()
        assert all(r.finish_reason == "length" for r in reqs)
        outs.append([r.generated for r in reqs])
    assert outs[0] == outs[1]


def test_int8_kv_cache_decode_close():
    """int8 KV cache (the §Perf decode optimization): logits stay close to
    the bf16-cache decode (fixed-point 1/16 resolution on O(1) post-rope
    values)."""
    import jax.numpy as jnp

    cfg = dataclasses.replace(get_config("qwen3-4b-reduced"), compute_dtype="float32")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    b, t = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, t), 0, cfg.vocab)
    ref = api.forward(params, {"tokens": toks})

    _, cache = api.prefill(params, {"tokens": toks[:, : t - 2]}, max_len=t + 2,
                           cache_dtype=jnp.int8)
    for i in (t - 2, t - 1):
        logits, cache = api.decode_step(params, toks[:, i : i + 1], cache)
        err = float(jnp.abs(logits - ref[:, i]).max())
        scale = float(jnp.abs(ref[:, i]).max())
        assert err < 0.05 * scale + 0.05, (i, err, scale)


def test_slot_decode_query_padding_keeps_logits(monkeypatch):
    """The slot attention pads a decode-shaped call's query axis to
    ``MIN_QUERY_COLS`` columns; the padding must change neither the real
    column's logits nor the K/V the call writes."""
    from repro.models import lm

    cfg = dataclasses.replace(get_config("olmo-1b-reduced"),
                              compute_dtype="float32")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    cache = api.init_slot_cache(2, 32, jnp.float32)
    _, cache = api.decode_slots(
        params, jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32),
        cache, jnp.asarray([10, 5], np.int32))
    tok = jnp.asarray(rng.integers(0, cfg.vocab, (2, 1)), jnp.int32)
    nv = jnp.asarray([1, 1], np.int32)
    assert lm.MIN_QUERY_COLS > 1
    padded, padded_cache = api.decode_slots(params, tok, cache, nv)
    monkeypatch.setattr(lm, "MIN_QUERY_COLS", 1)
    plain, plain_cache = api.decode_slots(params, tok, cache, nv)
    np.testing.assert_allclose(np.asarray(padded), np.asarray(plain),
                               rtol=1e-5, atol=1e-5)
    for key in ("k", "v", "lengths"):
        np.testing.assert_array_equal(np.asarray(padded_cache[key]),
                                      np.asarray(plain_cache[key]))
