"""The expert layer of one chip of an expert-parallel deployment, the
routing rule and YaRN: the shares of a layer add up to the whole layer,
the gates are renormalised only where the model says so, YaRN's constants
at DeepSeek-V2-Lite's published dims, the grouped integer product on
signed bytes is exact, and a dense model's step is untouched by the MoE
counters."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.grouped_approx import _ragged_int_dot
from repro.nn import moe as moelib
from repro.nn.layers import yarn_range


def test_yarn_constants_at_published_dims():
    cfg = get_config("deepseek-v2-lite-16b")
    yarn = cfg.rope_scaling
    assert yarn_range(cfg.qk_rope_dim, cfg.rope_theta, yarn) == (10, 23)
    assert yarn.cos_sin_factor == 1.0
    mcfg = cfg.mla_config()
    assert mcfg.softmax_scale == pytest.approx(0.114721, abs=1e-6)
    assert 192**-0.5 == pytest.approx(0.0721688, abs=1e-7)
    assert yarn.softmax_factor == pytest.approx(1.58963, abs=1e-5)
    # the published rope_scaling mapping is equal to the field
    assert yarn == {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                    "mscale": 0.707, "mscale_all_dim": 0.707,
                    "original_max_position_embeddings": 4096,
                    "type": "yarn"}
    assert hash(yarn) == hash(dataclasses.replace(yarn))
    assert get_config("moonshot-v1-16b-a3b").rope_scaling is None


def test_yarn_frequencies_blend_over_the_ramp():
    from repro.nn.layers import rope_freqs

    cfg = get_config("deepseek-v2-lite-16b")
    base = np.asarray(rope_freqs(64, cfg.rope_theta))
    yarn = np.asarray(rope_freqs(64, cfg.rope_theta, cfg.rope_scaling))
    np.testing.assert_array_equal(yarn[:11], base[:11])  # ramp 0 to i=10
    np.testing.assert_allclose(yarn[23:], base[23:] / 40, rtol=1e-6)
    i = 16
    ramp = (i - 10) / 13
    np.testing.assert_allclose(yarn[i], base[i] / 40 * ramp
                               + base[i] * (1 - ramp), rtol=1e-6)


def _layer(norm_topk_prob=True, **kw):
    cfg = moelib.MoEConfig(d_model=32, d_ff_expert=16, n_experts=8, top_k=2,
                           n_shared=2, norm_topk_prob=norm_topk_prob, **kw)
    return cfg, moelib.init_moe(jax.random.PRNGKey(0), cfg)


@pytest.mark.parametrize("norm", [False, True],
                         ids=["published", "renormalised"])
def test_gates_follow_norm_topk_prob(norm):
    cfg, p = _layer(norm)
    x = jax.random.normal(jax.random.PRNGKey(1), (10, 32))
    gates, idx, probs = moelib._route(p, x, cfg)
    top = np.take_along_axis(np.asarray(probs), np.asarray(idx), -1)
    want = top / top.sum(-1, keepdims=True) if norm else top
    np.testing.assert_allclose(np.asarray(gates), want, rtol=1e-6)
    if not norm:
        assert (np.asarray(gates).sum(-1) < 1.0).all()


def test_default_routing_renormalises():
    assert moelib.MoEConfig(d_model=8, d_ff_expert=8, n_experts=4,
                            top_k=2).norm_topk_prob
    assert get_config("moonshot-v1-16b-a3b").norm_topk_prob
    assert not get_config("deepseek-v2-lite-16b").norm_topk_prob


def test_shares_add_up_to_the_whole_layer():
    """Each of 8 chips holding one of the reduced config's 8 experts: their
    routed parts, with the shared experts counted once, give the uncut
    layer."""
    cfg = get_config("deepseek-v2-lite-16b").reduced().moe_config()
    p = moelib.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(moelib.moe_apply(p, x, cfg))
        shared = np.asarray(moelib.swiglu(p["shared"], x))
        parts = []
        for e in range(cfg.n_experts):
            share = dataclasses.replace(cfg, experts_held=1, expert_offset=e)
            ps = {**p, "experts": jax.tree.map(lambda a: a[e:e + 1],
                                               p["experts"])}
            parts.append(np.asarray(moelib.moe_apply(ps, x, share)) - shared)
    np.testing.assert_allclose(shared + sum(parts), whole, atol=2e-5)
    assert all(np.abs(pt).max() > 0 for pt in parts)


def test_share_holds_only_its_experts():
    cfg = get_config("deepseek-v2-lite-16b").reduced()
    cfg = dataclasses.replace(cfg, experts_held=2, expert_offset=4)
    p = moelib.init_moe(jax.random.PRNGKey(0), cfg.moe_config(),
                        jnp.bfloat16)
    assert p["experts"]["gate"]["w"].shape[0] == 2
    assert p["router"]["w"].shape[-1] == cfg.n_experts
    assert p["router"]["w"].dtype == jnp.bfloat16  # the parameter dtype


def test_counts_are_the_held_pairs_and_groups():
    cfg, p = _layer(experts_held=3, expert_offset=2)
    p = {**p, "experts": jax.tree.map(lambda a: a[:3], p["experts"])}
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 16, 32))
    _, counts = moelib.moe_apply(p, x, cfg, with_counts=True)
    _, idx, _ = moelib._route(p, x.reshape(-1, 32), cfg)
    idx = np.asarray(idx)
    held = (idx >= 2) & (idx < 5)
    assert int(counts[0]) == int(held.sum())
    assert int(counts[1]) == len(set(idx[held].tolist()))


def test_ep_psum_refuses_an_expert_share():
    cfg, p = _layer(experts_held=2, impl="ep_psum")
    mesh = jax.make_mesh((1,), ("model",))
    with pytest.raises(ValueError, match="experts_held"):
        moelib.moe_apply(p, jnp.zeros((1, 4, 32)), cfg, mesh=mesh)


@pytest.mark.parametrize("k", [64, 1408])
def test_grouped_int_product_on_signed_bytes_is_exact(k):
    """Codes in [0, 255] shifted to signed bytes and the shift put back:
    equal to the int32 grouped product, rows past the groups 0."""
    rng = np.random.default_rng(k)
    w = jnp.asarray(rng.integers(0, 256, (4, k, 24)), jnp.uint8)
    gs = jnp.asarray([3, 0, 5, 2], jnp.int32)
    a = jnp.asarray(rng.integers(0, 256, (13, k)), jnp.int32)
    want = jax.lax.ragged_dot(a, w.astype(jnp.int32), gs,
                              preferred_element_type=jnp.int32)
    got = _ragged_int_dot(a, w, gs)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.asarray(got)[10:].any()


def test_dense_step_has_no_moe_output_or_scope():
    """A dense model's served step: two outputs, with or without the
    counters asked for, the same program, and no MoE scope in it."""
    from repro.configs.base import EngineConfig
    from repro.models import build_model
    from repro.serving import ServingEngine

    cfg = get_config("olmo-1b").reduced()
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, EngineConfig(slots=2, max_len=32,
                                                  prefill_chunk=8), api=api)
    args = (params, jnp.zeros((2, 8), jnp.int32), eng.pool.cache,
            jnp.zeros((2,), jnp.int32))
    texts = []
    for counted in (False, True):
        fn = jax.jit(lambda p, t, c, n, counted=counted: api.decode_slots(
            p, t, c, n, moe_counts=counted))
        assert len(jax.eval_shape(fn, *args)) == 2
        texts.append(fn.lower(*args).as_text())
    assert texts[0] == texts[1]
    # the op names (scopes) ride in the locations
    engine_text = eng._step_fns["chunk"].lower(*args).as_text(
        debug_info=True)
    assert '"mlp_in/' in engine_text
    for scope in moelib.SCOPES:
        assert f"{scope}/" not in engine_text
    assert eng._moe_counts is None and not eng._moe


def test_moe_step_names_its_scopes_and_returns_counts():
    from repro.configs.base import EngineConfig
    from repro.models import build_model
    from repro.serving import ServingEngine

    cfg = get_config("deepseek-v2-lite-16b").reduced()
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, EngineConfig(slots=2, max_len=32,
                                                  prefill_chunk=8), api=api)
    args = (params, jnp.zeros((2, 8), jnp.int32), eng.pool.cache,
            jnp.zeros((2,), jnp.int32))
    out = jax.eval_shape(eng._step_fns["chunk"], *args)
    assert len(out) == 3 and out[2].shape == (2,)
    text = eng._step_fns["chunk"].lower(*args).as_text(debug_info=True)
    for scope in moelib.SCOPES:
        assert f'"mlp_in/{scope}/' in text
