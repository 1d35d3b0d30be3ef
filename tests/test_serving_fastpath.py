"""Zero-overhead serving path: fan-out fusion, offline-blocked layout,
quantize-in-kernel, and decode-shape specialization.

Equality contract (docs/kernels.md):

  * fused fan-out vs separate member calls — BIT-identical (same lowering,
    per-column arithmetic unchanged);
  * offline-blocked kernel path vs the legacy per-call-padding path —
    bit-identical at tile-aligned K; float-ulp association difference when
    the legacy path pads K (its pad compensation sits outside the sa*sw
    rescale), in which case the BLOCKED path is the one matching ref.py;
  * Pallas kernels vs ref.py scalar semantics — exact integer accumulators,
    f32 epilogue within the kernel suite's standard rtol=2e-5 (FMA
    contraction differs across lowerings);
  * folded jnp serving operands (build_fold) vs the exact integer path —
    the same math re-associated into float GEMMs: float-ulp agreement.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.approx_linear import (
    QuantizedDense,
    QuantizedDenseGroup,
    dense,
    dense_group,
    pack_dense,
    pack_params,
    packed_layer_paths,
)
from repro.core.policy import ApproxPolicy
from repro.kernels import ops, ref
from repro.quant.quantize import quantize

RNG = np.random.default_rng(11)

ALL_MODES = [("exact", 0), ("perforated", 2), ("recursive", 3), ("truncated", 6)]


def _qkv_params(k=64, nq=64, nkv=32, bias=False):
    def leaf(n):
        p = {"w": jnp.asarray(RNG.normal(0, 0.1, (k, n)), jnp.float32)}
        if bias:
            p["b"] = jnp.asarray(RNG.normal(0, 0.3, (n,)), jnp.float32)
        return p

    return {"q": leaf(nq), "k": leaf(nkv), "v": leaf(nkv), "o": leaf(k)}


# ---------------------------------------------------------------------------
# fan-out fusion: bit-identity vs separate dense() calls
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode,m", ALL_MODES)
@pytest.mark.parametrize("use_cv", [True, False])
def test_fused_qkv_bit_identical_vs_separate(mode, m, use_cv):
    params = _qkv_params()
    pol = ApproxPolicy(mode, m, use_cv=use_cv)
    fused = pack_params(params, lambda p: pol)
    sep = pack_params(params, lambda p: pol, fuse=False)
    assert isinstance(fused["qkv"], QuantizedDenseGroup)
    assert fused["qkv"].names == ("q", "k", "v")
    x = jnp.asarray(RNG.normal(0, 1, (3, 7, 64)), jnp.float32)
    outs = dense_group(fused["qkv"], x)
    for name in ("q", "k", "v"):
        np.testing.assert_array_equal(
            np.asarray(outs[name]), np.asarray(dense(sep[name], x)), err_msg=name)


def test_fused_qkv_with_bias_and_grouped_cv():
    params = _qkv_params(bias=True)
    pol = ApproxPolicy("perforated", 3, groups=4)
    fused = pack_params(params, lambda p: pol)
    sep = pack_params(params, lambda p: pol, fuse=False)
    x = jnp.asarray(RNG.normal(0, 1, (5, 64)), jnp.float32)
    outs = dense_group(fused["qkv"], x)
    for name in ("q", "k", "v"):
        np.testing.assert_array_equal(
            np.asarray(outs[name]), np.asarray(dense(sep[name], x)))


def test_fused_gateup_swiglu_bit_identical():
    from repro.nn.layers import init_swiglu, swiglu

    p = init_swiglu(jax.random.PRNGKey(0), 64, 128)
    pol = ApproxPolicy("recursive", 3)
    fused = pack_params(p, lambda path: pol)
    sep = pack_params(p, lambda path: pol, fuse=False)
    assert isinstance(fused["gateup"], QuantizedDenseGroup)
    assert "gate" not in fused and "up" not in fused
    x = jnp.asarray(RNG.normal(0, 1, (2, 5, 64)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(swiglu(fused, x)), np.asarray(swiglu(sep, x)))


def test_fused_qkv_stacked_scan_sliceable():
    """(L, k, n) stacked fused groups slice per layer under lax.scan and
    stay bit-identical to the unfused stacked packs."""
    L, k = 2, 32
    params = {
        n: {"w": jnp.asarray(RNG.normal(0, 0.1, (L, k, w)), jnp.float32)}
        for n, w in (("q", 32), ("k", 16), ("v", 16), ("o", 32))
    }
    pol = ApproxPolicy("perforated", 2)
    fused = pack_params(params, lambda p: pol)
    sep = pack_params(params, lambda p: pol, fuse=False)
    x = jnp.asarray(RNG.normal(0, 1, (3, k)), jnp.float32)

    def body_fused(carry, g):
        outs = dense_group(g, carry)
        return carry, jnp.concatenate([outs["q"], outs["k"], outs["v"]], -1)

    def body_sep(carry, layer):
        q, kk, v = layer
        return carry, jnp.concatenate(
            [dense(q, carry), dense(kk, carry), dense(v, carry)], -1)

    _, yf = jax.lax.scan(body_fused, x, fused["qkv"])
    _, ys = jax.lax.scan(body_sep, x, (sep["q"], sep["k"], sep["v"]))
    np.testing.assert_array_equal(np.asarray(yf), np.asarray(ys))


def test_fusion_skips_mismatched_policies_and_experts():
    params = _qkv_params()
    pols = {"q": ApproxPolicy("perforated", 2), "k": ApproxPolicy("perforated", 3),
            "v": ApproxPolicy("perforated", 2), "o": ApproxPolicy("perforated", 2)}
    packed = pack_params(params, lambda p: pols[p[-1]])
    assert "qkv" not in packed  # policies differ: no fusion
    assert isinstance(packed["q"], QuantizedDense)

    # q/k/v names WITHOUT the attention companion "o" (e.g. RWKV-style
    # mixes whose members take different inputs) must never fuse
    no_comp = {kk: vv for kk, vv in _qkv_params().items() if kk != "o"}
    packed = pack_params(no_comp, lambda p: ApproxPolicy("perforated", 2))
    assert "qkv" not in packed
    assert isinstance(packed["q"], QuantizedDense)

    # MoE expert stacks keep per-member packs for the ragged grouped path
    experts = {"experts": {
        n: {"w": jnp.asarray(RNG.normal(0, 0.1, (4, 16, 8)), jnp.float32)}
        for n in ("gate", "up", "down")}}
    packed = pack_params(experts, lambda p: ApproxPolicy("perforated", 2))
    assert "gateup" not in packed["experts"]
    assert isinstance(packed["experts"]["gate"], QuantizedDense)


def test_fused_model_forward_and_paths_match_unfused():
    from repro.configs import get_config
    from repro.models import build_model
    from repro.numerics import apply_numerics, get_preset

    cfg = get_config("olmo-1b-reduced")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    plan = get_preset("serve-default").resolve(params)
    fused = apply_numerics(params, plan)
    want = {e.path: e.policy for e in plan.entries}
    unfused = pack_params(params, lambda p: want.get("/".join(p)), fuse=False)
    assert packed_layer_paths(fused) == packed_layer_paths(unfused)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 12), 0, cfg.vocab)
    np.testing.assert_array_equal(
        np.asarray(api.forward(fused, {"tokens": toks})),
        np.asarray(api.forward(unfused, {"tokens": toks})))


# ---------------------------------------------------------------------------
# offline-blocked layout + quantize-in-kernel (pallas backend)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode,m", [("perforated", 2), ("recursive", 3),
                                    ("truncated", 6)])
@pytest.mark.parametrize("use_cv", [True, False])
def test_blocked_kernel_matches_ref_scalar_semantics(mode, m, use_cv):
    """Quantize-in-kernel over the blocked layout vs ref.py on the same
    codes (standard kernel-suite tolerance; integer parts are exact)."""
    k, n = 200, 48  # deliberately unaligned: exercises in-kernel K masking
    w = jnp.asarray(RNG.normal(0, 0.1, (k, n)), jnp.float32)
    b = jnp.asarray(RNG.normal(0, 0.5, (n,)), jnp.float32)
    x = jnp.asarray(RNG.normal(0, 1, (5, k)), jnp.float32)
    pol = ApproxPolicy(mode, m, use_cv=use_cv, backend="pallas")
    qd = pack_dense({"w": w, "b": b}, pol, (-4.0, 4.0))
    assert qd.blocked is not None
    y = np.asarray(dense(qd, x))
    a_q = quantize(x, qd.a_qp)
    r = np.asarray(ref.approx_matmul_cv_ref(
        a_q, qd.pack.w_q, qd.pack.c, qd.pack.c0, qd.pack.sum_qw, b,
        qd.a_qp.scale, qd.pack.w_scale, qd.a_qp.zero_point, qd.pack.w_zp,
        mode=mode, m=m, use_cv=use_cv))
    np.testing.assert_allclose(y, r, rtol=2e-5, atol=2e-3)


@pytest.mark.parametrize("mode,m", [("perforated", 2), ("recursive", 3),
                                    ("truncated", 6)])
def test_blocked_bit_identical_to_legacy_at_aligned_k(mode, m):
    k, n = 256, 48  # K already a tile multiple: no legacy pad compensation
    w = jnp.asarray(RNG.normal(0, 0.1, (k, n)), jnp.float32)
    x = jnp.asarray(RNG.normal(0, 1, (5, k)), jnp.float32)
    qd = pack_dense({"w": w}, ApproxPolicy(mode, m, backend="pallas"),
                    (-4.0, 4.0))
    y_blocked = np.asarray(dense(qd, x))
    y_legacy = np.asarray(dense(dataclasses.replace(qd, blocked=None), x))
    np.testing.assert_array_equal(y_blocked, y_legacy)


def test_blocked_close_to_legacy_at_unaligned_k():
    """With K padding the legacy path compensates (k_pad-k)*za*zw outside
    the sa*sw rescale — ulp-level association difference only."""
    k, n = 200, 48
    w = jnp.asarray(RNG.normal(0, 0.1, (k, n)), jnp.float32)
    x = jnp.asarray(RNG.normal(0, 1, (5, k)), jnp.float32)
    qd = pack_dense({"w": w}, ApproxPolicy("perforated", 2, backend="pallas"),
                    (-4.0, 4.0))
    y_blocked = np.asarray(dense(qd, x))
    y_legacy = np.asarray(dense(dataclasses.replace(qd, blocked=None), x))
    np.testing.assert_allclose(y_blocked, y_legacy, rtol=2e-5, atol=2e-4)


def test_pallas_fused_group_bit_identical_vs_separate_pallas():
    params = _qkv_params(k=128)
    pol = ApproxPolicy("perforated", 2, backend="pallas")
    fused = pack_params(params, lambda p: pol)
    sep = pack_params(params, lambda p: pol, fuse=False)
    assert fused["qkv"].blocked is not None
    x = jnp.asarray(RNG.normal(0, 1, (4, 128)), jnp.float32)
    outs = dense_group(fused["qkv"], x)
    for name in ("q", "k", "v"):
        np.testing.assert_array_equal(
            np.asarray(outs[name]), np.asarray(dense(sep[name], x)))


@pytest.mark.parametrize("m_rows", [4, 128])
def test_decode_and_prefill_shapes_pick_valid_blocks(m_rows):
    """M=4 exercises the decode-specialized single-K-step tiles, M=128 the
    prefill tiles; both must agree with ref."""
    k, n = 384, 32
    w = jnp.asarray(RNG.normal(0, 0.1, (k, n)), jnp.float32)
    x = jnp.asarray(RNG.normal(0, 1, (m_rows, k)), jnp.float32)
    qd = pack_dense({"w": w}, ApproxPolicy("perforated", 2, backend="pallas"),
                    (-4.0, 4.0))
    y = np.asarray(dense(qd, x))
    a_q = quantize(x, qd.a_qp)
    r = np.asarray(ref.approx_matmul_cv_ref(
        a_q, qd.pack.w_q, qd.pack.c, qd.pack.c0, qd.pack.sum_qw,
        jnp.zeros((n,), jnp.float32), qd.a_qp.scale, qd.pack.w_scale,
        qd.a_qp.zero_point, qd.pack.w_zp, mode="perforated", m=2))
    np.testing.assert_allclose(y, r, rtol=2e-5, atol=2e-3)


def test_pick_blocks_decode_merges_k_axis():
    bm, bn, bk = ops._pick_blocks(4, 2048, 128, 128, 128, 512)
    assert bm == 8 and bk == 2048  # single K step for decode rows
    bm, bn, bk = ops._pick_blocks(128, 2048, 128, 128, 128, 512)
    assert bk == 512  # prefill keeps the default K depth


def test_pallas_grouped_cv_falls_back_to_jnp():
    """backend="pallas" with groups > 1 has no kernel: the policy is
    refused where it is built, so no pack can silently serve through the
    jnp grouped path in its place (the jnp policy still builds and serves)."""
    with pytest.raises(ValueError, match="groups > 1"):
        ApproxPolicy("perforated", 3, groups=4, backend="pallas")
    w = jnp.asarray(RNG.normal(0, 0.1, (64, 16)), jnp.float32)
    x = jnp.asarray(RNG.normal(0, 1, (4, 64)), jnp.float32)
    qd_j = pack_dense({"w": w},
                      ApproxPolicy("perforated", 3, groups=4, backend="jnp"),
                      (-4.0, 4.0))
    assert np.isfinite(np.asarray(dense(qd_j, x))).all()


# ---------------------------------------------------------------------------
# folded serving operands (jnp fast path)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode,m", ALL_MODES)
@pytest.mark.parametrize("use_cv", [True, False])
def test_folded_path_matches_integer_reference(mode, m, use_cv):
    """The folded float-GEMM path vs the exact-integer reference path:
    same math re-associated, so agreement to float ulps."""
    from repro.quant.quantize import quantized_linear

    k, n = 96, 40
    w = jnp.asarray(RNG.normal(0, 0.1, (k, n)), jnp.float32)
    b = jnp.asarray(RNG.normal(0, 0.5, (n,)), jnp.float32)
    x = jnp.asarray(RNG.normal(0, 1, (9, k)), jnp.float32)
    qd = pack_dense({"w": w, "b": b}, ApproxPolicy(mode, m, use_cv=use_cv),
                    (-4.0, 4.0))
    assert qd.fold is not None
    y = np.asarray(dense(qd, x))
    r = np.asarray(quantized_linear(x, qd.pack, qd.a_qp, mode, m,
                                    use_cv=use_cv))
    np.testing.assert_allclose(y, r, rtol=2e-5, atol=2e-4)


def test_pack_params_fold_false_keeps_exact_integer_path():
    from repro.quant.quantize import quantized_linear

    params = _qkv_params()
    pol = ApproxPolicy("perforated", 2)
    packed = pack_params(params, lambda p: pol, fuse=False, fold=False)
    assert packed["q"].fold is None
    x = jnp.asarray(RNG.normal(0, 1, (5, 64)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(dense(packed["q"], x)),
        np.asarray(quantized_linear(x, packed["q"].pack, packed["q"].a_qp,
                                    "perforated", 2)))


def test_fold_skipped_for_grouped_and_deep_fanin():
    w_deep = jnp.asarray(RNG.normal(0, 0.1, (512, 16)), jnp.float32)
    qd = pack_dense({"w": w_deep}, ApproxPolicy("perforated", 2), (-4.0, 4.0))
    assert qd.fold is None  # deep fan-in keeps the exact integer path
    w = jnp.asarray(RNG.normal(0, 0.1, (64, 16)), jnp.float32)
    qd = pack_dense({"w": w}, ApproxPolicy("perforated", 2, groups=4),
                    (-4.0, 4.0))
    assert qd.fold is None  # grouped CV keeps the exact integer path


# ---------------------------------------------------------------------------
# plan accounting + engine surfacing
# ---------------------------------------------------------------------------


def test_plan_reports_blocked_and_fold_bytes():
    from repro.numerics import uniform_spec
    from repro.quant.quantize import EPI_ROWS, META_LEN, serving_blocks

    k, n = 200, 48
    params = {"lin": {"w": jnp.zeros((k, n))}}
    plan_j = uniform_spec(ApproxPolicy("perforated", 2)).resolve(params)
    plan_p = uniform_spec(
        ApproxPolicy("perforated", 2, backend="pallas")).resolve(params)
    bn, bk = serving_blocks(k, n)
    kb, nb = -(-k // bk) * bk, -(-n // bn) * bn
    legacy = k * n + 4 * n * 3  # uint8 codes + sum_qw/c/c0 vectors
    blocked = kb * nb + 4 * (EPI_ROWS * nb + META_LEN)
    assert plan_p.entries[0].packed_bytes == legacy + blocked
    # jnp backend: canonical pack + the folded f32 operands
    # (A and B are (k, n) each for perforated, delta is (n,))
    fold = 4 * (2 * k * n + n)
    assert plan_j.entries[0].packed_bytes == legacy + fold

    # deep fan-in: no fold built, none counted
    deep = {"lin": {"w": jnp.zeros((512, n))}}
    plan_deep = uniform_spec(ApproxPolicy("perforated", 2)).resolve(deep)
    assert plan_deep.entries[0].packed_bytes == 512 * n + 4 * n * 3


def test_engine_metrics_surface_decode_specialization():
    from repro.configs import get_config
    from repro.configs.base import EngineConfig
    from repro.launch.serve import ServeConfig, build_serving_params
    from repro.models import build_model
    from repro.serving import ServingEngine

    cfg = get_config("olmo-1b-reduced")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    ecfg = EngineConfig(slots=4, max_len=32, prefill_chunk=8)

    # float params: no blocked packs, so the flag must stay off even though
    # the slot count fits the decode window
    eng = ServingEngine(cfg, params, ecfg)
    assert eng.metrics.snapshot()["decode_specialized"] is False

    pallas = build_serving_params(params, cfg, ServeConfig(
        policy=ApproxPolicy("perforated", 2, backend="pallas")))
    eng_p = ServingEngine(cfg, pallas, ecfg)
    assert eng_p.metrics.snapshot()["decode_specialized"] is True
    eng_p.reset_metrics()
    assert eng_p.metrics.snapshot()["decode_specialized"] is True

    eng16 = ServingEngine(cfg, pallas, EngineConfig(slots=16, max_len=32,
                                                    prefill_chunk=8))
    assert eng16.metrics.snapshot()["decode_specialized"] is False
