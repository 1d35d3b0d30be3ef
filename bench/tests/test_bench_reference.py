"""The dense GQA family's plain reference against the program's own float
forward at a tiny olmo and a tiny granite size (CPU, float32 compute), and
the control's lower precision moving it."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from bench.families import dense_gqa

FIXTURES = Path(__file__).resolve().parent / "fixtures"


def _tiny(name):
    from repro.configs import get_config

    conf = json.loads((FIXTURES / "configs" / f"{name}.json").read_text())
    over = {**conf["overrides"], "compute_dtype": "float32",
            "param_dtype": "float32"}
    cfg = dataclasses.replace(get_config(conf["arch"]), **over)
    return conf["model"], cfg


@pytest.mark.parametrize("name", ["tiny-olmo", "tiny-granite"])
def test_reference_matches_the_program_float_forward(name):
    from repro.models import build_model

    model, cfg = _tiny(name)
    w = dense_gqa.make(model, 2**33 + 1, "float32")
    toks = np.random.default_rng(0).integers(0, model["vocab"],
                                             reference.Q_BLOCK)
    ref = np.asarray(dense_gqa.forward(w, toks, model))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(build_model(cfg).forward(
            w, {"tokens": jnp.asarray(toks[None], jnp.int32)}))[0]
    np.testing.assert_allclose(ref, got, rtol=2e-4, atol=2e-4)


def test_readings_agree_with_the_full_logits():
    model, _ = _tiny("tiny-granite")
    w = dense_gqa.make(model, 3, "float32")
    toks = np.random.default_rng(1).integers(0, model["vocab"],
                                             reference.Q_BLOCK)
    picks = np.random.default_rng(2).integers(0, model["vocab"],
                                              (2, reference.Q_BLOCK))
    full = np.asarray(dense_gqa.forward(w, toks, model))
    best, at, top = (np.asarray(x) for x in
                     dense_gqa.readings(w, toks, picks, model))
    np.testing.assert_allclose(best, full.max(-1), rtol=1e-6)
    np.testing.assert_allclose(at, np.take_along_axis(
        full[None], picks[..., None], -1)[..., 0], rtol=1e-6)
    assert np.array_equal(top, full.argmax(-1))


def test_control_is_lower_precision():
    model, _ = _tiny("tiny-olmo")
    w = dense_gqa.make(model, 4, "float32")
    toks = np.arange(reference.Q_BLOCK) % model["vocab"]
    ref = np.asarray(dense_gqa.forward(w, toks, model))
    ctl = np.asarray(dense_gqa.forward(w, toks, model, bits=4))
    rel = np.linalg.norm(ctl - ref) / np.linalg.norm(ref)
    assert rel > 0.05


def test_weights_are_seeded():
    model, _ = _tiny("tiny-olmo")
    a = dense_gqa.make(model, 2**33 + 9, "float32")
    b = dense_gqa.make(model, 2**33 + 9, "float32")
    c = dense_gqa.make(model, 2**33 + 10, "float32")
    la, lb, lc = (jax.tree.leaves(t) for t in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert not np.array_equal(la[0], lc[0])
