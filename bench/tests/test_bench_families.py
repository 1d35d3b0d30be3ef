"""The dense GQA family gives the numbers the harness gave before its
model-specific parts moved into a family module: the seeded weights
(hashed), the reference's readings at 8 positions with and without the
control's 4 bits, and the counts, against values recorded from the tree
before the move (``fixtures/dense_gqa_numbers.json``).

XLA's CPU backend rounds the reference's float32 products differently on
one core and on several, so the readings are taken in a child process held
to one core, as they were recorded:

    PYTHONPATH=. JAX_PLATFORMS=cpu python bench/tests/test_bench_families.py
"""

import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from bench.families import dense_gqa

BENCH = Path(__file__).resolve().parents[1]
FIXTURES = Path(__file__).resolve().parent / "fixtures"
NUMBERS = json.loads((FIXTURES / "dense_gqa_numbers.json").read_text())
CONFIGS = {"tiny-olmo": FIXTURES / "configs" / "tiny-olmo.json",
           "tiny-granite": FIXTURES / "configs" / "tiny-granite.json",
           "olmo-1b-sd": BENCH / "configs" / "olmo-1b-sd.json",
           "granite-8b-stage-int8":
               BENCH / "configs" / "granite-8b-stage-int8.json"}


def _model(name: str) -> dict:
    """A configuration's model; ``<name>@<n>`` cut to ``n`` layers."""
    name, _, layers = name.partition("@")
    model = json.loads(CONFIGS[name].read_text())["model"]
    return {**model, "n_layers": int(layers)} if layers else model


def _tree_hash(tree) -> str:
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(x)
        h.update(f"{jax.tree_util.keystr(path)}|{a.dtype}|{a.shape}|"
                 .encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _readings() -> dict:
    """The reference's readings at the recorded positions, keyed like the
    fixture: ``<config>/<seed>/<bits>``."""
    out = {}
    for key in NUMBERS["readings"]:
        name, seed, bits = key.split("/")
        model, seed = _model(name), int(seed)
        bits = None if bits == "None" else int(bits)
        w = dense_gqa.make(model, seed, "float32")
        rng = np.random.default_rng(seed)
        toks = rng.integers(0, model["vocab"], 256)
        picks = rng.integers(0, model["vocab"], (2, 256))
        best, at, top = (np.asarray(x) for x in
                         dense_gqa.readings(w, toks, picks, model, bits=bits))
        pos = NUMBERS["positions"]
        out[key] = {"best": [float(best[p]) for p in pos],
                    "at": [[float(row[p]) for p in pos] for row in at],
                    "top": [int(top[p]) for p in pos]}
    return out


@pytest.fixture(scope="module")
def readings_on_one_core() -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join(
               [str(BENCH.parent), os.environ.get("PYTHONPATH", "")])}
    proc = subprocess.run([sys.executable, __file__], capture_output=True,
                          text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.splitlines()[-1])


@pytest.mark.parametrize("key", sorted(NUMBERS["weights"]))
def test_weights_are_the_recorded_ones(key):
    name, dtype, seed = key.split("/")
    tree = dense_gqa.make(_model(name), int(seed), dtype)
    assert _tree_hash(tree) == NUMBERS["weights"][key]


@pytest.mark.parametrize("key", sorted(NUMBERS["readings"]))
def test_readings_are_the_recorded_ones(readings_on_one_core, key):
    assert readings_on_one_core[key] == NUMBERS["readings"][key]


@pytest.mark.parametrize("name", sorted(NUMBERS["counts"]))
def test_counts_are_the_recorded_ones(name):
    model = _model(name)
    got = {"matmul_params": dense_gqa.matmul_params(model)}
    for rows in (1, 32):
        for cv in (False, True):
            got[f"dense_work/{rows}/{cv}"] = list(
                dense_gqa.dense_work(model, rows, cv))
        got[f"nominal_ops/{rows}"] = dense_gqa.nominal_ops(
            model, rows, rows * 300.5)
    assert got == NUMBERS["counts"][name]


if __name__ == "__main__":
    # before the CPU backend starts: it sizes its thread pool from the
    # cores this process may use
    os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})
    print(json.dumps(_readings()))
