"""Both CV kernels compile for a described TPU v5e at olmo-1b's published
widths, and the engine's step updates its KV cache in place there, with no
chip attached (the TPU compiler is installed, the chip is not needed to
compile).

Shapes are the packs the engine serves: prefill-shaped calls (4 slots x a
32-token chunk = 128 rows) run the fan-out-fused Q|K|V and gate|up packs,
decode-shaped calls (4 slots x 1 token) run the member packs, and o/down
run in both.  Blocks are the ones the serving block picker chooses.  The
topology is described inside a fixture, so collecting this file never
loads the TPU library.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import EngineConfig
from repro.kernels import ops
from repro.quant.quantize import (EPI_ROWS, META_LEN, BlockedPack,
                                  serving_blocks)

D_MODEL, D_FF = 2048, 8192  # configs/olmo_1b.py
PREFILL_ROWS, DECODE_ROWS = 4 * 32, 4

CASES = [
    ("qkv", D_MODEL, 3 * D_MODEL, PREFILL_ROWS),
    ("o", D_MODEL, D_MODEL, PREFILL_ROWS),
    ("gateup", D_MODEL, 2 * D_FF, PREFILL_ROWS),
    ("down", D_FF, D_MODEL, PREFILL_ROWS),
    ("q|k|v|o", D_MODEL, D_MODEL, DECODE_ROWS),
    ("gate|up", D_MODEL, D_FF, DECODE_ROWS),
    ("down", D_FF, D_MODEL, DECODE_ROWS),
]
NUMERICS = [("perforated", 2, True), ("truncated", 6, True)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _ids(case):
    return f"{case[0]}-K{case[1]}-N{case[2]}-M{case[3]}"


@pytest.mark.parametrize("numerics", NUMERICS,
                         ids=lambda v: f"{v[0]}{v[1]}")
@pytest.mark.parametrize("case", CASES, ids=_ids)
def test_fused_kernel_compiles_for_v5e(one_chip, case, numerics):
    _, k, n, rows = case
    mode, m, use_cv = numerics
    bn, bk = serving_blocks(k, n)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pack = BlockedPack(
        w_qb=s((k, n), jnp.uint8), epilogue=s((EPI_ROWS, n), jnp.float32),
        meta=s((1, META_LEN), jnp.float32), k=k, n=n, bk=bk, bn=bn)
    step = jax.jit(lambda x, p: ops.quantized_dense_fused_op(
        x, p, mode=mode, m=m, use_cv=use_cv, interpret=False))
    compiled = step.lower(s((rows, k), jnp.bfloat16), pack).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("numerics", NUMERICS,
                         ids=lambda v: f"{v[0]}{v[1]}")
@pytest.mark.parametrize("case", CASES, ids=_ids)
def test_plain_kernel_compiles_for_v5e(one_chip, case, numerics):
    _, k, n, rows = case
    mode, m, use_cv = numerics
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    vec = lambda dt: s((n,), dt)
    step = jax.jit(lambda a, w, c, c0, sqw, b: ops.approx_matmul_cv_op(
        a, w, c, c0, sqw, b, 0.05, 0.01, 128.0, 120.0, mode=mode, m=m,
        use_cv=use_cv, interpret=False))
    compiled = step.lower(s((rows, k), jnp.uint8), s((k, n), jnp.uint8),
                          vec(jnp.float32), vec(jnp.float32),
                          vec(jnp.int32), vec(jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# the engine's step updates the stacked KV cache in place
# ---------------------------------------------------------------------------

#: ops that only name a buffer (or loop over one) and move no data
_PASS = {"parameter", "get-tuple-element", "tuple", "bitcast", "while"}


def _unfused_ops(hlo: str):
    """(opcode, result shape) of each op outside the fused computations."""
    bodies, fused, cur = {}, set(), None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(", line)
        if head:
            cur = head.group(1)
            bodies[cur] = []
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            bodies[cur].append(line)
            called = re.search(r" fusion\(.*calls=%([\w.\-]+)", line)
            if called:
                fused.add(called.group(1))
    for name, lines in bodies.items():
        if name in fused:
            continue
        for line in lines:
            op = re.match(r"^\s*(?:ROOT )?%[\w.\-]+ = \(?\w+\[([\d,]*)\]"
                          r"(?:\{[^}]*\})? ([\w\-]+)\(", line)
            if op:
                yield op.group(2), tuple(int(d) for d in op.group(1).split(",")
                                         if d)


@pytest.mark.parametrize("shape", ["decode", "chunk"])
def test_engine_step_updates_the_cache_in_place_on_v5e(one_chip, shape):
    """Both step programs alias the donated cache, and outside the fused
    computations no op has the stacked cache's or a layer stripe's shape
    but the per-slot column writes into the stack: the layer scan neither
    slices, copies nor restacks the cache, and attention reads each
    stripe inside its own fusion.  At head_dim 128 a decode-shaped step
    whose carried cache were free to change layout copies the stack."""
    from repro.models import build_model
    from repro.serving import ServingEngine

    cfg = dataclasses.replace(get_config("olmo-1b"), n_layers=2, d_model=256,
                              n_heads=2, kv_heads=2, head_dim=128, d_ff=512,
                              vocab=512)
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, EngineConfig(slots=4, max_len=256,
                                                  prefill_chunk=16), api=api)
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    c = 1 if shape == "decode" else 16
    hlo = eng._step_fns[shape].lower(
        on_chip(params), on_chip(jnp.zeros((4, c), jnp.int32)),
        on_chip(eng.pool.cache), on_chip(jnp.zeros((4,), jnp.int32))
    ).compile().as_text()
    head = hlo.splitlines()[0]
    assert head.startswith(f"HloModule jit_engine_{shape}_step")
    assert len(re.findall(r"may-alias", head)) == len(eng.pool.cache)
    stack = eng.pool.cache["k"].shape
    moved = [(op, dims) for op, dims in _unfused_ops(hlo)
             if dims[-3:] == stack[-3:] and op not in _PASS
             and not (op == "dynamic-update-slice" and dims == stack)]
    assert not moved, moved
