"""Both CV kernels compile for a described TPU v5e at olmo-1b's published
widths, with no chip attached (the TPU compiler is installed, the chip is
not needed to compile).

Shapes are the packs the engine serves: prefill-shaped calls (4 slots x a
32-token chunk = 128 rows) run the fan-out-fused Q|K|V and gate|up packs,
decode-shaped calls (4 slots x 1 token) run the member packs, and o/down
run in both.  Blocks are the ones the serving block picker chooses.  The
topology is described inside a fixture, so collecting this file never
loads the TPU library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

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
