"""Operation and byte counts against hand-worked numbers for one olmo-1b
projection (fan-in 2048, fan-out 2048)."""

import json
from pathlib import Path

import pytest

from bench import counts
from bench.families import dense_gqa

BENCH = Path(__file__).resolve().parents[1]
OLMO = json.loads((BENCH / "configs" / "olmo-1b-sd.json").read_text())["model"]
PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]


def test_q_projection_at_decode_rows():
    # 32 rows x 2048 x 2048: 2*32*2048*2048 = 268,435,456 product ops,
    # 32*2048 = 65,536 row sums, 3*32*2048 = 196,608 zero-point ops
    ops, nbytes = counts.layer_work(32, 2048, 2048, cv=False)
    assert ops == 268_435_456 + 65_536 + 196_608
    # 4,194,304 weight codes + 131,072 + 131,072 bf16 in/out + 8,192 sums
    assert nbytes == 4_194_304 + 131_072 + 131_072 + 8_192


def test_q_projection_with_cv():
    ops, nbytes = counts.layer_work(32, 2048, 2048, cv=True)
    # the CV adds 32*2048 row statistics and 2*32*2048 column corrections
    assert ops == 268_435_456 + 65_536 + 196_608 + 65_536 + 131_072
    assert nbytes == 4_194_304 + 131_072 + 131_072 + 8_192 + 16_384


def test_dense_work_sums_every_projection_of_every_layer():
    ops, nbytes = dense_gqa.dense_work(OLMO, 1, cv=False)
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    assert nbytes > OLMO["n_layers"] * per_layer  # every weight read once
    assert ops == pytest.approx(2 * OLMO["n_layers"] * per_layer, rel=1e-3)


@pytest.mark.parametrize("rows,bound", [(32, "memory"), (1024, "compute")])
def test_roofline_bound(rows, bound):
    t, which = counts.least_time(*dense_gqa.dense_work(OLMO, rows, cv=True),
                                 PEAKS["int8_ops_per_s"],
                                 PEAKS["hbm_bytes_per_s"])
    assert which == bound and t > 0


def test_nominal_ops():
    # olmo-1b: 1,073,741,824 projection parameters + 103,022,592 in the head
    assert dense_gqa.matmul_params(OLMO) == 1_073_741_824 + 2048 * 50304
    got = dense_gqa.nominal_ops(OLMO, tokens=2, context_sum=10)
    attn = 4 * 16 * 16 * 128 * 10
    assert got == 2 * dense_gqa.matmul_params(OLMO) * 2 + attn
