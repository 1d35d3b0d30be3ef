"""Roofline-term extraction from compiled (SPMD-partitioned) artifacts.

Hardware model: TPU v5e-class chip — 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.  The compiled module is the PER-DEVICE program (XLA SPMD
partitions before optimization), so `cost_analysis()` flops/bytes and the
collective shapes parsed from the optimized HLO are already per-chip:

    compute    = flops / 197e12            seconds
    memory     = bytes_accessed / 819e9    seconds
    collective = collective_bytes / 50e9   seconds

collective_bytes sums, over every all-reduce / all-gather / reduce-scatter /
all-to-all / collective-permute in the optimized HLO, the larger of the op's
result vs summed-operand bytes (a per-device lower bound on wire traffic; we
report the breakdown per op kind so schedule changes are attributable).
"""

from __future__ import annotations

import dataclasses
import re

PEAK_FLOPS = 197e12  # bf16 per chip
HBM_BW = 819e9  # bytes/s
ICI_BW = 50e9  # bytes/s/link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_COLLECTIVES = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(txt: str) -> int:
    """Sum bytes of every 'dtype[dims]' shape literal in ``txt``."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(txt):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: dict
    count_by_kind: dict

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Scan optimized HLO for collective ops; bytes = max(result, operands)."""
    bytes_by: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    count_by: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(.+?)\s+([\w\-]+)\(", s)
        if not m:
            continue
        result_shapes, opname = m.groups()
        kind = None
        for k in _COLLECTIVES:
            if opname == k or opname.startswith(k + "-") or opname.startswith(k + "."):
                kind = k
                break
        if kind is None:
            continue
        res_bytes = _shape_bytes(result_shapes)
        # operand shapes appear in the argument list; HLO text usually lists
        # operand names only, so result bytes are our proxy (exact for
        # all-reduce/permute; result >= wire for all-gather; <= for rs).
        bytes_by[kind] += res_bytes
        count_by[kind] += 1
    return CollectiveStats(bytes_by, count_by)


@dataclasses.dataclass
class RooflineTerms:
    flops: float
    bytes_accessed: float
    collective_bytes: float
    collectives: dict
    collective_counts: dict
    raw_cost: dict | None = None

    @property
    def compute_s(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.bytes_accessed / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / ICI_BW

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    def as_dict(self) -> dict:
        return {
            "flops_per_chip": self.flops,
            "bytes_per_chip": self.bytes_accessed,
            "collective_bytes_per_chip": self.collective_bytes,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "collectives": self.collectives,
            "collective_counts": self.collective_counts,
            "raw_cost_analysis": self.raw_cost,
        }


def roofline_from_compiled(compiled) -> RooflineTerms:
    """Trip-count-aware analysis of the optimized per-device HLO.

    `compiled.cost_analysis()` counts while-loop (lax.scan) bodies once —
    useless for scanned layer stacks — so terms come from
    launch.hlo_analysis, which multiplies bodies by inferred trip counts.
    The raw cost_analysis numbers are kept in `raw_cost` for comparison.
    """
    from repro.launch.hlo_analysis import analyze_hlo

    cost = compiled.cost_analysis()
    raw_flops = float(cost.get("flops", 0.0))
    raw_bytes = float(cost.get("bytes accessed", cost.get("bytes_accessed", 0.0)))

    hc = analyze_hlo(compiled.as_text())
    terms = RooflineTerms(
        flops=hc.flops,
        bytes_accessed=hc.bytes_accessed,
        collective_bytes=hc.collective_bytes,
        collectives=hc.collectives,
        collective_counts=hc.collective_counts,
    )
    terms.raw_cost = {"flops": raw_flops, "bytes_accessed": raw_bytes,
                      "unknown_trip_counts": hc.unknown_trip_counts}
    return terms


def model_flops_estimate(cfg, shape_kind: str, seq_len: int, batch: int) -> float:
    """MODEL_FLOPS: 6*N*D for training (N = active params), 2*N*D per
    generated/processed token for serving, GLOBAL (divide by chips to compare
    with per-chip HLO flops)."""
    n_active = cfg.active_param_count()
    if shape_kind == "train":
        return 6.0 * n_active * seq_len * batch
    if shape_kind == "prefill":
        return 2.0 * n_active * seq_len * batch
    return 2.0 * n_active * batch  # decode: one token per sequence
