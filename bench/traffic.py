"""The one traffic generator.  A mix is a data file, ``traffic/<mix>.json``.

A mix gives the loop (``open``: requests due at the cell's rate on the
schedule its ``arrivals`` kind draws; ``closed``: a fixed number of
clients, each sending its next request when the previous one finishes),
the prompt and output length distributions, the cap on prompt plus
output, and optional request classes.  Which rate or how many clients is
the cell's, not the mix's.  An arrival kind is a module of its own,
``arrivals/<kind>.py`` (``gaps(levels, rate, params)``), found by name, so
a new schedule is a new file; ``poisson`` is the default.  A mix with a
key, a loop or an arrival kind that the generator does not know is
refused, so that nothing it asks for is silently left out.

Every seed gets the same multiset of sizes and arrival gaps: they are the
distribution's quantiles at levels ``(i + 0.5) / n``, prompt and output
paired alike for every seed, and the seed only permutes them and draws the
prompt tokens.  So two seeds offer the same
work in another order, and a spread between seeds is noise, not a
different load.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

_LOOPS = ("open", "closed")
#: the keys a mix may have; ``arrivals`` and ``arrival_params`` only with
#: an open loop, ``block`` only with a closed one
_KEYS = {"loop", "prompt", "output", "max_total", "classes", "source",
         "assumed", "arrivals", "arrival_params", "block"}
ARRIVALS = Path(__file__).resolve().parent / "arrivals"


@dataclasses.dataclass(frozen=True)
class Job:
    """One request: when it is due (open loop, seconds from the window's
    start; None in a closed loop), its prompt, its output budget, its
    class."""

    due: float | None
    prompt: np.ndarray
    max_new: int
    klass: str


def arrival_kind(name: str):
    """The module of arrival kind ``name`` (``arrivals/<name>.py``)."""
    path = ARRIVALS / f"{name}.py"
    if not path.exists():
        known = sorted(p.stem for p in ARRIVALS.glob("*.py"))
        raise ValueError(f"unknown arrival kind {name!r}; known: {known}")
    spec = importlib.util.spec_from_file_location(f"bench_arrivals_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_mix(root: Path, name: str) -> dict:
    mix = json.loads((root / "traffic" / f"{name}.json").read_text())
    if mix.get("loop") not in _LOOPS:
        raise ValueError(f"traffic {name}: loop must be one of {_LOOPS}")
    other_loop = {"block"} if mix["loop"] == "open" \
        else {"arrivals", "arrival_params"}
    unknown = set(mix) - (_KEYS - other_loop)
    if unknown:
        raise ValueError(f"traffic {name}: the generator does not know "
                         f"{sorted(unknown)} for a {mix['loop']} loop")
    if mix["loop"] == "open":
        kind = arrival_kind(mix.setdefault("arrivals", "poisson"))
        extra = set(mix.get("arrival_params", {})) - set(kind.PARAMS)
        if extra:
            raise ValueError(f"traffic {name}: arrival kind "
                             f"{mix['arrivals']!r} takes no {sorted(extra)}")
    return mix


def _quantiles(dist: dict, levels: np.ndarray) -> np.ndarray:
    """Integer lengths at the given quantile levels, clipped to
    ``[min, max]``."""
    lo, hi = int(dist["min"]), int(dist["max"])
    kind = dist["dist"]
    if kind == "lognormal":
        nd = NormalDist()
        z = np.array([nd.inv_cdf(float(u)) for u in levels])
        vals = np.rint(float(dist["median"]) * np.exp(float(dist["sigma"]) * z))
    elif kind == "uniform":
        vals = lo + np.floor(levels * (hi - lo + 1))
    elif kind == "fixed":
        vals = np.full(levels.shape, float(dist["value"]))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(vals, lo, hi).astype(np.int64)


def _levels(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _classes(mix: dict, n: int, rng: np.random.Generator) -> list[str]:
    """Class labels in their stated shares (largest remainder), shuffled."""
    classes = mix.get("classes") or [{"name": "default", "share": 1.0}]
    counts = [int(math.floor(c["share"] * n)) for c in classes]
    rest = sorted(range(len(classes)),
                  key=lambda i: classes[i]["share"] * n - counts[i],
                  reverse=True)
    for i in rest[: n - sum(counts)]:
        counts[i] += 1
    labels = [c["name"] for c, k in zip(classes, counts) for _ in range(k)]
    rng.shuffle(labels)
    return labels


def generate(mix: dict, n: int, seed, vocab: int,
             rate: float | None = None) -> list[Job]:
    """``n`` jobs of ``mix`` from ``seed``.  An open loop needs ``rate``
    (requests per second); its jobs come back in due order, the first due
    at 0.  A closed loop's jobs are taken in order by whichever client is
    free; its sizes repeat in blocks of ``mix["block"]`` (default 64).
    ``seed`` is an int or a list of ints (NumPy's seed sequence)."""
    rng = np.random.default_rng(seed)
    # sizes come in blocks, each a whole quantile set in the seed's order,
    # so any run that uses a whole number of blocks sees the same sizes
    block = n if mix["loop"] == "open" else int(mix.get("block", 64))
    # prompt and output quantiles are paired by a shuffle that no seed
    # changes; the seed orders the pairs
    prompts = _quantiles(mix["prompt"], _levels(block))
    outputs = _quantiles(mix["output"], _levels(block))
    outputs = outputs[np.random.default_rng(0).permutation(block)]
    nb = -(-n // block)
    order = np.concatenate([rng.permutation(block) for _ in range(nb)])[:n]
    prompts, outputs = prompts[order], outputs[order]
    cap = int(mix["max_total"])
    # the output budget is kept; a prompt that would pass the cap is cut
    prompts = np.minimum(prompts, cap - outputs)
    if np.any(prompts < 1):
        raise ValueError("traffic: an output budget leaves no room for a "
                         "prompt under max_total")
    labels = _classes(mix, n, rng)
    dues: list[float | None] = [None] * n
    if mix["loop"] == "open":
        if not rate or rate <= 0:
            raise ValueError("an open-loop mix needs a positive rate")
        gaps = arrival_kind(mix.get("arrivals", "poisson")).gaps(
            _levels(n), float(rate), mix.get("arrival_params", {}))
        gaps = gaps[rng.permutation(n)]
        dues = [0.0] + np.cumsum(gaps[:-1]).tolist()
    return [Job(due=dues[i],
                prompt=rng.integers(0, vocab, int(prompts[i]),
                                    dtype=np.int32),
                max_new=int(outputs[i]), klass=labels[i])
            for i in range(n)]


def open_loop_count(rate: float, seconds: float) -> int:
    """Requests due in a window of ``seconds`` at ``rate``."""
    return max(1, int(round(rate * seconds)))
