#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print one result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``workloads/<cell>.json``) names a configuration
(``configs/<config>.json``: the model and its family, the numerics pack it
is served in, the engine's settings), a traffic mix
(``traffic/<mix>.json``), the chips, and the fixed rate (open loop) or
number of clients (closed loop).  The run:

1. checks the devices: a TPU whose ``device_kind`` is in ``peaks.json``,
   as many chips as the cell asks for; otherwise it exits non-zero and
   prints no result;
2. sets up: seeded weights made on the device by the configuration's
   family (``families/<family>.py``) and checked against the program's
   parameter tree, packed by the program's ``build_serving_params``, the
   float weights freed, the serving engine built (its KV cache
   allocated), its two step shapes and its token pick warmed up on a few
   requests;
3. drives ``ServingEngine.step()`` for ``--seconds``, feeding requests
   through ``submit`` as the mix says, and records on the host clock when
   each was due, submitted, given a slot, and each of its tokens; an open
   loop then goes on until every request due in the window has its first
   token, so that no time to first token is cut at the close;
4. with ``--trace 1`` records a JAX profiler trace of the window's last
   ``TRACE_S`` seconds and reports the cell's per-layer metrics
   (``metrics/<metric>.py``) in place of its end-to-end ones;
5. checks what the window served: the engine state is freed, the weights
   are made again from the seed, and the family's plain float32
   reference runs over a seeded sample of the finished requests,
   teacher-forced on the tokens they were served, reading at each served
   position the gap by which the served token's reference logit lies
   below the reference's best.  The numbers compared (the cell's
   ``limits``) are the pooled mean of those gaps (``served_gap_mean``),
   which a uniformly worse numerics moves, and the largest of the
   sampled requests' mean gaps (``worst_request_gap``), which one wrong
   request moves: each sampled request is also judged on its own.

``--control`` puts the configuration's ``control`` in the program's place
and ``correct`` has to come out false: either the program's own
lower-precision pack, served in place of its ``numerics``
(``{"numerics": ..., "policy": ...}``), or the reference at a lower
precision (``{"reference_bits": 4}``), read at the same prompts and
served tokens, whose first choices are judged in place of the served
tokens.

The last line of standard output is one JSON object; the numbers compared
are printed beside their limits as the last lines of standard error and
under ``checks``, the last key of that line.  Which metrics a cell reports
is read from ``BENCHMARK.json``.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import ModuleType  # noqa: E402

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
for _p in (CHECKOUT / "src", CHECKOUT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import numpy as np  # noqa: E402

from bench import trace as trace_lib  # noqa: E402
from bench import traffic  # noqa: E402
from bench.stats import percentile  # noqa: E402
from bench.weights import is_shape  # noqa: E402

#: requests checked against the reference after the window
SAMPLE_N = 8
#: an open loop keeps offering its traffic after the window closes until
#: every request due in the window has its first token, at most this long
DRAIN_S = 30.0
#: a traced run traces the last this many seconds of the window: the
#: profiler keeps a bounded number of device events, and a 51 s trace of a
#: chat cell lost its first 9 s of them, which then read as idle
TRACE_S = 20.0


class NoChip(RuntimeError):
    """The devices cannot run the cell: no result is printed."""


# ---------------------------------------------------------------------------
# the files a cell is made of
# ---------------------------------------------------------------------------


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    name: str
    spec: dict  # workloads/<cell>.json
    config: dict  # configs/<config>.json
    mix: dict  # traffic/<mix>.json
    benchmark: dict  # BENCHMARK.json
    family: ModuleType  # families/<family>.py

    @property
    def model(self) -> dict:
        return self.config["model"]

    @property
    def engine(self) -> dict:
        return self.config["engine"]


def load_cell(name: str, root: Path = BENCH,
              benchmark: Path | None = None) -> Cell:
    spec = load_json(root / "workloads" / f"{name}.json")
    config = load_json(root / "configs" / f"{spec['config']}.json")
    return Cell(name=name, spec=spec, config=config,
                mix=traffic.load_mix(root, spec["traffic"]),
                benchmark=load_json(benchmark or CHECKOUT / "BENCHMARK.json"),
                family=load_family(root, spec["config"], config))


#: a family's name: a module's name under ``families/``, no path
_FAMILY_NAME = re.compile(r"[A-Za-z_][A-Za-z0-9_]{0,63}")


def load_family(root: Path, config_name: str, config: dict) -> ModuleType:
    """The module of the family the configuration names,
    ``families/<family>.py`` under ``root``, else under the benchmark's own
    directory.  A configuration that names none, or one that does not
    exist, is refused."""
    family = config.get("family")
    if family is None:
        raise ValueError(f"configuration {config_name!r} names no family "
                         f"(\"family\": a module under families/)")
    if not isinstance(family, str) or not _FAMILY_NAME.fullmatch(family):
        raise ValueError(f"configuration {config_name!r}: {family!r} is not "
                         f"a family's name")
    path = find_file(root, "families", family)
    if not path.exists():
        raise ValueError(f"configuration {config_name!r} names the family "
                         f"{family!r}, and there is no families/{family}.py "
                         f"under {root} or {BENCH}")
    return exec_file(path, f"bench_family_{family}")


def cell_metrics(cell: Cell, kind: str) -> list[dict]:
    """The cell's ``end_to_end`` or ``per_layer`` metrics: those without a
    ``workloads`` key, and those that list the cell."""
    return [m for m in cell.benchmark[kind]
            if "workloads" not in m or cell.name in m["workloads"]]


def find_file(root: Path, kind: str, name: str) -> Path:
    """``<kind>/<name>.py`` under ``root`` where it is there, else under
    the benchmark's own directory."""
    path = root / kind / f"{name}.py"
    return path if path.exists() else BENCH / kind / f"{name}.py"


def exec_file(path: Path, module_name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: Path, metric: str):
    """The ``read(run)`` function of ``metrics/<metric>.py`` under ``root``,
    else under the benchmark's own directory."""
    return exec_file(find_file(root, "metrics", metric),
                     f"bench_metric_{metric.replace('.', '_')}").read


# ---------------------------------------------------------------------------
# devices
# ---------------------------------------------------------------------------


def check_devices(chips: int, require_tpu: bool = True) -> tuple[list, dict]:
    """The devices to use and the peaks of their kind."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    peaks_all = load_json(BENCH / "peaks.json")["devices"]
    if not require_tpu:
        return devices[:chips], next(iter(peaks_all.values()))
    if dev.platform != "tpu":
        raise NoChip(f"no TPU: JAX found {dev.platform!r} devices")
    if dev.device_kind not in peaks_all:
        raise NoChip(f"device kind {dev.device_kind!r} is not in peaks.json")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips], peaks_all[dev.device_kind]


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


def arch_config(cell: Cell):
    """The program's ArchConfig for the cell: its registry entry with the
    configuration file's overrides, checked against the file's numbers."""
    from repro.configs import get_config

    cfg = dataclasses.replace(get_config(cell.config["arch"]),
                              **cell.config.get("overrides", {}))
    wrong = {k: (getattr(cfg, k), cell.model[k])
             for k in cell.family.MODEL_KEYS
             if getattr(cfg, k) != cell.model[k]}
    if wrong:
        raise ValueError(f"{cell.config['arch']}: the program's config "
                         f"differs from the configuration file: {wrong}")
    return cfg


def check_layout(api, cell: Cell, dtype: str) -> None:
    """The family's seeded tree must have the program's parameter
    shapes."""
    import jax

    want = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    have = cell.family.shapes(cell.model)
    flat_w = {jax.tree_util.keystr(p): (tuple(x.shape), str(x.dtype))
              for p, x in jax.tree_util.tree_flatten_with_path(want)[0]}
    flat_h = {jax.tree_util.keystr(p): (tuple(s), dtype)
              for p, s in jax.tree_util.tree_flatten_with_path(
                  have, is_leaf=is_shape)[0]}
    if flat_w != flat_h:
        diff = sorted(set(flat_w.items()) ^ set(flat_h.items()))[:6]
        raise ValueError(f"seeded weights do not match the program's "
                         f"parameter tree: {diff}")


@dataclasses.dataclass
class Served:
    """What set-up built: the engine and what the window needs of it."""

    engine: object
    cfg: object
    spec_name: str
    cv: bool
    timings: dict


def warm_up(engine, ecfg: dict, vocab: int) -> None:
    """Serve a few requests so that both step shapes (chunk-shaped with
    only prompt rows and with mixed rows, decode-shaped) and the token
    pick of each are compiled, then clear the counters."""
    ch = ecfg["prefill_chunk"]
    rng = np.random.default_rng(0)
    a = engine.submit(rng.integers(0, vocab, ch + 1).tolist(), 6)
    engine.step()  # a: first chunk (prefill only)
    b = engine.submit(rng.integers(0, vocab, 2 * ch).tolist(), 3)
    engine.run()  # mixed chunk calls, then decode-shaped calls
    for r in (a, b):
        if not r.finished:
            raise RuntimeError(f"warm-up request {r.rid} ended {r.state}")
    engine.reset_metrics()


def numerics_spec(cell: Cell, control: bool = False):
    """The pack the cell serves: the configuration's ``numerics`` preset,
    or with ``control`` its ``control`` pack (a preset and an optional
    policy for it)."""
    from repro.core.policy import ApproxPolicy
    from repro.numerics import get_preset

    if not control:
        return get_preset(cell.config["numerics"])
    ctl = cell.config["control"]
    kw = {"policy": ApproxPolicy.from_dict(ctl["policy"])} \
        if "policy" in ctl else {}
    return get_preset(ctl["numerics"], **kw)


def set_up(cell: Cell, seed: int, control: bool = False) -> Served:
    import jax

    from repro.configs.base import EngineConfig
    from repro.launch.serve import ServeConfig, build_serving_params
    from repro.models import build_model
    from repro.serving import ServingEngine

    t = [time.perf_counter()]

    def lap() -> float:
        t.append(time.perf_counter())
        return t[-1] - t[-2]

    cfg = arch_config(cell)
    api = build_model(cfg)
    check_layout(api, cell, cfg.param_dtype)
    w = jax.block_until_ready(
        cell.family.make(cell.model, seed, cfg.param_dtype))
    timings = {"weights_s": lap()}
    spec = numerics_spec(cell, control)
    served = jax.block_until_ready(
        build_serving_params(w, cfg, ServeConfig(spec=spec)))
    del w
    gc.collect()
    timings["pack_s"] = lap()
    engine = ServingEngine(cfg, served, EngineConfig(**cell.engine), api=api,
                           numerics=spec.name)
    timings["engine_s"] = lap()
    warm_up(engine, cell.engine, cell.model["vocab"])
    timings["warm_up_s"] = lap()
    default = spec.default
    cv = bool(getattr(default, "use_cv", False)) and getattr(
        default, "mode", "exact") != "exact"
    return Served(engine=engine, cfg=cfg, spec_name=spec.name, cv=cv,
                  timings=timings)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


@dataclasses.dataclass(eq=False)
class Rec:
    """One request as the benchmark saw it (times on ``perf_counter``)."""

    job: traffic.Job
    due: float
    t_submit: float | None = None
    req: object = None
    tokens: list = dataclasses.field(default_factory=list)
    admitted_at: float | None = None
    client: int | None = None
    rejected: bool = False

    @property
    def prompt_len(self) -> int:
        return len(self.job.prompt)


@dataclasses.dataclass
class Step:
    """One engine step that ran a batch."""

    t0: float
    t1: float
    kind: str  # "decode" (slots x 1) or "chunk" (slots x prefill_chunk)
    prompt_rows: int  # prompt tokens processed
    decode_rows: int  # decode tokens processed
    context_sum: float  # live context positions over processed tokens
    traced: bool


class _Annot:
    """``jax.profiler.TraceAnnotation`` while tracing, nothing otherwise."""

    on = False

    def __call__(self, name: str):
        if self.on:
            import jax

            return jax.profiler.TraceAnnotation(name)
        return _NULL


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


@dataclasses.dataclass
class Window:
    t_lead: float  # the first request due: set-up ends here
    t0: float
    t1: float  # the window's close
    t_drained: float  # when the drain after it ended
    recs: list  # every request due in the window
    all_recs: list  # and those of the lead-in before it
    steps: list  # the steps that started in the window
    compiles: int  # compilations inside the window
    gc_pauses: list = dataclasses.field(default_factory=list)  # seconds


def drive(served: Served, cell: Cell, jobs: list, seconds: float,
          trace_dir: str | None, lead_in: float = 0.0) -> Window:
    """Drive the engine for ``lead_in`` seconds (set-up: the queue and the
    slots fill as the traffic fills them), then for ``seconds``: the
    measured window.  An open loop then drains: the traffic goes on until
    every request due in the window has its first token (``DRAIN_S`` at
    most), so that a time to first token is measured whole and not cut at
    the close.  A traced run traces the last ``TRACE_S`` seconds of the
    window alone."""
    import jax

    from repro.serving.request import RequestState

    eng = served.engine
    now = time.perf_counter
    annot = _Annot()
    emitted: list = []  # (rec, token index) of the current step
    waiting: list[Rec] = []  # submitted, no slot yet
    running: list[Rec] = []  # holding a slot

    def submit(rec: Rec) -> None:
        def on_token(_req, _tok, rec=rec):
            with annot("bench.on_token"):
                rec.tokens.append(now())
                emitted.append((rec, len(rec.tokens) - 1))

        with annot("bench.submit"):
            rec.req = eng.submit(rec.job.prompt.tolist(), rec.job.max_new,
                                 on_token=on_token)
            rec.t_submit = now()
        if rec.req.state == RequestState.REJECTED:
            rec.rejected = True
        else:
            waiting.append(rec)

    compiles = [0]

    def on_event(event: str, _dur: float, **_kw) -> None:
        if "backend_compile" in event or "jaxpr_trace" in event:
            compiles[0] += 1

    # Python's garbage collections inside the window, for the earlier line
    gc_pauses: list[float] = []
    gc_start = [0.0]

    def on_gc(phase: str, _info: dict) -> None:
        if phase == "start":
            gc_start[0] = now()
        elif in_window and t_close is None:
            gc_pauses.append(now() - gc_start[0])

    closed = cell.mix["loop"] == "closed"
    recs: list[Rec] = []
    steps: list[Step] = []
    m = eng.metrics
    win_span = _NULL  # the traced part of the window
    t_lead = now()
    t0 = t_lead + lead_in
    t_end = t0 + seconds
    # the requests due in the window, by the schedule: the window opens at
    # the first step boundary after its planned start
    due_lo, due_hi = t0, t_end
    in_window = False
    t_close = None  # when the window closed; the drain follows
    job_iter = iter(jobs)
    if closed:
        for c in range(int(cell.spec["clients"])):
            rec = Rec(job=next(job_iter), due=t_lead, client=c)
            recs.append(rec)
            submit(rec)
    else:
        recs = [Rec(job=j, due=t_lead + j.due) for j in jobs]
    nxt = 0  # next open-loop request to submit

    def close_window() -> None:
        jax.monitoring.unregister_event_duration_listener(on_event)
        if annot.on:
            win_span.__exit__(None, None, None)
            annot.on = False
            jax.profiler.stop_trace()

    def drained() -> bool:
        return all(r.tokens or r.rejected for r in recs
                   if due_lo <= r.due < due_hi)

    gc.callbacks.append(on_gc)
    try:
        while True:
            t = now()
            if in_window and t_close is None and t >= t_end:
                t_close = t
                close_window()
            if t_close is not None and (closed or drained()
                                        or t >= t_close + DRAIN_S):
                break
            if not in_window and t >= t0:
                # the window opens: count compilations from here
                in_window = True
                jax.monitoring.register_event_duration_secs_listener(
                    on_event)
                t0 = now()
                t_end = t0 + seconds
            if (trace_dir is not None and in_window and t_close is None
                    and not annot.on and now() >= t_end - TRACE_S):
                jax.profiler.start_trace(trace_dir)
                annot.on = True
                win_span = annot("bench.window")
                win_span.__enter__()
            if not closed:
                while nxt < len(recs) and recs[nxt].due <= t:
                    submit(recs[nxt])
                    nxt += 1
            if eng.idle:
                if t_close is not None:
                    break
                due = recs[nxt].due if not closed and nxt < len(recs) \
                    else t_end
                due = min(due, t_end if in_window else t0)
                with annot("bench.wait"):
                    time.sleep(max(0.0, due - now()))
                continue
            before = [(r, r.req.prefilled) for r in running]
            n0 = m.prefill_steps + m.mixed_steps + m.decode_steps
            d0 = m.decode_steps
            emitted.clear()
            ta = now()
            with annot("bench.step"):
                finished = eng.step()
            tb = now()
            # a request that got a slot in this step started prefilling in it
            still = []
            for r in waiting:
                if r.req.slot is not None:
                    r.admitted_at = ta
                    before.append((r, 0))
                    running.append(r)
                else:
                    still.append(r)
            waiting[:] = still
            if in_window and t_close is None and (
                    m.prefill_steps + m.mixed_steps + m.decode_steps > n0):
                prompt_rows, ctx = 0, 0.0
                for r, a in before:
                    b = r.req.prefilled
                    prompt_rows += b - a
                    ctx += (b * (b + 1) - a * (a + 1)) / 2
                decode_rows = 0
                for r, j in emitted:
                    if j >= 1:  # token j came from processing token j - 1
                        decode_rows += 1
                        ctx += r.prompt_len + j
                steps.append(Step(ta, tb, "decode" if m.decode_steps > d0
                                  else "chunk", prompt_rows, decode_rows,
                                  ctx, annot.on))
            if finished:
                done = {id(f) for f in finished}
                for r in [r for r in running if id(r.req) in done]:
                    running.remove(r)
                    if closed and t_close is None:
                        nr = Rec(job=next(job_iter), due=tb, client=r.client)
                        recs.append(nr)
                        submit(nr)
    finally:
        t_last = now()
        gc.callbacks.remove(on_gc)
        if in_window and t_close is None:
            t_close = t_last
            close_window()
    window_recs = [r for r in recs if due_lo <= r.due < due_hi]
    return Window(t_lead=t_lead, t0=t0, t1=min(t_close, t_end),
                  t_drained=t_last, recs=window_recs, all_recs=recs,
                  steps=steps, compiles=compiles[0], gc_pauses=gc_pauses)


# ---------------------------------------------------------------------------
# what a run reports
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""

    cell: Cell
    window: Window
    setup_s: float
    peaks: dict
    cv: bool
    trace: dict | None = None

    @property
    def model(self) -> dict:
        return self.cell.model

    @property
    def family(self) -> ModuleType:
        return self.cell.family

    @property
    def slots(self) -> int:
        return self.cell.engine["slots"]

    @property
    def chunk(self) -> int:
        return self.cell.engine["prefill_chunk"]

    @property
    def recs(self) -> list:
        return self.window.recs

    def ttfts(self) -> list[float]:
        """Seconds from due to first token of every request due in the
        window, its first token waited for through the drain; one still
        without a token after the drain counts as due-to-drain-end."""
        end = self.window.t_drained
        return [(r.tokens[0] if r.tokens else end) - r.due
                for r in self.recs]

    def _window_tokens(self, r: Rec) -> list[float]:
        return [t for t in r.tokens if self.window.t0 <= t <= self.window.t1]

    def itls(self) -> list[float]:
        """Every gap between consecutive tokens inside the window, of every
        request (those of the lead-in too)."""
        gaps = []
        for r in self.window.all_recs:
            ts = self._window_tokens(r)
            gaps.extend(b - a for a, b in zip(ts, ts[1:]))
        return gaps

    def generated_in_window(self) -> int:
        """Tokens generated inside the window, of every request."""
        return sum(len(self._window_tokens(r)) for r in self.window.all_recs)

    def traced_steps(self) -> list[tuple[Step, tuple[int, int], int]]:
        """Each traced step with the device interval of its step program on
        the profiler's clock and that program's device time; [] without a
        device trace."""
        if not self.trace or not self.trace["devices"]:
            return []
        steps = [s for s in self.window.steps if s.traced]
        progs = trace_lib.step_programs(self.trace, [s.kind for s in steps])
        return [(s, (a, a + d), d) for s, (a, d) in zip(steps, progs)]

    def rows(self, step: Step) -> int:
        """Activation rows a step computes (padding rows included)."""
        return self.slots * (1 if step.kind == "decode" else self.chunk)


def e2e_value(run: Run, name: str) -> float | None:
    """The harness's end-to-end metrics."""
    if name == "setup_s":
        return run.setup_s
    if name == "ttft_p90_ms":
        v = percentile(run.ttfts(), 90)
        return None if v is None else v * 1e3
    if name == "itl_p95_ms":
        v = percentile(run.itls(), 95)
        return None if v is None else v * 1e3
    if name == "gen_tok_per_s":
        return run.generated_in_window() / (run.window.t1 - run.window.t0)
    raise KeyError(f"no end-to-end metric {name!r} in the harness")


# ---------------------------------------------------------------------------
# the comparison with the reference
# ---------------------------------------------------------------------------


def sample(recs: list, seed: int) -> list:
    """A seeded sample of ``SAMPLE_N`` finished requests (all of them when
    fewer finished), the longest among them."""
    done = [r for r in recs if r.req is not None and r.req.finished
            and r.req.generated]
    if not done:
        return []
    done.sort(key=lambda r: -(r.prompt_len + len(r.req.generated)))
    rest = done[1:]
    order = np.random.default_rng(seed).permutation(len(rest))
    return [done[0]] + [rest[i] for i in order[: SAMPLE_N - 1]]


def compare(cell: Cell, seed: int, picked: list, param_dtype: str,
            bits: int | None = None) -> dict:
    """Teacher-force the family's reference over each sampled request's
    prompt and served tokens, reading at each served position the gap by
    which the served token's logit lies below the reference's best.
    Returns the pooled mean gap, the largest of the requests' mean gaps,
    each request's mean gap, the widest gap, and the share of served
    tokens that are the reference's first choice.  ``bits`` puts the
    reference at that many bits in the program's place: at the same
    positions the gap read is that of the token the lower precision ranks
    first."""
    import jax

    model, family = cell.model, cell.family
    w = jax.block_until_ready(family.make(model, seed, param_dtype))
    width = cell.engine["max_len"]
    gaps, agree = [], 0
    for r in picked:
        prompt = [int(t) for t in r.job.prompt]
        gen = list(r.req.generated)
        seq = np.zeros(width, np.int32)
        fed = prompt + gen[:-1]
        seq[: len(fed)] = fed
        pos = np.arange(len(prompt) - 1, len(fed))  # predicts gen[i]
        judged = np.zeros(width, np.int32)
        judged[pos] = gen
        if bits:
            judged = np.asarray(family.readings(
                w, seq, judged[None], model, bits=bits)[2])
        best, at, top = (np.asarray(x) for x in
                         family.readings(w, seq, judged[None], model))
        gaps.append(best[pos] - at[0][pos])
        agree += int(np.sum(top[pos] == judged[pos]))
    del w
    every = np.concatenate(gaps)
    req_means = [float(g.mean()) for g in gaps]
    return {"served_gap_mean": float(every.mean()),
            "worst_request_gap": max(req_means),
            "request_gaps": req_means,
            "served_gap_max": float(every.max()),
            "served_tokens": int(every.size), "requests": len(picked),
            "top1_agreement": agree / every.size}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def peak_bytes(devices) -> int | None:
    vals = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]
    vals = [v for v in vals if v is not None]
    return max(vals) if vals else None


def make_jobs(cell: Cell, seed: int, seconds: float,
              rate: float | None = None) -> list:
    """The cell's requests for its lead-in and a window of ``seconds``."""
    vocab = cell.model["vocab"]
    if cell.mix["loop"] == "closed":
        n = max(512, 16 * int(cell.spec["clients"]))
        return traffic.generate(cell.mix, n, seed, vocab)
    # the lead-in and the window are scheduled apart, so that the window
    # holds the same number and sizes of requests for every seed
    rate = rate or float(cell.spec["rate_per_s"])
    lead = float(cell.spec.get("lead_in_s", 0.0))
    jobs = []
    if lead > 0:
        jobs = traffic.generate(cell.mix, traffic.open_loop_count(rate, lead),
                                [seed, 0], vocab, rate=rate)
    window = traffic.generate(cell.mix, traffic.open_loop_count(
        rate, seconds), [seed, 1], vocab, rate=rate)
    jobs += [dataclasses.replace(j, due=lead + j.due) for j in window]
    # the traffic the drain offers after the window's close
    tail = traffic.generate(cell.mix, traffic.open_loop_count(rate, DRAIN_S),
                            [seed, 2], vocab, rate=rate)
    end = lead + seconds
    return jobs + [dataclasses.replace(j, due=end + j.due) for j in tail]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             root: Path = BENCH, require_tpu: bool = True,
             control: bool = False, t_start: float = T_PROCESS) -> dict:
    """Run the cell once; returns the result line as a dict (and the
    comparison's extra readings under ``_extra``).  ``control`` puts the
    configuration's control in the program's place (see the module's
    docstring)."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices, peaks = check_devices(int(cell.spec["chips"]), require_tpu)
    cache_dir = enable_compile_cache()
    ctl = cell.config["control"] if control else {}
    served = set_up(cell, seed, control and "numerics" in ctl)
    jobs = make_jobs(cell, seed, seconds)
    step_shapes = served.engine.compile_count()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    win = drive(served, cell, jobs, seconds, trace_dir,
                lead_in=float(cell.spec.get("lead_in_s", 0.0)))
    setup_s = win.t_lead - t_start
    shapes_after = served.engine.compile_count()
    mem = peak_bytes(devices)
    run = Run(cell=cell, window=win, setup_s=setup_s, peaks=peaks,
              cv=served.cv)
    t_trace = time.perf_counter()
    if trace:
        run.trace = trace_lib.extract(trace_dir)
    t_trace = time.perf_counter() - t_trace
    finished = sum(1 for r in win.all_recs if r.req is not None
                   and r.req.finished)
    failed = sum(1 for r in win.recs if r.rejected)
    attempted = sum(1 for r in win.recs if r.t_submit is not None)
    picked = sample(win.all_recs, seed)
    param_dtype = served.cfg.param_dtype
    if trace:
        metrics = {}
        for m in cell_metrics(cell, "per_layer"):
            v = load_reader(root, m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": e2e_value(run, m["name"]),
                               "unit": m["unit"]}
                   for m in cell_metrics(cell, "end_to_end")}
        metrics = {k: v for k, v in metrics.items() if v["value"] is not None}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    breakdown = None
    if trace and run.trace and run.trace["devices"]:
        busy_s, window_s, _ = trace_lib.idle_share(run.trace)
        device["busy_s"] = busy_s
        device["window_s"] = window_s
        breakdown = {"device_ops": trace_lib.top_ops(run.trace),
                     "idle_gaps": trace_lib.longest_gaps(run.trace)}
    timings = served.timings
    # free the program's state before the reference runs
    served.engine = None
    del served
    gc.collect()
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
    t_ref = time.perf_counter()
    cmp = compare(cell, seed, picked, param_dtype,
                  ctl.get("reference_bits")) \
        if picked else {}
    t_ref = time.perf_counter() - t_ref
    checks = {name: {"value": cmp.get(name), "limit": float(limit)}
              for name, limit in cell.spec["limits"].items()}
    checks["compiles_in_window"] = {"value": win.compiles, "limit": 0}
    checks["requests_finished"] = {"value": finished, "limit": 1}
    correct = (all(c["value"] is not None and c["value"] <= c["limit"]
                   for n, c in checks.items() if n in cell.spec["limits"])
               and win.compiles == 0 and shapes_after == step_shapes
               and finished >= 1)
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    result["_extra"] = {
        "compile_cache": cache_dir, "step_shapes": shapes_after,
        "finished": finished, "steps": len(win.steps),
        "decode_steps": sum(s.kind == "decode" for s in win.steps),
        "longest_step_s": max((s.t1 - s.t0 for s in win.steps), default=0.0),
        "gc_pauses": len(win.gc_pauses),
        "gc_longest_s": max(win.gc_pauses, default=0.0),
        "drain_s": win.t_drained - win.t1, "lead_in_s": win.t0 - win.t_lead,
        "reference_s": t_ref, "trace_read_s": t_trace,
        "jax": jax.__version__, **timings, **cmp}
    return result


def report(result: dict) -> None:
    """The earlier lines, the checks on standard error, and the result
    line last on standard output."""
    ex = result.pop("_extra")
    dev = result["device"]
    print(f"run: platform={dev['platform']} device_kind={dev['kind']} "
          f"count={dev['count']}; requests sent {result['attempted']}, "
          f"succeeded {ex['finished']}, failed {result['failed']}; "
          f"peak device memory {dev['memory_peak_bytes']} bytes; compiled "
          f"step shapes {ex['step_shapes']}, compilations in the window "
          f"{result['checks']['compiles_in_window']['value']}; steps "
          f"{ex['steps']} ({ex['decode_steps']} decode-shaped), the longest "
          f"{ex['longest_step_s']:.3f} s; garbage collections in the window "
          f"{ex['gc_pauses']}, the longest {ex['gc_longest_s']:.3f} s; "
          f"drain after the window {ex['drain_s']:.3f} s", flush=True)
    print("served tokens against the float32 reference: "
          + ", ".join(f"{k} {ex[k]}" for k in
                      ("served_gap_mean", "worst_request_gap",
                       "request_gaps", "served_gap_max", "top1_agreement",
                       "served_tokens", "requests", "reference_s")
                      if k in ex), flush=True)
    print("host clock, s: " + ", ".join(
        f"{k} {ex[k]:.3f}" for k in ("weights_s", "pack_s", "engine_s",
                                     "warm_up_s", "lead_in_s", "trace_read_s",
                                     "reference_s") if k in ex), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="put the configuration's control in the "
                         "program's place: correct has to come out false")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          control=args.control)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
