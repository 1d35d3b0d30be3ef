"""The MoE layer's scopes and counters, from the profiler's trace of a run.

A model with MoE layers names three scopes inside its MoE layer's
``mlp_in`` (``repro/nn/moe.py``): ``router`` (logits, softmax, top-k, the
sort and the group sizes), ``experts`` (the gather, the grouped products,
the gate weighting and the combine) and ``shared_expert``.  Its engine
records, after each step's token fetch, a zero-length ``engine.moe`` span
whose arguments ``pairs`` and ``groups_hit`` are the (token, held expert)
pairs the step computed and the (layer, held expert) groups given a row,
summed over the MoE layers.

:func:`record` keeps, for the traced window, under ``run.trace["moe"]``:

* ``scope_ns``: the device time of the step programs' operations by the
  innermost of :data:`SCOPES` in their ``op_name`` (``""``: none), on the
  first device, loops and calls left out (their time is their
  operations');
* ``program_ns``: the device time of the step programs;
* ``pairs`` and ``groups_hit``: the ``engine.moe`` spans' arguments,
  summed over the window; ``steps``: how many there were.

It reads the same ``.xplane.pb`` as ``bench/engine_trace.py``, found the
same way.  A program without these scopes and spans (a dense model, or an
engine from before them) gives zeros and no spans, and the readers then
report nothing.
"""

from __future__ import annotations

import bisect

from bench import engine_trace
from bench import trace as trace_lib

#: the MoE layer's scopes
SCOPES = ("router", "experts", "shared_expert")
_SCOPE_SET = frozenset(SCOPES)
SPAN = "engine.moe"


def scope_of(op_name: str) -> str:
    """The innermost MoE scope in an ``op_name``; ``""`` for none."""
    for part in reversed(op_name.split("/")[:-1]):
        if part in _SCOPE_SET:
            return part
    return ""


def extract(path: str, lo: int, hi: int, pd=None) -> dict:
    """The record (module docstring) of the trace in ``path`` for the
    window ``[lo, hi)`` on the profiler's clock."""
    from jax.profiler import ProfileData

    pd = pd or ProfileData.from_file(path)
    names = engine_trace.op_names(path)
    pairs = groups = steps = 0
    programs: list = []
    device = min((p for p in pd.planes if p.name.startswith("/device:TPU:")),
                 key=lambda p: p.name, default=None)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == SPAN and lo <= int(ev.start_ns) < hi:
                    args = dict(ev.stats)
                    pairs += int(args.get("pairs", 0))
                    groups += int(args.get("groups_hit", 0))
                    steps += 1
    lines = {line.name: line for line in device.lines} if device else {}
    if "XLA Modules" in lines:
        programs = sorted(
            (int(ev.start_ns), int(ev.duration_ns))
            for ev in lines["XLA Modules"].events
            if engine_trace.STEP_PROGRAM.match(ev.name)
            and lo <= int(ev.start_ns) < hi)
    starts = [p[0] for p in programs]
    by: dict[str, int] = {}
    scope: dict[str, str | None] = {}
    ops = lines["XLA Ops"].events if programs and "XLA Ops" in lines else ()
    for ev in ops:
        s = int(ev.start_ns)
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= programs[i][0] + programs[i][1]:
            continue
        name = ev.name
        if name not in scope:
            scope[name] = (
                None if trace_lib.op_kind(name)[1] == trace_lib.CONTAINER
                else scope_of(names.get(name, "")))
        if scope[name] is not None:
            by[scope[name]] = by.get(scope[name], 0) + int(ev.duration_ns)
    return {"scope_ns": by, "program_ns": sum(d for _, d in programs),
            "pairs": pairs, "groups_hit": groups, "steps": steps}


def record(run) -> dict | None:
    """The run's record (found once, then kept under ``run.trace["moe"]``);
    None without a device trace."""
    if not run.trace or not run.trace.get("devices"):
        return None
    if "moe" not in run.trace:
        win = trace_lib.window(run.trace)
        path, pd = engine_trace._find_xplane(win[0]) if win else (None, None)
        run.trace["moe"] = (extract(path, *win, pd=pd) if path else
                            {"scope_ns": {}, "program_ns": 0, "pairs": 0,
                             "groups_hit": 0, "steps": 0})
    return run.trace["moe"]


def share(rec: dict, scopes) -> float | None:
    """Device time of the operations under ``scopes`` over the step
    programs' device time, %; None without a step program or when no
    operation carries any of ``scopes``."""
    by = rec["scope_ns"]
    if rec["program_ns"] <= 0 or not any(s in by for s in scopes):
        return None
    return 100.0 * sum(by.get(s, 0) for s in scopes) / rec["program_ns"]
