"""The engine's own spans and the step's named scopes, from the profiler's
trace of a run.

The serving engine opens a ``jax.profiler`` annotation per phase of each
step (``engine.step`` and its children ``engine.schedule``,
``engine.dispatch``, ``engine.fetch``, ``engine.emit``, ``engine.account``;
``repro/serving/telemetry.py``), compiles one step program per shape
(``jit_engine_decode_step``, ``jit_engine_chunk_step``) and names the
layers of the step with ``jax.named_scope`` (:data:`SCOPES`).  A TPU op's
trace event carries no ``op_name``; the op's event metadata in the
``.xplane.pb`` does, as its ``tf_op`` statistic, which
``jax.profiler.ProfileData`` does not expose, so :func:`op_names` reads it
from the file's protobuf encoding.

:func:`record` keeps, for the traced window, a compact record under
``run.trace["engine"]``:

* ``spans``: ``[start_ns, duration_ns, name, args]`` of each ``engine.*``
  host span (``args``: the annotation's arguments);
* ``programs``: ``[start_ns, duration_ns, name, scopes]`` of each
  execution of a step program on the first device, ``scopes`` the device
  time of the operations that start inside it by scope (``{scope: ns}``;
  loops and calls left out: their time is their operations'), a scope the
  innermost of :data:`SCOPES` in the operation's ``op_name``, ``""`` for
  none.

The benchmark's ``run.py`` keeps no path to the trace it read, so
:func:`record` finds this run's trace among the profiler directories the
harness makes (``bench_trace_*`` under the temporary directory), by the
start of the ``bench.window`` span.  A program without these spans and
programs (an engine from before them) gives an empty record, and the
readers then report nothing.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import tempfile

from bench import trace as trace_lib

#: the served step's named scopes (``repro/models/lm.py``); ``layer_stack``
#: with no inner scope is the layer scan's slicing and restacking of the
#: stacked KV cache and weights, ``cv`` the control-variate correction
SCOPES = ("embed", "attn_norm", "qkv", "kv_write", "attn", "o_proj",
          "mlp_norm", "mlp_in", "mlp_out", "final_norm", "head",
          "layer_stack", "cv")
_SCOPE_SET = frozenset(SCOPES)
#: the scopes that move the KV cache: its writes and the scan's restacking
KV_STACK = ("kv_write", "layer_stack")
SPAN_PREFIX = "engine."
STEP_PROGRAM = re.compile(r"^jit_engine_(decode|chunk)_step\b")


def scope_of(op_name: str) -> str:
    """The innermost step scope in an ``op_name``
    (``jit(engine_decode_step)/layer_stack/while/body/qkv/cv/mul`` ->
    ``cv``); ``""`` for none.  The last part, the operation itself, is
    not a scope."""
    parts = op_name.split("/")[:-1]
    for part in reversed(parts):
        if part in _SCOPE_SET:
            return part
    return ""


# ---------------------------------------------------------------------------
# the xplane's protobuf encoding (XSpace > XPlane > XEventMetadata > XStat)
# ---------------------------------------------------------------------------


def _varint(buf, i: int) -> tuple[int, int]:
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf, i: int, end: int):
    """``(field number, wire type, value)`` of a message's fields; a
    length-delimited value is its ``(start, end)`` in ``buf``."""
    while i < end:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val = (i, i + n)
            i += n
        elif wire == 1:
            val = buf[i:i + 8]
            i += 8
        elif wire == 5:
            val = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, wire, val


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def op_names(path: str, plane_prefix: str = "/device:TPU:") -> dict:
    """``{event name: op_name}`` of the operations on the planes whose name
    starts with ``plane_prefix``: each event metadata's ``tf_op`` statistic
    (a string, or a reference to one), under both its name and its display
    name."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: dict[str, str] = {}
    for num, wire, val in _fields(buf, 0, len(buf)):
        if num != 1 or wire != 2:  # XSpace.planes
            continue
        name, metas, stat_names = "", [], {}
        for pnum, pwire, pval in _fields(buf, *val):
            if pnum == 2 and pwire == 2:  # XPlane.name
                name = _text(buf, pval)
            elif pnum == 4 and pwire == 2:  # XPlane.event_metadata entry
                metas.append(pval)
            elif pnum == 5 and pwire == 2:  # XPlane.stat_metadata entry
                for enum, ewire, eval_ in _fields(buf, *pval):
                    if enum == 2 and ewire == 2:
                        sid, sname = None, ""
                        for snum, swire, sval in _fields(buf, *eval_):
                            if snum == 1 and swire == 0:
                                sid = sval
                            elif snum == 2 and swire == 2:
                                sname = _text(buf, sval)
                        stat_names[sid] = sname
        if not name.startswith(plane_prefix):
            continue
        tf_op = [k for k, v in stat_names.items() if v == "tf_op"]
        if not tf_op:
            continue
        tf_op_id = tf_op[0]
        for entry in metas:
            for enum, ewire, eval_ in _fields(buf, *entry):
                if enum != 2 or ewire != 2:  # the map entry's value
                    continue
                names, op_name = [], None
                for mnum, mwire, mval in _fields(buf, *eval_):
                    if mnum in (2, 4) and mwire == 2:  # name, display_name
                        names.append(_text(buf, mval))
                    elif mnum == 5 and mwire == 2:  # stats
                        sid, sval = None, None
                        for snum, swire, v in _fields(buf, *mval):
                            if snum == 1 and swire == 0:
                                sid = v
                            elif snum == 5 and swire == 2:
                                sval = _text(buf, v)
                            elif snum == 7 and swire == 0:
                                sval = stat_names.get(v)
                        if sid == tf_op_id:
                            op_name = sval
                if op_name:
                    for n in names:
                        if n:
                            out[n] = op_name
    return out


# ---------------------------------------------------------------------------
# the record
# ---------------------------------------------------------------------------


def extract(path: str, lo: int, hi: int, pd=None) -> dict:
    """The compact record (module docstring) of the trace in ``path`` (read
    already as ``pd``, a ``jax.profiler.ProfileData``) for the window
    ``[lo, hi)`` on the profiler's clock."""
    from jax.profiler import ProfileData

    pd = pd or ProfileData.from_file(path)
    names = op_names(path)
    spans: list = []
    programs: list = []
    device = min((p for p in pd.planes if p.name.startswith("/device:TPU:")),
                 key=lambda p: p.name, default=None)
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    if ev.name.startswith(SPAN_PREFIX) and lo <= s < hi:
                        spans.append([s, int(ev.duration_ns), ev.name,
                                      {k: v for k, v in ev.stats}])
    lines = {line.name: line for line in device.lines} if device else {}
    if "XLA Modules" in lines:
        programs = sorted(
            [int(ev.start_ns), int(ev.duration_ns), ev.name, {}]
            for ev in lines["XLA Modules"].events
            if STEP_PROGRAM.match(ev.name) and lo <= int(ev.start_ns) < hi)
    starts = [p[0] for p in programs]
    scope: dict[str, str | None] = {}  # None: a loop or call
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
            by = programs[i][3]
            by[scope[name]] = by.get(scope[name], 0) + int(ev.duration_ns)
    spans.sort(key=lambda x: (x[0], -x[1]))
    return {"spans": spans, "programs": programs}


def _find_xplane(lo: int):
    """``(path, ProfileData)`` of the newest profiler output under the
    temporary directory whose ``bench.window`` span starts at ``lo``."""
    from jax.profiler import ProfileData

    dirs = glob.glob(os.path.join(tempfile.gettempdir(), "bench_trace_*"))
    for d in sorted(dirs, key=os.path.getmtime, reverse=True):
        files = sorted(glob.glob(f"{d}/**/*.xplane.pb", recursive=True))
        if not files:
            continue
        pd = ProfileData.from_file(files[-1])
        for plane in pd.planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if (ev.name == "bench.window"
                                and int(ev.start_ns) == lo):
                            return files[-1], pd
    return None, None


def record(run) -> dict | None:
    """The run's compact record (found once, then kept under
    ``run.trace["engine"]``); None without a device trace."""
    if not run.trace or not run.trace.get("devices"):
        return None
    if "engine" not in run.trace:
        win = trace_lib.window(run.trace)
        path, pd = _find_xplane(win[0]) if win else (None, None)
        run.trace["engine"] = (extract(path, *win, pd=pd) if path else
                               {"spans": [], "programs": []})
    return run.trace["engine"]


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def _children(spans: list, parent: list, name: str) -> list:
    s, d = parent[0], parent[1]
    return [c for c in spans if c[2] == name and s <= c[0] < s + d]


def host_ms_per_step(rec: dict) -> float | None:
    """Mean over the ``engine.step`` spans that ran a batch (those with a
    ``shape``) of the span less its ``engine.fetch`` children, ms: the
    host's work while the device has nothing queued."""
    steps = [sp for sp in rec["spans"]
             if sp[2] == "engine.step" and "shape" in sp[3]]
    if not steps:
        return None
    host = [sp[1] - sum(c[1] for c in
                        _children(rec["spans"], sp, "engine.fetch"))
            for sp in steps]
    return sum(host) / len(host) / 1e6


def program_ns(rec: dict) -> int:
    """Device time of the step programs' executions."""
    return sum(p[1] for p in rec["programs"])


def scope_ns(rec: dict) -> dict[str, int]:
    """Device time of the step programs' operations by scope (``""``: no
    scope)."""
    out: dict[str, int] = {}
    for *_, by in rec["programs"]:
        for scope, ns in by.items():
            out[scope] = out.get(scope, 0) + ns
    return out


def scope_share(rec: dict, scopes) -> float | None:
    """Device time of the operations under ``scopes`` over the step
    programs' device time, %; None without a step program or when no
    operation carries any of ``scopes``."""
    total = program_ns(rec)
    by = scope_ns(rec)
    ns = sum(by.get(s, 0) for s in scopes)
    if total <= 0 or not any(s in by for s in scopes):
        return None
    return 100.0 * ns / total


def longest_gaps(run, n: int = 10) -> list[list]:
    """The ``n`` longest idle gaps of the first device in the window, each
    named by the innermost ``engine.*`` span open at its middle (else the
    innermost benchmark span): ``[[name, seconds]]``."""
    rec = record(run)
    win = trace_lib.window(run.trace) if run.trace else None
    if rec is None or win is None:
        return []
    dev = run.trace["devices"][sorted(run.trace["devices"])[0]]
    gaps = sorted(trace_lib.busy_gaps(dev["ops"], *win),
                  key=lambda g: g[0] - g[1])[:n]
    out = []
    for a, b in gaps:
        t = (a + b) // 2
        inner = [sp for sp in rec["spans"] if sp[0] <= t < sp[0] + sp[1]]
        name = (min(inner, key=lambda sp: sp[1])[2] if inner
                else trace_lib.host_span_at(run.trace, t))
        out.append([name, (b - a) / 1e9])
    return out
