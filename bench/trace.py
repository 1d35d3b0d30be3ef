"""From a JAX profiler trace to the numbers the per-layer metrics read.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
a compact record: for each TPU device, its operations (line ``XLA Ops``)
as ``[start_ns, duration_ns, name, kind]`` and its programs (line ``XLA
Modules``) as ``[start_ns, duration_ns, name]``; and the host spans the
benchmark itself opened with ``jax.profiler.TraceAnnotation`` (names
starting ``bench.``).  Host and device events share the profiler's clock.
On a TPU an operation's event is named by its HLO instruction text; the
record keeps the instruction's name and result shape (``fusion.200
bf16[32,32,16384]``) and what :func:`op_kind` makes of the text.  The
reductions below work on that record only, so they are checked on a small
recorded one (``tests/fixtures``).

The rule that finds the packed dense layers' products is in
:func:`op_kind`: an output fusion (``kind=kOutput``, XLA's fusion rooted at
a dot) with a ``u8`` operand, the packed weight codes.  It holds for the
packs' XLA path, which the cells serve; a Pallas kernel is a custom call
and is not matched.
"""

from __future__ import annotations

import bisect
import glob
import re

#: host spans the benchmark opens around its own calls
SPAN_PREFIX = "bench."


#: what :func:`op_kind` says of an operation
OTHER, DENSE, CONTAINER = 0, 1, 2
_CONTAINERS = ("while", "conditional", "call")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OPCODE = re.compile(r"(?:^|[\s)])([a-z][\w-]*)\(")


def op_kind(text: str) -> tuple[str, int]:
    """(short name, kind) of an operation from its HLO instruction text:
    ``DENSE`` for a packed dense layer's product, ``CONTAINER`` for a loop
    or call whose time is that of the operations inside it, else
    ``OTHER``."""
    if " = " not in text:
        return text, OTHER
    lhs, rhs = text.split(" = ", 1)
    name = lhs.strip().lstrip("%")
    body = _LAYOUT.sub("", rhs)
    if body.startswith("("):  # a tuple result
        depth, i = 0, 0
        for i, ch in enumerate(body):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        result, rest = body[: i + 1], body[i + 1:]
    else:
        result, _, rest = body.partition(" ")
        rest = " " + rest
    m = _OPCODE.search(rest)
    opcode = m.group(1) if m else ""
    short = f"{name} {result[:60]}"
    if opcode in _CONTAINERS:
        return short, CONTAINER
    operands = rest[rest.find("(") + 1:] if "(" in rest else ""
    if opcode == "fusion" and "kind=kOutput" in rest and "u8[" in operands:
        return short, DENSE
    return short, OTHER


def extract(trace_dir: str) -> dict:
    """The compact record of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        return {"devices": {}, "host": []}
    pd = ProfileData.from_file(files[-1])
    devices: dict[str, dict] = {}
    host: list = []
    parsed: dict[str, tuple[str, int]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            rec = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        name = ev.name
                        got = parsed.get(name)
                        if got is None:
                            got = parsed[name] = op_kind(name)
                        rec["ops"].append([int(ev.start_ns),
                                           int(ev.duration_ns), *got])
                elif line.name == "XLA Modules":
                    rec["modules"].extend(
                        [int(ev.start_ns), int(ev.duration_ns), ev.name]
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host.append([int(ev.start_ns), int(ev.duration_ns),
                                     ev.name])
    host.sort()
    for rec in devices.values():
        rec["ops"].sort()
    return {"devices": devices, "host": host}


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def union_ns(intervals, lo: int, hi: int) -> int:
    """Length of the union of ``[start, start + dur)`` intervals, clipped
    to ``[lo, hi)``."""
    spans = sorted((max(s, lo), min(s + d, hi)) for s, d, *_ in intervals
                   if s < hi and s + d > lo)
    total, cur_s, cur_e = 0, None, None
    for s, e in spans:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_gaps(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """The idle gaps ``(start, end)`` between busy intervals in
    ``[lo, hi)``."""
    spans = sorted((max(s, lo), min(s + d, hi)) for s, d, *_ in intervals
                   if s < hi and s + d > lo)
    gaps, t = [], lo
    for s, e in spans:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def window(rec: dict, name: str = "bench.window") -> tuple[int, int] | None:
    """The traced window: the host span the benchmark opened around it."""
    for s, d, n in rec["host"]:
        if n == name:
            return s, s + d
    return None


def idle_share(rec: dict) -> tuple[float, float, float] | None:
    """(busy seconds averaged over the devices, window seconds, idle share)
    in the traced window; None when the trace has no device."""
    win = window(rec)
    if win is None or not rec["devices"]:
        return None
    lo, hi = win
    busy = [union_ns(d["ops"], lo, hi) for d in rec["devices"].values()]
    mean_busy = sum(busy) / len(busy)
    return mean_busy / 1e9, (hi - lo) / 1e9, 1.0 - mean_busy / (hi - lo)


def host_span_at(rec: dict, t: int) -> str:
    """The innermost benchmark span open at ``t`` (the host's doing)."""
    best = None
    for s, d, n in rec["host"]:
        if s <= t < s + d and n != "bench.window":
            if best is None or d < best[1]:
                best = (s, d, n)
    return best[2] if best else "outside bench spans"


def longest_gaps(rec: dict, n: int = 10) -> list[list]:
    """The ``n`` longest idle gaps of the first device in the window, each
    named by what the host was doing at its middle: ``[[name, seconds]]``.
    """
    win = window(rec)
    if win is None or not rec["devices"]:
        return []
    dev = rec["devices"][sorted(rec["devices"])[0]]
    gaps = sorted(busy_gaps(dev["ops"], *win), key=lambda g: g[0] - g[1])
    return [[host_span_at(rec, (a + b) // 2), (b - a) / 1e9]
            for a, b in gaps[:n]]


def top_ops(rec: dict, n: int = 10) -> list[list]:
    """Device time by operation in the window, averaged over the devices,
    loops and calls left out (their time is their operations'): the ``n``
    largest, ``[[name, seconds]]``."""
    win = window(rec)
    if win is None or not rec["devices"]:
        return []
    lo, hi = win
    tot: dict[str, int] = {}
    for dev in rec["devices"].values():
        for s, d, name, kind in dev["ops"]:
            if lo <= s < hi and kind != CONTAINER:
                tot[name] = tot.get(name, 0) + d
    k = len(rec["devices"])
    return [[name, ns / k / 1e9] for name, ns in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def dense_ns(rec: dict, intervals: list[tuple[int, int]]) -> int:
    """Device time of the dense products that start inside ``intervals``
    (sorted, not overlapping: the step programs' executions)."""
    starts = [a for a, _ in intervals]
    total = 0
    for dev in rec["devices"].values():
        for s, d, _, kind in dev["ops"]:
            if kind == DENSE:
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s < intervals[i][1]:
                    total += d
    return total


def step_programs(rec: dict, kinds: list[str]) -> list[tuple[int, int]]:
    """The device executions of the engine's step programs in the traced
    window, one per step of ``kinds`` (the host's step shapes, in order):
    ``[(start_ns, duration_ns)]``, at most ``len(kinds)`` of them.

    A step whose batch emits no token returns before its program has run,
    so a program need not lie inside its step's host span; the order
    holds.  The step programs are the programs (by name: one per compiled
    shape) with the most device time, taken while their executions come
    nearer to one per step, among those with at least 1% of the busiest
    one's time.  A program that the step before the window dispatched can
    still start inside it: up to two leading executions are skipped, so
    that each program name matches one step shape as often as can be.
    Only the first device is read (the cells run on one chip)."""
    win = window(rec)
    if win is None or not rec["devices"] or not kinds:
        return []
    lo, hi = win
    dev = rec["devices"][sorted(rec["devices"])[0]]
    mods = [m for m in dev["modules"] if lo <= m[0] < hi]
    total: dict[str, int] = {}
    count: dict[str, int] = {}
    for _, d, name in mods:
        total[name] = total.get(name, 0) + d
        count[name] = count.get(name, 0) + 1
    chosen, n = set(), 0
    for name in sorted(total, key=total.get, reverse=True):
        if (100 * total[name] < max(total.values())
                or abs(n + count[name] - len(kinds)) > abs(n - len(kinds))):
            break
        chosen.add(name)
        n += count[name]
    progs = [m for m in mods if m[2] in chosen]

    def agreement(skip: int) -> int:
        votes: dict[tuple[str, str], int] = {}
        for (_, _, name), kind in zip(progs[skip:], kinds):
            votes[name, kind] = votes.get((name, kind), 0) + 1
        best: dict[str, int] = {}
        for (name, _), v in votes.items():
            best[name] = max(best.get(name, 0), v)
        return sum(best.values())

    skip = max(range(min(3, len(progs) + 1)), key=lambda k: (agreement(k), -k))
    return [(s, d) for s, d, _ in progs[skip:skip + len(kinds)]]
