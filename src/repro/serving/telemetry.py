"""Request-span tracing for the serving engine, on the profiler's clock.

Two records, one API.  Every engine phase opens a :class:`Span`: a
``jax.profiler.TraceAnnotation`` named ``engine.<kind>`` (so a profiler
trace shows the host's phases beside the device's programs), and, when the
engine's ring is on, a typed :class:`SpanEvent` in a per-engine ring
buffer (:class:`SpanTracer`) with the same kind, request id and arguments.
With the ring off a span costs one annotation and two clock reads; the
engine opens a handful per step and none per row or per token.

Engine phases (children of ``engine.step``, which is a
``jax.profiler.StepTraceAnnotation`` carrying ``step_num``):

  * ``step``        — one engine iteration; ``shape`` (``decode`` /
    ``chunk``), ``prompt_rows``, ``decode_rows`` and ``clock_ns`` (the
    span clock's stamp at its start: the anchor, below) ride in args
  * ``schedule``    — deadline purge, admission, the next batch;
    ``admitted`` and ``queued`` ride in args
  * ``admit``       — zero-length, one per request given a slot:
    ``rid``, ``slot`` and ``queue_wait_s`` (submit to slot)
  * ``dispatch``    — host-to-device inputs, the jitted step, the pool
    update
  * ``fetch``       — the token pick and its blocking device-to-host copy:
    the span in which the host waits for the device
  * ``emit``        — per-row advance, ``on_token`` callbacks, finishes
  * ``account``     — ``EngineMetrics.record_step`` and the window roll
  * ``probe``       — one approximation-error probe
    (:mod:`repro.quant.error_probe`); its result rides in args
  * ``shadow``      — one A/B shadow replay of a finished sampled request
    through the second pack (:mod:`repro.serving.shadow`)
  * ``quarantine``  — fault detection and the exact replay of flagged rows
    (profiler only: the ring records one ``quarantine`` event per row)

Ring-only events (:meth:`SpanTracer.record`), at points the engine already
touches a request:

  * ``queued``        — request entered the queue (instant, at submit)
  * ``prefill_chunk`` — one chunk-shaped batch row advanced its prompt
    (t and duration: its step's, up to the accounting)
  * ``decode_step``   — one generated-token batch row (likewise)
  * ``cow_copy``      — copy-on-write block copies flushed before a step
  * ``prefix_hit``    — admission attached to cached prefix blocks
  * ``capacity_stall``— queued work could not be placed this iteration
  * ``evicted``       — re-rejected from a full queue by higher priority
  * ``rejected``      — admission control refused the request
  * ``finished``      — terminal; ``reason``/``generated`` ride in args
  * ``draft``         — one speculative round's draft calls for a
    participating slot: ``k`` approximate-spec tokens proposed
    (:mod:`repro.serving.speculative`)
  * ``verify``        — the exact-spec verification of those drafts:
    ``drafted``/``accepted``/``emitted`` ride in args, so per-request
    acceptance is reconstructable from the trace alone
  * ``metrics_window``— one windowed time-series sample
    (:class:`~repro.serving.metrics.EngineMetrics`); exported as Chrome
    *counter* events so Perfetto plots the series
  * ``governor_switch`` — the accuracy-SLO governor hot-swapped the live
    numerics pack (:mod:`repro.serving.governor`); ``from``/``to``/
    ``reason``/``power_delta_pct`` ride in args
  * ``fault_detected`` — engine-side NaN/divergence detection flagged a
    batch row before emission (:mod:`repro.quant.faults`)
  * ``quarantine``    — a flagged row's KV cursor was rolled back and the
    step replayed on the exact pack; ``replayed`` tokens ride in args
  * ``routed``        — the fleet router assigned a request to a replica
    (:mod:`repro.serving.fleet`); ``klass``/``tier``/``replica``/``spill``
    ride in args, so tier placement is auditable from the trace alone
  * ``prefix_import`` — this replica adopted prefix-cache blocks exported
    by another replica (cross-replica sharing); ``blocks`` rides in args

**Clock and anchor.**  Ring timestamps are :func:`clock` seconds
(``time.perf_counter``, monotonic); ``Request.t_queued_mono`` is stamped on
it too.  The profiler stamps its events on a clock of its own (on the CPU,
``jax.profiler.ProfileData`` gives host events a ``start_ns`` that is not
any Python clock), so every ``engine.step`` annotation carries its start
on the span clock as ``clock_ns``: ``start_ns - clock_ns`` of any step is
the offset that puts the ring (or any other :func:`clock` stamp) onto the
profiler trace of the same run.

Two ring export formats (exports rebase to the tracer's construction):

  * **JSONL** (``write("x.jsonl")``) — one event object per line; trivially
    greppable and the format ``tools/trace_report.py`` consumes natively;
  * **Chrome ``trace_event`` JSON** (``write("x.json")``) — opens directly
    in Perfetto / ``chrome://tracing``: the engine is a process, every
    request is a track (tid), batch rows are duration events, the windowed
    metrics are counter tracks.

The ring buffer drops the OLDEST events once ``capacity`` is reached
(``dropped`` counts them) so a long-running engine's tracing cost is a
bounded append, never an unbounded list.
"""

from __future__ import annotations

import collections
import json
import time
import typing

import jax

#: the span clock, seconds: ring timestamps and request stamps
clock = time.perf_counter

#: prefix of every profiler annotation the engine opens
PREFIX = "engine."

#: engine phases (:class:`Span` kinds); ``engine.<kind>`` in the profiler
PHASE_KINDS: tuple[str, ...] = (
    "step", "schedule", "admit", "dispatch", "fetch", "emit", "account",
    "probe", "shadow", "quarantine")

#: every ring event kind: the phases and the ring-only events
SPAN_KINDS: tuple[str, ...] = PHASE_KINDS + (
    "queued",
    "prefill_chunk",
    "decode_step",
    "cow_copy",
    "prefix_hit",
    "capacity_stall",
    "evicted",
    "rejected",
    "finished",
    "draft",
    "verify",
    "metrics_window",
    "governor_switch",
    "fault_detected",
    "routed",
    "prefix_import",
)

#: request-lifecycle stages every served-to-completion request passes
#: through (the CI smoke asserts >= 1 span of each in a traced run)
LIFECYCLE_KINDS: tuple[str, ...] = (
    "queued", "admit", "prefill_chunk", "decode_step", "finished")

_SPAN_KIND_SET = frozenset(SPAN_KINDS)  # O(1) hot-path validation


class SpanEvent(typing.NamedTuple):
    """One typed telemetry event.  ``rid`` None = engine-scoped.

    A NamedTuple, not a (frozen) dataclass: events are constructed on the
    engine's hot step loop, and frozen-dataclass ``__init__`` goes through
    ``object.__setattr__`` per field."""

    kind: str
    rid: int | None
    t: float  # span clock seconds (:func:`clock`)
    dur: float = 0.0  # seconds; 0 = instant event
    data: dict | None = None


class SpanTracer:
    """Bounded per-engine span ring buffer with JSONL / Chrome export."""

    def __init__(self, capacity: int = 65536, engine: str = "engine",
                 pid: int = 0) -> None:
        if capacity < 1:
            raise ValueError(f"tracer capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.engine = engine
        self.pid = pid
        self.dropped = 0  # events evicted by the ring (oldest first)
        self.t0 = clock()  # trace epoch; exports rebase to it
        self._buf: collections.deque[SpanEvent] = collections.deque(
            maxlen=capacity)

    # -- recording -----------------------------------------------------------

    def record(self, kind: str, rid: int | None = None, t: float | None = None,
               dur: float = 0.0, **data) -> None:
        if kind not in _SPAN_KIND_SET:
            raise ValueError(f"unknown span kind {kind!r}; "
                             f"valid: {list(SPAN_KINDS)}")
        if len(self._buf) == self.capacity:
            self.dropped += 1
        self._buf.append(SpanEvent(
            kind, rid, clock() if t is None else t, dur, data or None))

    def span(self, kind: str, rid: int | None = None,
             step: int | None = None, **args) -> "Span":
        """An engine phase recorded both in the profiler and in this ring
        (see :func:`span`)."""
        return Span(self, kind, rid, step, args)

    def __len__(self) -> int:
        return len(self._buf)

    def events(self) -> list[SpanEvent]:
        return list(self._buf)

    def clear(self) -> None:
        self._buf.clear()
        self.dropped = 0

    # -- export --------------------------------------------------------------

    def _ordered(self) -> list[SpanEvent]:
        """Events by start time: a span is recorded when it closes, after
        the spans nested in it."""
        return sorted(self._buf, key=lambda e: e.t)

    def to_jsonl(self) -> str:
        """One JSON object per line, by start time; times in seconds from
        the trace epoch."""
        lines = []
        for e in self._ordered():
            d = {"engine": self.engine, "kind": e.kind, "rid": e.rid,
                 "t": round(e.t - self.t0, 9), "dur": round(e.dur, 9)}
            if e.data:
                d.update(e.data)
            lines.append(json.dumps(d))
        return "\n".join(lines) + ("\n" if lines else "")

    def chrome_trace(self) -> dict:
        """Chrome ``trace_event`` JSON (the Perfetto-compatible subset).

        ts/dur are microseconds from the trace epoch.  Events with a
        duration become ``"X"`` (complete) events, instants ``"i"``,
        windowed metrics samples ``"C"`` (counter) events.  Each request
        gets its own thread track (``tid = rid + 1``; tid 0 is the
        engine-scoped track), named via metadata events.
        """
        evs: list[dict] = []
        evs.append({"ph": "M", "pid": self.pid, "tid": 0,
                    "name": "process_name", "args": {"name": self.engine}})
        named_tids = {0}
        evs.append({"ph": "M", "pid": self.pid, "tid": 0,
                    "name": "thread_name", "args": {"name": "engine"}})
        for e in self._ordered():
            tid = 0 if e.rid is None else e.rid + 1
            if tid not in named_tids:
                named_tids.add(tid)
                evs.append({"ph": "M", "pid": self.pid, "tid": tid,
                            "name": "thread_name",
                            "args": {"name": f"request {e.rid}"}})
            args = dict(e.data or {})
            if e.rid is not None:
                args["rid"] = e.rid
            base = {"name": e.kind, "cat": "serving", "pid": self.pid,
                    "tid": tid, "ts": round((e.t - self.t0) * 1e6, 3),
                    "args": args}
            if e.kind == "metrics_window":
                # counter track: numeric args only (Perfetto plots them)
                base["ph"] = "C"
                base["tid"] = 0
                base["args"] = {k: v for k, v in args.items()
                                if isinstance(v, (int, float))
                                and not isinstance(v, bool)}
            elif e.dur > 0:
                base["ph"] = "X"
                base["dur"] = round(e.dur * 1e6, 3)
            else:
                base["ph"] = "i"
                base["s"] = "t"  # thread-scoped instant
            evs.append(base)
        return {"traceEvents": evs, "displayTimeUnit": "ms",
                "otherData": {"engine": self.engine,
                              "dropped_events": self.dropped}}

    def write(self, path: str) -> None:
        """``*.jsonl`` -> JSONL, anything else -> Chrome trace JSON."""
        with open(path, "w") as f:
            if str(path).endswith(".jsonl"):
                f.write(self.to_jsonl())
            else:
                json.dump(self.chrome_trace(), f)
                f.write("\n")


class Span:
    """One engine phase: a ``jax.profiler`` annotation named
    ``engine.<kind>`` and, with a ring, one :class:`SpanEvent` recorded when
    it closes.  ``step`` makes it a ``StepTraceAnnotation`` with that
    ``step_num`` and the clock anchor ``clock_ns``.  ``t`` and ``dur`` (span
    clock seconds) are set on entry and exit."""

    __slots__ = ("tracer", "kind", "rid", "step", "args", "t", "dur", "_tm")

    def __init__(self, tracer: SpanTracer | None, kind: str,
                 rid: int | None, step: int | None, args: dict) -> None:
        self.tracer = tracer
        self.kind = kind
        self.rid = rid
        self.step = step
        self.args = args
        self.dur = 0.0

    def __enter__(self) -> "Span":
        t = self.t = clock()
        kw = self.args if self.rid is None else {"rid": self.rid, **self.args}
        if self.step is None:
            self._tm = jax.profiler.TraceAnnotation(PREFIX + self.kind, **kw)
        else:
            self._tm = jax.profiler.StepTraceAnnotation(
                PREFIX + self.kind, step_num=self.step,
                clock_ns=int(t * 1e9), **kw)
        self._tm.__enter__()
        return self

    def set(self, rid: int | None = None, **args) -> None:
        """Arguments known only inside the span (and its request id)."""
        self.args.update(args)
        if rid is not None:
            self.rid = rid
            args["rid"] = rid
        self._tm.set_metadata(**args)

    @property
    def recording(self) -> bool:
        """Whether anything keeps this span's arguments: the ring, or a
        profiler trace in progress.  Arguments that cost work per row are
        computed only then."""
        return (self.tracer is not None
                or jax.profiler.TraceAnnotation.is_enabled())

    def drop(self) -> None:
        """Record nothing in the ring for this span (it did no work)."""
        self.tracer = None

    def __exit__(self, *exc) -> bool:
        self._tm.__exit__(*exc)
        self.dur = clock() - self.t
        if self.tracer is not None:
            args = self.args if self.step is None else {
                "step_num": self.step, **self.args}
            self.tracer.record(self.kind, self.rid, t=self.t, dur=self.dur,
                               **args)
        return False


def span(kind: str, rid: int | None = None, step: int | None = None,
         **args) -> Span:
    """An engine phase recorded in the profiler alone (the engine's ring is
    off): ``with span("fetch"): ...``."""
    return Span(None, kind, rid, step, args)


def spans(tracer: SpanTracer | None):
    """The span constructor for an engine whose ring is ``tracer``."""
    return span if tracer is None else tracer.span


def instant(tracer: SpanTracer | None, kind: str, rid: int | None = None,
            **args) -> None:
    """A zero-length span: an engine event at one moment."""
    with spans(tracer)(kind, rid, **args):
        pass
