#!/usr/bin/env python
"""Summarize serving span traces (JSONL or Chrome trace_event JSON).

    python tools/trace_report.py /tmp/trace.json
    python tools/trace_report.py /tmp/trace.jsonl --format json
    python tools/trace_report.py /tmp/trace.json --assert-lifecycle
    python tools/trace_report.py --trace /tmp/fleet/trace-int8-0.jsonl \\
        --trace /tmp/fleet/trace-int8-1.jsonl ...

Reads either export format of ``repro.serving.telemetry.SpanTracer`` and
prints:

  * per-request timelines — queue wait, prefill chunks, decode steps,
    end-to-end span, finish reason; under speculative decode also the
    per-request draft rounds and acceptance rate (reconstructed from the
    ``draft``/``verify`` spans alone);
  * a speculative summary — trace-wide drafted/accepted counts and the
    acceptance rate, the draft-quality signal for the approximate spec;
  * stall attribution — the largest inter-decode-step gaps per request,
    attributed to prefill interference (another request's chunk ran in
    the gap), capacity stalls, an error-probe forward, an A/B shadow
    replay, or scheduler idle time;
  * probe error trend — the approximation-error probe's logits/layer
    error variance over time (first vs last, min/max);
  * shadow A/B — sampled replays through the second numerics pack:
    token agreement, logit-delta stats, and replay cost (``shadow``
    spans; see repro.serving.shadow);
  * windowed counters — min/median/max of the windowed gen tok/s series;
  * robustness — governor ladder switches (from/to rung, reason, cost-model
    power delta), detected faults, quarantine replays, and deadline
    evictions, when the trace carries any (old traces without the PR 8
    span kinds still load and report).

Fleet traces: pass several files (repeatable ``--trace FILE``, e.g. the
per-replica JSONLs ``FleetRouter.write_traces`` emits).  With more than
one trace, request ids are prefixed with the replica's engine id
(``"int8:0:7"`` — engine request counters are per-replica, so bare rids
collide across a fleet) and the report gains a **fleet** section:
per-tier request counts, routed classes/spills, TTFT, speculative
acceptance, prefix imports, and capacity-stall attribution.
Single-trace invocations are unchanged.

``--assert-lifecycle`` exits non-zero unless the trace holds at least one
span of every request-lifecycle stage (queued, admit, prefill_chunk,
decode_step, finished) — the CI smoke's trace-integrity gate.
``--assert-quarantine`` exits non-zero unless every ``fault_detected``
span is matched by a ``quarantine`` span (the fault-injection smoke's
no-corrupted-emission gate; also requires >= 1 of each).
"""

from __future__ import annotations

import argparse
import collections
import json
import sys

LIFECYCLE = ("queued", "admit", "prefill_chunk", "decode_step", "finished")


def load_events(path: str) -> list[dict]:
    """Normalize either export format to
    ``{kind, rid, t (s), dur (s), engine, data}`` sorted by time."""
    with open(path) as f:
        text = f.read()
    events: list[dict] = []
    try:
        doc = json.loads(text)  # Chrome trace is one JSON document
    except json.JSONDecodeError:
        doc = None  # JSONL: one object per line
    if isinstance(doc, dict) and "traceEvents" in doc:
        engine = (doc.get("otherData") or {}).get("engine")
        for e in doc["traceEvents"]:
            if e.get("ph") == "M":  # metadata (process/thread names)
                continue
            data = dict(e.get("args") or {})
            rid = data.pop("rid", None)
            events.append({"kind": e["name"], "rid": rid,
                           "t": e.get("ts", 0.0) / 1e6,
                           "dur": e.get("dur", 0.0) / 1e6,
                           "engine": engine, "data": data})
    else:
        for line in text.splitlines():
            line = line.strip()
            if not line:
                continue
            d = json.loads(line)
            data = {k: v for k, v in d.items()
                    if k not in ("engine", "kind", "rid", "t", "dur")}
            events.append({"kind": d["kind"], "rid": d.get("rid"),
                           "t": d["t"], "dur": d.get("dur", 0.0),
                           "engine": d.get("engine"), "data": data})
    events.sort(key=lambda e: e["t"])
    return events


def load_traces(paths: list[str]) -> list[dict]:
    """Load and merge several traces (a fleet's per-replica files).

    With more than one file, every request id is prefixed with its
    replica's engine id — each engine numbers requests independently, so
    bare rids collide across a fleet; ``"<engine>:<rid>"`` keeps every
    request's timeline distinct.  One file behaves exactly like
    :func:`load_events` (integer rids, identical report)."""
    events: list[dict] = []
    for i, path in enumerate(paths):
        evs = load_events(path)
        if len(paths) > 1:
            for e in evs:
                if e["rid"] is not None:
                    e["rid"] = f"{e['engine'] or f'trace{i}'}:{e['rid']}"
        events.extend(evs)
    events.sort(key=lambda e: e["t"])
    return events


def _request_timelines(events: list[dict]) -> dict:
    reqs: dict[int, dict] = {}
    for e in events:
        rid = e["rid"]
        if rid is None:
            continue
        r = reqs.setdefault(rid, {
            "queued_t": None, "queue_wait_s": None, "prefill_chunks": 0,
            "decode_steps": 0, "prefill_s": 0.0, "decode_s": 0.0,
            "spec_rounds": 0, "drafted": 0, "accepted": 0,
            "prefix_hit_tokens": 0, "finish_reason": None, "generated": None,
            "t_first": e["t"], "t_last": e["t"] + e["dur"]})
        r["t_first"] = min(r["t_first"], e["t"])
        r["t_last"] = max(r["t_last"], e["t"] + e["dur"])
        k = e["kind"]
        if k == "queued":
            r["queued_t"] = e["t"]
        elif k in ("admit", "admitted"):  # "admitted": older traces
            r["queue_wait_s"] = e["data"].get("queue_wait_s")
        elif k == "prefill_chunk":
            r["prefill_chunks"] += 1
            r["prefill_s"] += e["dur"]
        elif k == "decode_step":
            r["decode_steps"] += 1
            r["decode_s"] += e["dur"]
        elif k == "verify":
            # one verify span per speculative round per request; drafted/
            # accepted ride in its args, so acceptance reconstructs from
            # the trace alone (no metrics snapshot needed)
            r["spec_rounds"] += 1
            r["drafted"] += e["data"].get("drafted", 0)
            r["accepted"] += e["data"].get("accepted", 0)
        elif k == "prefix_hit":
            r["prefix_hit_tokens"] = e["data"].get("hit_tokens", 0)
        elif k == "finished":
            r["finish_reason"] = e["data"].get("reason")
            r["generated"] = e["data"].get("generated")
        elif k in ("rejected", "evicted"):
            r["finish_reason"] = k
    for r in reqs.values():
        r["span_s"] = round(r["t_last"] - r["t_first"], 6)
        r["acceptance_rate"] = (round(r["accepted"] / r["drafted"], 4)
                                if r["drafted"] else None)
        del r["t_first"], r["t_last"]
    return reqs


def _speculative_summary(events: list[dict]) -> dict | None:
    verifies = [e for e in events if e["kind"] == "verify"]
    if not verifies:
        return None
    drafted = sum(e["data"].get("drafted", 0) for e in verifies)
    accepted = sum(e["data"].get("accepted", 0) for e in verifies)
    return {"rounds": len(verifies),
            "draft_spans": sum(1 for e in events if e["kind"] == "draft"),
            "drafted": drafted, "accepted": accepted,
            "acceptance_rate": (round(accepted / drafted, 4)
                                if drafted else None)}


def _stall_attribution(events: list[dict], top: int = 5) -> list[dict]:
    """Largest gaps between a request's consecutive decode steps, with a
    cause guess: prefill interference (another rid's chunk ran inside the
    gap), a recorded capacity stall, an error-probe forward or A/B shadow
    replay that ran in the gap (both carry real wall-time durations), or
    scheduler idle."""
    per_rid: dict[int, list[dict]] = collections.defaultdict(list)
    for e in events:
        if e["kind"] == "decode_step":
            per_rid[e["rid"]].append(e)

    def overlaps(kind: str, t0: float, t1: float) -> bool:
        return any(e["kind"] == kind and e["dur"] > 0
                   and e["t"] < t1 and e["t"] + e["dur"] > t0
                   for e in events)

    gaps = []
    for rid, evs in per_rid.items():
        for a, b in zip(evs, evs[1:]):
            gap = b["t"] - (a["t"] + a["dur"])
            if gap <= 0:
                continue
            t0, t1 = a["t"] + a["dur"], b["t"]
            interference = sum(
                1 for e in events
                if e["kind"] == "prefill_chunk" and e["rid"] != rid
                and e["t"] < t1 and e["t"] + e["dur"] > t0)
            stalls = sum(1 for e in events
                         if e["kind"] == "capacity_stall"
                         and t0 <= e["t"] <= t1)
            cause = ("prefill_interference" if interference
                     else "capacity_stall" if stalls
                     else "probe" if overlaps("probe", t0, t1)
                     else "shadow" if overlaps("shadow", t0, t1)
                     else "scheduler_idle")
            gaps.append({"rid": rid, "gap_s": round(gap, 6),
                         "t": round(t0, 6), "cause": cause,
                         "interfering_chunks": interference})
    gaps.sort(key=lambda g: -g["gap_s"])
    return gaps[:top]


def _probe_trend(events: list[dict]) -> dict | None:
    probes = [e for e in events if e["kind"] == "probe"]
    if not probes:
        return None
    series = [{"t": round(e["t"], 4),
               "logits_err_var": e["data"].get("logits_err_var"),
               "mean_layer_err_var": e["data"].get("mean_layer_err_var")}
              for e in probes]
    lv = [s["logits_err_var"] for s in series
          if s["logits_err_var"] is not None]
    return {"runs": len(series), "first": series[0], "last": series[-1],
            "logits_err_var_min": min(lv) if lv else None,
            "logits_err_var_max": max(lv) if lv else None}


def _shadow_summary(events: list[dict]) -> dict | None:
    """A/B shadow replay rollup from the ``shadow`` spans alone (one per
    sampled finished request; token/match counts and the replay's wall
    time ride in its args).  None when the run had no shadow serving."""
    shadows = [e for e in events if e["kind"] == "shadow"]
    if not shadows:
        return None
    tokens = sum(e["data"].get("tokens", 0) for e in shadows)
    matches = sum(e["data"].get("matches", 0) for e in shadows)
    evs = [e["data"]["logits_err_var"] for e in shadows
           if e["data"].get("logits_err_var") is not None]
    return {"replays": len(shadows), "tokens": tokens,
            "token_matches": matches,
            "token_match_rate": (round(matches / tokens, 4)
                                 if tokens else None),
            "logits_err_var_last": evs[-1] if evs else None,
            "replay_time_s": round(sum(e["dur"] for e in shadows), 6)}


def _robustness_summary(events: list[dict]) -> dict | None:
    """Governor/fault/deadline activity (PR 8 span kinds).  None when the
    trace predates them or the run had no robustness events — the report
    stays loadable for every trace vintage."""
    switches = [e for e in events if e["kind"] == "governor_switch"]
    faults = sum(1 for e in events if e["kind"] == "fault_detected")
    quars = [e for e in events if e["kind"] == "quarantine"]
    deadline_evictions = sum(
        1 for e in events if e["kind"] == "evicted"
        and e["data"].get("reason") == "deadline")
    deadline_finishes = sum(
        1 for e in events if e["kind"] == "finished"
        and e["data"].get("reason") == "deadline")
    if not (switches or faults or quars or deadline_evictions
            or deadline_finishes):
        return None
    return {
        "governor_switches": [
            {k: e["data"].get(k)
             for k in ("step", "action", "from", "to", "reason", "layer",
                       "err_var", "power_delta_pct")}
            for e in switches],
        "faults_detected": faults,
        "quarantines": len(quars),
        "replayed_tokens": sum(e["data"].get("replayed", 0) for e in quars),
        "deadline_evictions": deadline_evictions,
        "deadline_finishes": deadline_finishes,
    }


def _fleet_summary(events: list[dict]) -> dict | None:
    """Per-tier rollup when the events span several engines (a merged
    fleet trace).  Tier = the engine id up to its last ``:`` (replica ids
    are ``"<tier>:<index>"``).  None for single-engine traces, so plain
    reports are unchanged.

    TTFT here is trace-derived: queued span -> end of the request's last
    prefill chunk (the call that produces its first token), so it stays
    computable from the per-replica files alone."""
    engines = sorted({e["engine"] for e in events if e["engine"]})
    if len(engines) < 2:
        return None

    def tier_of(eng: str) -> str:
        return eng.rsplit(":", 1)[0] if ":" in eng else eng

    tiers: dict[str, list[str]] = {}
    for eng in engines:
        tiers.setdefault(tier_of(eng), []).append(eng)
    out: dict[str, dict] = {}
    for tname, engs in sorted(tiers.items()):
        evs = [e for e in events if e["engine"] in engs]
        queued = {e["rid"]: e["t"] for e in evs if e["kind"] == "queued"}
        first_tok: dict = {}
        for e in evs:
            if e["kind"] == "prefill_chunk" and e["rid"] in queued:
                end = e["t"] + e["dur"]
                first_tok[e["rid"]] = max(first_tok.get(e["rid"], end), end)
        ttfts = [first_tok[r] - queued[r] for r in first_tok]
        verifies = [e for e in evs if e["kind"] == "verify"]
        drafted = sum(e["data"].get("drafted", 0) for e in verifies)
        accepted = sum(e["data"].get("accepted", 0) for e in verifies)
        routed = collections.Counter(
            e["data"].get("klass") for e in evs if e["kind"] == "routed")
        out[tname] = {
            "engines": engs,
            "requests_finished": sum(
                1 for e in evs if e["kind"] == "finished"),
            "routed": dict(sorted(routed.items())),
            "spills": sum(1 for e in evs if e["kind"] == "routed"
                          and e["data"].get("spill")),
            "ttft_mean_s": (round(sum(ttfts) / len(ttfts), 6)
                            if ttfts else None),
            "acceptance_rate": (round(accepted / drafted, 4)
                                if drafted else None),
            "capacity_stalls": sum(
                1 for e in evs if e["kind"] == "capacity_stall"),
            "prefix_hits": sum(1 for e in evs if e["kind"] == "prefix_hit"),
            "prefix_import_blocks": sum(
                e["data"].get("blocks", 0) for e in evs
                if e["kind"] == "prefix_import"),
            "top_decode_gaps": _stall_attribution(evs, top=3),
        }
    return out


def _window_summary(events: list[dict]) -> dict | None:
    xs = sorted(e["data"]["gen_tok_per_s"] for e in events
                if e["kind"] == "metrics_window"
                and "gen_tok_per_s" in e["data"])
    if not xs:
        return None
    return {"samples": len(xs), "gen_tok_per_s_min": xs[0],
            "gen_tok_per_s_p50": xs[len(xs) // 2],
            "gen_tok_per_s_max": xs[-1]}


def report(events: list[dict]) -> dict:
    kinds = collections.Counter(e["kind"] for e in events)
    return {"events": len(events), "kinds": dict(sorted(kinds.items())),
            "requests": _request_timelines(events),
            "top_decode_gaps": _stall_attribution(events),
            "speculative": _speculative_summary(events),
            "probe": _probe_trend(events),
            "shadow": _shadow_summary(events),
            "windows": _window_summary(events),
            "robustness": _robustness_summary(events),
            "fleet": _fleet_summary(events)}


def _rid_s(rid) -> str:
    """rids are ints (single trace) or ``"engine:rid"`` strings (merged
    fleet traces) — format either without breaking old output."""
    return f"{rid:4d}" if isinstance(rid, int) else f"{rid:>16}"


def _print_human(rep: dict) -> None:
    print(f"{rep['events']} events: "
          + ", ".join(f"{k}={v}" for k, v in rep["kinds"].items()))
    print("\nper-request timelines:")
    for rid, r in sorted(rep["requests"].items()):
        wait = (f"{r['queue_wait_s']*1e3:8.2f}ms"
                if r["queue_wait_s"] is not None else "       ?")
        print(f"  req {_rid_s(rid)}  wait {wait}  "
              f"prefill {r['prefill_chunks']:3d} chunks "
              f"({r['prefill_s']*1e3:8.2f}ms)  "
              f"decode {r['decode_steps']:3d} steps "
              f"({r['decode_s']*1e3:8.2f}ms)  "
              f"span {r['span_s']*1e3:8.2f}ms  "
              f"[{r['finish_reason'] or 'running'}]"
              + (f"  prefix_hit={r['prefix_hit_tokens']}"
                 if r["prefix_hit_tokens"] else "")
              + (f"  spec {r['accepted']}/{r['drafted']} accepted "
                 f"({r['spec_rounds']} rounds)"
                 if r["spec_rounds"] else ""))
    if rep["top_decode_gaps"]:
        print("\nlargest inter-decode gaps:")
        for g in rep["top_decode_gaps"]:
            print(f"  req {_rid_s(g['rid'])}  {g['gap_s']*1e3:8.2f}ms at "
                  f"t={g['t']:.3f}s  cause={g['cause']}"
                  + (f" ({g['interfering_chunks']} chunks)"
                     if g["interfering_chunks"] else ""))
    if rep["speculative"]:
        s = rep["speculative"]
        rate = (f"{s['acceptance_rate']:.2%}"
                if s["acceptance_rate"] is not None else "n/a")
        print(f"\nspeculative decode: {s['rounds']} verify rounds, "
              f"{s['accepted']}/{s['drafted']} drafts accepted ({rate})")
    if rep["probe"]:
        p = rep["probe"]
        print(f"\nerror probe: {p['runs']} runs, logits_err_var "
              f"{p['first']['logits_err_var']:.3e} (first) -> "
              f"{p['last']['logits_err_var']:.3e} (last), "
              f"range [{p['logits_err_var_min']:.3e}, "
              f"{p['logits_err_var_max']:.3e}]")
    if rep["shadow"]:
        sh = rep["shadow"]
        rate = (f"{sh['token_match_rate']:.2%}"
                if sh["token_match_rate"] is not None else "n/a")
        print(f"\nshadow A/B: {sh['replays']} replays, "
              f"{sh['token_matches']}/{sh['tokens']} tokens matched "
              f"({rate}), replay cost {sh['replay_time_s']*1e3:.2f}ms")
    if rep["windows"]:
        w = rep["windows"]
        print(f"\nwindowed gen tok/s: {w['samples']} samples, "
              f"min {w['gen_tok_per_s_min']} / p50 {w['gen_tok_per_s_p50']} "
              f"/ max {w['gen_tok_per_s_max']}")
    if rep["robustness"]:
        rb = rep["robustness"]
        print(f"\nrobustness: faults_detected={rb['faults_detected']} "
              f"quarantines={rb['quarantines']} "
              f"(replayed {rb['replayed_tokens']} tokens), "
              f"deadline evictions={rb['deadline_evictions']} "
              f"finishes={rb['deadline_finishes']}")
        for s in rb["governor_switches"]:
            ev = (f"{s['err_var']:.3e}" if isinstance(s["err_var"], float)
                  else s["err_var"])
            layer = f"  layer={s['layer']}" if s.get("layer") else ""
            print(f"  step {s['step']:5}  {s['action']:8} "
                  f"{s['from']} -> {s['to']}  [{s['reason']}]{layer}  "
                  f"err_var={ev}  power_delta={s['power_delta_pct']}%")
    if rep["fleet"]:
        print("\nfleet (per tier):")
        for tname, t in rep["fleet"].items():
            ttft = (f"{t['ttft_mean_s']*1e3:.2f}ms"
                    if t["ttft_mean_s"] is not None else "n/a")
            acc = (f"{t['acceptance_rate']:.2%}"
                   if t["acceptance_rate"] is not None else "n/a")
            print(f"  tier {tname}: {len(t['engines'])} replicas, "
                  f"{t['requests_finished']} finished, "
                  f"routed={t['routed']} spills={t['spills']}, "
                  f"ttft {ttft}, acceptance {acc}, "
                  f"stalls={t['capacity_stalls']}, "
                  f"prefix hits={t['prefix_hits']} "
                  f"imported_blocks={t['prefix_import_blocks']}")
            for g in t["top_decode_gaps"]:
                print(f"    gap {_rid_s(g['rid'])}  "
                      f"{g['gap_s']*1e3:8.2f}ms  cause={g['cause']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Summarize serving span traces (JSONL or Chrome JSON)")
    ap.add_argument("trace", nargs="*",
                    help="trace file(s) written by --trace-out / --trace-dir")
    ap.add_argument("--trace", action="append", dest="traces", default=[],
                    metavar="FILE",
                    help="additional trace file; repeatable (several files "
                         "= a fleet: rids get engine-id prefixes and the "
                         "report gains a per-tier fleet section)")
    ap.add_argument("--format", choices=("text", "json"), default=None,
                    help="output format (default: text)")
    ap.add_argument("--json", action="store_true",
                    help="alias for --format json (kept for old scripts)")
    ap.add_argument("--assert-lifecycle", action="store_true",
                    help="fail unless >= 1 span of every lifecycle stage "
                         f"{list(LIFECYCLE)} is present")
    ap.add_argument("--assert-quarantine", action="store_true",
                    help="fail unless the trace holds >= 1 fault_detected "
                         "span and every one is matched by a quarantine "
                         "span (the fault-injection smoke gate)")
    args = ap.parse_args(argv)
    paths = list(args.trace) + list(args.traces)
    if not paths:
        ap.error("no trace files given (positional or --trace)")
    events = load_traces(paths)
    rep = report(events)
    fmt = args.format or ("json" if args.json else "text")
    if fmt == "json":
        print(json.dumps(rep, indent=2))
    else:
        _print_human(rep)
    if args.assert_lifecycle:
        missing = [k for k in LIFECYCLE if not rep["kinds"].get(k)]
        if missing:
            print(f"\nFAIL: lifecycle stages missing from trace: {missing}",
                  file=sys.stderr)
            return 2
        print("\nlifecycle OK: "
              + ", ".join(f"{k}={rep['kinds'][k]}" for k in LIFECYCLE))
    if args.assert_quarantine:
        detected = rep["kinds"].get("fault_detected", 0)
        quars = rep["kinds"].get("quarantine", 0)
        if not detected or quars < detected:
            print(f"\nFAIL: quarantine gate: fault_detected={detected} "
                  f"quarantine={quars} (need >= 1 detection, all "
                  "quarantined)", file=sys.stderr)
            return 3
        print(f"\nquarantine OK: {detected} detected, {quars} quarantined")
    return 0


if __name__ == "__main__":
    sys.exit(main())
