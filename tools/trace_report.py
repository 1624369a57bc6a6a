"""Summarize (or validate) a PAPI engine trace.

    python tools/trace_report.py out.trace.json            # human summary
    python tools/trace_report.py out.trace.json --validate # CI schema gate

Reads either trace serialization `repro.serving.telemetry.write_trace`
produces — Chrome-trace-event JSON (autodetected by its ``traceEvents``
key; the typed payload rides in each event's ``args`` and the aggregate
tables under the top-level ``"papi"`` key) or raw JSONL (one typed event
per line plus a trailing ``summary`` record) — and prints:

  * host seconds by engine phase (`engine.admit`, `engine.fetch`, `gc`,
    ...: the typed ``phase`` spans, summed by name) — device time per
    compiled program lives in the profiler's trace instead
    (`python3 bench/trace_reduce.py <dir>`);
  * the scheduler flip timeline: every pu<->pim reschedule with the AI
    estimate and the alpha threshold that flipped it;
  * page-pool occupancy: high-water mark and the peak sampled used/free;
  * per-request span summaries: queue (submit->admit) -> prefill
    (admit->first token) -> decode (first token->finish), with the finish
    reason and token count.

``--validate`` (used by CI) checks the schema instead: every event kind
must be in the vocabulary, the aggregate tables must be well-formed, and
the trace must contain a nonzero number of scheduler decisions and
iteration spans — exit 1 with a message otherwise.

Deliberately stdlib-only (no jax, no repro imports): the report must run
anywhere a trace file lands, so it keeps its OWN copy of the event
vocabulary, mirrored from `repro.serving.telemetry.EVENT_KINDS` (the
telemetry tests assert the two stay in sync).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

# mirror of repro.serving.telemetry.EVENT_KINDS (tests assert equality)
EVENT_KINDS = frozenset({
    "submit", "admit", "first_token", "finish", "preempt", "defer",
    "scheduler", "iteration", "pool", "fault", "degraded", "phase",
    "page_map", "page_unmap", "page_reserve", "stall", "journal", "recover",
})


def load_trace(path: Path) -> tuple[list[dict], dict]:
    """Parse either serialization into (typed events, aggregate summary).

    Events are normalized to ``{"kind", "iteration", "ts", "dur", "data"}``
    with ts/dur in seconds; the summary dict carries ``counters``,
    ``gauges``, ``events_emitted``, ``events_dropped``.
    """
    text = path.read_text()
    head = text.lstrip()[:1]
    if head == "{" and '"traceEvents"' in text[:4096]:
        doc = json.loads(text)
        events = []
        for rec in doc.get("traceEvents", []):
            args = rec.get("args") or {}
            kind = args.get("kind")
            if rec.get("ph") == "C" and rec.get("name") == "kv_pages":
                # pool samples export as a Perfetto counter track whose args
                # must stay numeric-only — recover the typed event here
                events.append({"kind": "pool", "iteration": 0,
                               "ts": rec.get("ts", 0) / 1e6, "dur": 0.0,
                               "data": dict(args)})
                continue
            if rec.get("ph") == "M" or kind is None:
                continue   # lane-metadata records
            data = {k: v for k, v in args.items()
                    if k not in ("kind", "iteration")}
            events.append({"kind": kind,
                           "iteration": args.get("iteration", 0),
                           "ts": rec.get("ts", 0) / 1e6,
                           "dur": rec.get("dur", 0) / 1e6,
                           "data": data})
        return events, doc.get("papi", {})
    events, summary = [], {}
    for line in text.splitlines():
        if not line.strip():
            continue
        rec = json.loads(line)
        if rec.get("kind") == "summary":
            summary = rec.get("data", {})
        else:
            events.append(rec)
    return events, summary


def validate(events: list[dict], summary: dict) -> list[str]:
    """Schema + liveness checks for the CI gate; returns failure messages."""
    problems = []
    for i, ev in enumerate(events):
        kind = ev.get("kind")
        if kind not in EVENT_KINDS:
            problems.append(f"event {i}: unknown kind {kind!r}")
        for field in ("iteration", "ts", "dur"):
            if not isinstance(ev.get(field), (int, float)):
                problems.append(f"event {i} ({kind}): non-numeric {field}")
        if not isinstance(ev.get("data"), dict):
            problems.append(f"event {i} ({kind}): data is not an object")
        if problems and len(problems) > 20:
            problems.append("... (truncated)")
            break
    counters = summary.get("counters", {})
    if not isinstance(counters, dict):
        problems.append("summary counters table missing")
        return problems
    # liveness: a trace of a real run must contain scheduler decisions and
    # iteration spans — zero of either means the engine hooks regressed.
    # Counts come from the aggregate counters (exact under ring truncation;
    # the chrome lanes only carry the FLIPPED scheduler decisions).
    n_sched = counters.get("scheduler", 0)
    n_iter = counters.get("iteration", 0)
    if n_sched <= 0:
        problems.append(f"no scheduler-decision events (counter {n_sched})")
    if n_iter <= 0:
        problems.append(f"no iteration-span events (counter {n_iter})")
    return problems


def _fmt_s(seconds: float) -> str:
    if seconds >= 1.0:
        return f"{seconds:8.3f}s "
    return f"{seconds * 1e3:8.3f}ms"


def report(events: list[dict], summary: dict, out=None) -> None:
    out = out or sys.stdout
    w = out.write
    counters = summary.get("counters", {})
    gauges = summary.get("gauges", {})

    w(f"events: {summary.get('events_emitted', len(events))} emitted, "
      f"{summary.get('events_dropped', 0)} dropped from the ring "
      f"({len(events)} in file)\n")
    w(f"iterations: {counters.get('iteration', 0)}   "
      f"tokens: {counters.get('tokens', 0)}   "
      f"degraded: {counters.get('degraded', 0)}   "
      f"preemptions: {counters.get('preempt', 0)}   "
      f"deferrals: {counters.get('defer', 0)}\n")

    # ---- host time by engine phase (typed phase spans in the file) ----
    phases: dict[str, list[float]] = {}
    for ev in events:
        if ev["kind"] == "phase":
            phases.setdefault(ev["data"].get("name", "?"), []).append(
                ev["dur"])
    if phases:
        w("\nhost time by engine phase (spans in the file):\n")
        w(f"  {'phase':22s} {'spans':>6s} {'mean':>10s} {'total':>10s}\n")
        for name, durs in sorted(phases.items(), key=lambda kv: -sum(kv[1])):
            w(f"  {name:22s} {len(durs):6d} "
              f"{_fmt_s(sum(durs) / len(durs))} {_fmt_s(sum(durs))}\n")

    # ---- scheduler flip timeline ----
    flips = [ev for ev in events
             if ev["kind"] == "scheduler" and ev["data"].get("flipped")]
    w(f"\nscheduler: {counters.get('scheduler', 0)} decisions, "
      f"{counters.get('scheduler_flip', len(flips))} flips\n")
    for ev in flips:
        d = ev["data"]
        w(f"  iter {ev['iteration']:5d}: -> {d.get('assignment', '?'):4s} "
          f"(AI {d.get('ai_estimate', 0):.1f} vs alpha "
          f"{d.get('alpha', 0):.1f}, rlp={d.get('rlp')}, "
          f"tlp={d.get('tlp')})\n")

    # ---- pool occupancy ----
    pool = [ev for ev in events if ev["kind"] == "pool"]
    if pool or any(k.startswith("kv_pages") for k in gauges):
        peak = max((ev["data"].get("used", 0) for ev in pool), default=0)
        w(f"\nkv page pool: high-water "
          f"{gauges.get('kv_pages_watermark', peak)} pages mapped "
          f"(peak sampled used {peak}, last free "
          f"{gauges.get('kv_pages_free', '?')}, fragmentation "
          f"{gauges.get('kv_pages_fragmentation', 0):.1%})\n")

    # ---- per-request spans: queue -> prefill -> decode -> finish ----
    marks: dict[int, dict] = {}
    for ev in events:
        rid = ev["data"].get("req_id")
        if rid is None or ev["kind"] not in ("submit", "admit",
                                            "first_token", "finish",
                                            "preempt"):
            continue
        m = marks.setdefault(rid, {})
        if ev["kind"] == "preempt":
            m["preempts"] = m.get("preempts", 0) + 1
        elif ev["kind"] not in m:     # first occurrence wins (preemption
            m[ev["kind"]] = ev        # re-admits through the same hooks)
        elif ev["kind"] == "finish":
            m["finish"] = ev          # ...except finish: last wins
    if marks:
        w(f"\nper-request spans ({len(marks)} requests, iterations "
          "[wall]):\n")
        w(f"  {'req':>5s} {'queue':>7s} {'prefill':>8s} {'decode':>7s} "
          f"{'total':>7s}  {'tokens':>6s}  reason\n")
        for rid in sorted(marks):
            m = marks[rid]
            sub, adm = m.get("submit"), m.get("admit")
            ft, fin = m.get("first_token"), m.get("finish")

            def span(a, b):
                if a is None or b is None:
                    return "     --"
                return f"{b['iteration'] - a['iteration']:7d}"

            toks = fin["data"].get("tokens", 0) if fin else 0
            reason = fin["data"].get("reason", "in-flight") if fin else \
                "in-flight"
            if m.get("preempts"):
                reason += f" ({m['preempts']}x preempted)"
            w(f"  {rid:5d} {span(sub, adm)} {span(adm, ft):>8s} "
              f"{span(ft, fin)} {span(sub, fin)}  {toks:6d}  {reason}\n")

    faults = {k.split(':', 1)[1]: v for k, v in counters.items()
              if k.startswith("fault:")}
    if faults or counters.get("stall"):
        w(f"\nfaults fired: {faults}   stalls: {counters.get('stall', 0)}\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="chrome-trace JSON or JSONL file written "
                                  "by --trace / write_trace()")
    ap.add_argument("--validate", action="store_true",
                    help="schema-check only (CI gate): exit 1 unless every "
                         "event kind is known and the trace holds nonzero "
                         "scheduler decisions and iteration spans")
    args = ap.parse_args(argv)
    path = Path(args.trace)
    if not path.exists():
        print(f"trace_report: {path} not found", file=sys.stderr)
        return 1
    try:
        events, summary = load_trace(path)
    except (json.JSONDecodeError, KeyError, TypeError) as err:
        print(f"trace_report: cannot parse {path}: {err}", file=sys.stderr)
        return 1
    if args.validate:
        problems = validate(events, summary)
        if problems:
            for p in problems:
                print(f"trace_report INVALID: {p}", file=sys.stderr)
            return 1
        counters = summary.get("counters", {})
        print(f"trace_report: {path} valid — {len(events)} events, "
              f"{counters.get('scheduler', 0)} scheduler decisions, "
              f"{counters.get('iteration', 0)} iteration spans, "
              f"{counters.get('phase', 0)} phase spans")
        return 0
    report(events, summary)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
