"""Engine telemetry: typed event traces, phase spans, exporters.

PAPI's whole mechanism is *online kernel characterization* — the runtime
watches per-kernel behavior and schedules compute-bound vs memory-bound
work accordingly (§5.2).  The engine therefore needs an observation layer
that is always available and (nearly) free when off:

  * `Tracer` — a bounded ring buffer of typed `Event`s: iteration and
    phase spans, scheduler decisions (estimate + threshold, not
    just the verdict), preemptions, deferrals, fault injections, degraded
    re-runs, page-pool occupancy samples, and per-request lifecycle marks
    (submit / admit / first_token / finish).  The buffer keeps the NEWEST
    events and counts what it dropped; aggregate counters live outside
    the ring, so exports stay exact under truncation.
  * `phase(name, tracer, **meta)` — one named span of engine work.  It
    enters `jax.profiler.TraceAnnotation` whenever a profiler session is
    open, so every engine phase (admission, input preparation, dispatch,
    fetch, commit, park, accounting, GC pauses) lands in the profiler's
    trace on the device trace's clock, where a reduction can read the host
    time between device programs; with no session open it costs one
    TraceMe check and two clock reads.  Under an enabled `Tracer` the same span is also emitted as a
    typed ``phase`` event (the engine step as its ``iteration`` event).
    Device time per compiled program comes from the profiler trace, under
    the programs' own names (``papi_decode_step``, ``papi_mixed_wave``,
    ...), never from a host clock around a blocking call.
  * `NullTracer` — the engine default.  Every hook is a no-op, so the
    traced-off hot path is the bare dispatch (gated by the
    traced-vs-untraced A/B in ``benchmarks/engine_hotpath.py --arrivals
    --trace``).

Exporters (one event vocabulary, three views — see docs/ARCHITECTURE.md,
"Observability & telemetry"):

  * `export_chrome` — Chrome-trace-event JSON (`{"traceEvents": [...]}`),
    opens in Perfetto / chrome://tracing.  One lane per engine slot
    (request residency spans + first-token marks), one for the scheduler
    (iteration spans named by the chosen FC variant with their phase
    spans nested inside, flip instants), one for the page pool (a counter
    track), one for the queue (submit/defer/fault instants).  The
    full typed-event payload rides in each event's ``args`` and the
    aggregate tables under a top-level ``"papi"`` key, so
    `tools/trace_report.py` reads the same facts from either format.
  * `export_prometheus` — text-exposition snapshot of ``papi_engine_*``
    counters/gauges derived from the same events (iterations, tokens,
    finishes by reason, preemptions, deferrals, degraded steps, faults by
    kind, scheduler flips, pool occupancy).
  * `export_jsonl` — the raw typed events, one JSON object per line, with
    a trailing ``summary`` record carrying the aggregate tables.

Both the offline `PapiEngine.run()` and the streaming `serve()` loop emit
the same vocabulary, so one trace format covers every engine mode
(dense/paged x greedy/spec x mesh x faults).
"""
from __future__ import annotations

import dataclasses
import functools
import json
import time
from collections import Counter, deque
from typing import Iterable

from jax.profiler import StepTraceAnnotation, TraceAnnotation

# The event vocabulary.  `tools/trace_report.py` validates traces against
# this set, so additions here must be mirrored there (it keeps its own
# copy: the report tool is stdlib-only and must not import jax transitively).
EVENT_KINDS = frozenset({
    "submit",        # request entered the queue        {req_id, prompt_len, max_new}
    "admit",         # request first admitted to a slot {req_id, slot, prompt_len}
    "first_token",   # request's first output token     {req_id}
    "finish",        # result emitted                   {req_id, reason, tokens, slot}
    "preempt",       # in-flight request preempted      {req_id, slot, done}
    "defer",         # queue head deferred by the pool  {req_id, age}
    "scheduler",     # per-iteration decision           {ai_estimate, alpha,
                     #   assignment, flipped, rlp, tlp}
    "iteration",     # span: one engine step            {IterStats fields}
    "pool",          # page-pool occupancy sample       {used, free, watermark,
                     #   fragmentation}
    "fault",         # an injected fault fired          {fault, ...}
    "degraded",      # finite-logits guard re-ran the   {mode: step|wave}
                     #   step on the oracle path
    "phase",         # span: one engine phase           {name, ...}
                     #   (engine.admit, engine.fetch, gc, ...)
    "page_map",      # allocator mapped pages           {slot, pages}
    "page_unmap",    # allocator returned pages         {slot, pages, cause}
    "page_reserve",  # admission reserved a budget      {slot, budget_pages,
                     #   mapped_pages}
    "stall",         # EngineStallError snapshot        {snapshot}
    "journal",       # WAL lifecycle                    {op, path, ...}
                     #   op="open" (torn tail truncated) / "snapshot"
    "recover",       # restore() re-admitted work       {path, resumed,
                     #   finished, records, torn_bytes, next_req_id}
})


@dataclasses.dataclass
class Event:
    """One typed trace event.  ``ts`` is seconds on the tracer's clock
    (zero at `Tracer` construction); ``dur`` is nonzero for spans."""
    kind: str
    iteration: int
    ts: float
    dur: float = 0.0
    data: dict = dataclasses.field(default_factory=dict)


@functools.lru_cache(maxsize=None)
def format_program_key(key: tuple) -> str:
    """Stable string form of a jit-cache key for export/labels, e.g.
    ``('spec_fused', 4, 'pim', None, False)`` -> ``spec_fused|4|pim|-|-``
    (None and False compress to '-': most keys are mostly defaults)."""
    return "|".join("-" if part in (None, False) else str(part)
                    for part in key)


class Tracer:
    """Bounded typed-event trace.

    ``capacity`` bounds the event ring (the NEWEST events are kept;
    ``dropped`` counts the truncated prefix).  Aggregate ``counters`` and
    ``gauges`` are maintained at emit time, outside the ring, so the
    Prometheus snapshot and the report tool's tables stay exact regardless
    of truncation.

    ``page_events=True`` opts into the allocator's per-call
    map/unmap/reserve events even without ``debug_invariants`` (they are
    the highest-volume kind; the engine attaches the tracer to the page
    manager only when one of the two flags asks for them).
    """

    enabled = True

    def __init__(self, capacity: int = 65536, *, page_events: bool = False):
        assert capacity >= 1, capacity
        self.capacity = int(capacity)
        self.page_events = bool(page_events)
        self._events: deque[Event] = deque(maxlen=self.capacity)
        self.emitted = 0
        self.iteration = 0           # engine refreshes this every step
        self.counters: Counter = Counter()
        self.gauges: dict[str, float] = {}
        self._t0 = time.perf_counter()

    # ------------------------------------------------------------ emission
    @property
    def events(self) -> Iterable[Event]:
        return self._events

    @property
    def dropped(self) -> int:
        return self.emitted - len(self._events)

    def _now(self) -> float:
        return time.perf_counter() - self._t0

    def emit(self, kind: str, iteration: int | None = None, *,
             ts: float | None = None, dur: float = 0.0, **data) -> Event:
        """Append one typed event (newest-wins ring) and fold it into the
        aggregate counters/gauges."""
        ev = Event(kind,
                   self.iteration if iteration is None else int(iteration),
                   self._now() if ts is None else ts, dur, data)
        self._events.append(ev)
        self.emitted += 1
        self.counters[kind] += 1
        if kind == "finish":
            self.counters[f"finish:{data.get('reason', 'unknown')}"] += 1
        elif kind == "fault":
            self.counters[f"fault:{data.get('fault', 'unknown')}"] += 1
        elif kind == "scheduler" and data.get("flipped"):
            self.counters["scheduler_flip"] += 1
        elif kind == "iteration":
            self.counters["tokens"] += int(data.get("new_tokens", 0))
        elif kind == "pool":
            for field in ("used", "free", "watermark", "fragmentation"):
                if field in data:
                    self.gauges[f"kv_pages_{field}"] = data[field]
        return ev


class NullTracer:
    """The engine default: every hook is a no-op.  Shares the read surface
    (events/counters/gauges/...) so exporters degrade gracefully on an
    untraced engine."""

    enabled = False
    page_events = False
    iteration = 0
    emitted = 0
    dropped = 0
    events: tuple = ()
    counters: dict = {}
    gauges: dict = {}

    def emit(self, kind, iteration=None, *, ts=None, dur=0.0, **data):
        return None


NULL_TRACER = NullTracer()


class phase:
    """One named span of engine work, written to the profiler's trace and,
    under an enabled `Tracer`, to its ring.

    While a profiler session is open, entering enters
    `jax.profiler.TraceAnnotation(name, **meta)`; with ``step`` set,
    `StepTraceAnnotation(name, step_num=step)` instead, so a reduction of
    the profiler trace can join the step's spans to its `IterStats` line.
    With no session open that is one TraceMe check.  When the block exits
    without an exception and the tracer is enabled, the same span is
    emitted as the typed event ``kind``: ``phase`` carries
    ``{name, **meta}``, any other kind (the engine step's ``iteration``)
    just ``meta``.  ``set(**meta)`` adds
    metadata known only inside the block, such as an admitted count.
    ``start`` is the `time.perf_counter()` value at entry.
    """

    __slots__ = ("name", "tracer", "kind", "step", "meta", "start", "_ann")

    def __init__(self, name: str, tracer=NULL_TRACER, *,
                 kind: str = "phase", step: int | None = None, **meta):
        self.name, self.tracer, self.kind = name, tracer, kind
        self.step, self.meta = step, meta
        self.start = 0.0
        self._ann = None

    def __enter__(self) -> "phase":
        if TraceAnnotation.is_enabled():
            if self.step is None:
                self._ann = TraceAnnotation(self.name, **self.meta)
            else:
                self._ann = StepTraceAnnotation(
                    self.name, step_num=self.step, **self.meta)
            self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def set(self, **meta) -> None:
        self.meta.update(meta)
        if self._ann is not None:
            self._ann.set_metadata(**meta)

    def __exit__(self, exc_type, exc, tb) -> bool:
        end = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        if exc_type is None and self.tracer.enabled:
            data = (dict(self.meta, name=self.name) if self.kind == "phase"
                    else self.meta)
            self.tracer.emit(self.kind, ts=self.start - self.tracer._t0,
                             dur=end - self.start, **data)
        return False


# --------------------------------------------------------------- exporters
def _jsonable(obj):
    """json.dumps default= hook: numpy scalars -> python, else str."""
    item = getattr(obj, "item", None)
    if callable(item):
        try:
            return item()
        except (TypeError, ValueError):
            pass
    return str(obj)


# Chrome lane (tid) layout inside pid 1 ("papi-engine").  Slot lanes start
# at SLOT_TID0 so any max_slots fits after the fixed lanes.
SCHED_TID, POOL_TID, QUEUE_TID, SLOT_TID0 = 1, 2, 4, 10
_PID = 1


def export_chrome(tracer) -> dict:
    """Chrome-trace-event JSON (the ``traceEvents`` array format).

    Opens directly in Perfetto (ui.perfetto.dev) or chrome://tracing:
    iteration spans on the scheduler lane are named by the FC variant the
    scheduler chose (the pu<->pim flip pattern is visible at a glance,
    flips marked as instants) and hold the step's phase spans, each slot
    lane shows request residency spans with first-token marks, and the
    pool lane is a page-occupancy counter track.  The typed payload of
    every event rides in ``args`` (with its ``kind``), and the aggregate
    counter/gauge tables under the top-level ``"papi"`` key —
    `tools/trace_report.py` consumes those rather than re-deriving from
    the lanes.
    """
    out: list[dict] = []

    def meta(tid: int, name: str) -> None:
        out.append({"ph": "M", "pid": _PID, "tid": tid, "ts": 0,
                    "name": "thread_name", "args": {"name": name}})

    out.append({"ph": "M", "pid": _PID, "tid": 0, "ts": 0,
                "name": "process_name", "args": {"name": "papi-engine"}})
    meta(SCHED_TID, "scheduler")
    meta(POOL_TID, "kv-page-pool")
    meta(QUEUE_TID, "queue")

    def us(ts: float) -> float:
        return ts * 1e6

    open_slots: dict[int, dict] = {}   # slot -> open residency span
    slot_lanes: set[int] = set()
    last_ts = 0.0

    def base(ev: Event, tid: int, ph: str, name: str) -> dict:
        return {"ph": ph, "pid": _PID, "tid": tid, "ts": us(ev.ts),
                "name": name,
                "args": {"kind": ev.kind, "iteration": ev.iteration,
                         **ev.data}}

    def close_slot(slot: int, ts: float, name_suffix: str = "") -> None:
        span = open_slots.pop(slot, None)
        if span is None:
            return
        span["dur"] = max(us(ts) - span["ts"], 0.0)
        span["name"] += name_suffix
        out.append(span)

    for ev in tracer.events:
        last_ts = max(last_ts, ev.ts + ev.dur)
        if ev.kind == "iteration":
            rec = base(ev, SCHED_TID, "X",
                       f"fc={ev.data.get('fc_variant', '?')}")
            rec["dur"] = us(ev.dur)
            out.append(rec)
        elif ev.kind == "scheduler":
            if ev.data.get("flipped"):
                rec = base(ev, SCHED_TID, "i",
                           f"flip->{ev.data.get('assignment')}")
                rec["s"] = "t"
                out.append(rec)
        elif ev.kind == "pool":
            rec = base(ev, POOL_TID, "C", "kv_pages")
            rec["args"] = {"used": ev.data.get("used", 0),
                           "free": ev.data.get("free", 0)}
            out.append(rec)
        elif ev.kind == "phase":
            rec = base(ev, SCHED_TID, "X", ev.data.get("name", "phase"))
            rec["dur"] = us(ev.dur)
            out.append(rec)
        elif ev.kind == "admit":
            slot = ev.data.get("slot")
            if slot is not None:
                tid = SLOT_TID0 + int(slot)
                slot_lanes.add(int(slot))
                close_slot(int(slot), ev.ts)   # defensive: no dangling span
                open_slots[int(slot)] = base(
                    ev, tid, "X", f"req {ev.data.get('req_id')}")
        elif ev.kind in ("finish", "preempt"):
            slot = ev.data.get("slot")
            suffix = " (preempted)" if ev.kind == "preempt" else ""
            if slot is not None:
                close_slot(int(slot), ev.ts, suffix)
            rec = base(ev, QUEUE_TID, "i", f"{ev.kind} "
                       f"req {ev.data.get('req_id')}")
            rec["s"] = "t"
            out.append(rec)
        elif ev.kind == "first_token":
            rec = base(ev, QUEUE_TID, "i",
                       f"first_token req {ev.data.get('req_id')}")
            rec["s"] = "t"
            out.append(rec)
        elif ev.kind in ("submit", "defer", "fault", "degraded", "stall",
                         "page_map", "page_unmap", "page_reserve",
                         "journal", "recover"):
            rec = base(ev, QUEUE_TID, "i", ev.kind)
            rec["s"] = "t"
            out.append(rec)

    for slot in list(open_slots):
        close_slot(slot, last_ts, " (open)")
    for slot in sorted(slot_lanes):
        meta(SLOT_TID0 + slot, f"slot {slot}")

    return {
        "traceEvents": out,
        "displayTimeUnit": "ms",
        "papi": {
            "counters": dict(tracer.counters),
            "gauges": dict(tracer.gauges),
            "events_emitted": tracer.emitted,
            "events_dropped": tracer.dropped,
        },
    }


def export_jsonl(tracer) -> str:
    """Raw typed events, one JSON object per line, newest-ring contents in
    order, with a trailing ``summary`` record carrying the aggregate
    tables (exact under ring truncation)."""
    lines = []
    for ev in tracer.events:
        lines.append(json.dumps(
            {"kind": ev.kind, "iteration": ev.iteration, "ts": ev.ts,
             "dur": ev.dur, "data": ev.data},
            default=_jsonable, sort_keys=True))
    lines.append(json.dumps(
        {"kind": "summary", "iteration": tracer.iteration,
         "ts": 0.0, "dur": 0.0,
         "data": {"counters": dict(tracer.counters),
                  "gauges": dict(tracer.gauges),
                  "events_emitted": tracer.emitted,
                  "events_dropped": tracer.dropped}},
        default=_jsonable, sort_keys=True))
    return "\n".join(lines) + "\n"


def _prom_escape(value: str) -> str:
    return value.replace("\\", "\\\\").replace('"', '\\"')


def export_prometheus(tracer) -> str:
    """Prometheus text-exposition snapshot of ``papi_engine_*`` series,
    derived from the tracer's aggregate counters/gauges (NOT the ring, so
    truncation never undercounts).  Counter series end in ``_total``;
    pool occupancy is a gauge."""
    c, g = tracer.counters, tracer.gauges
    lines: list[str] = []

    def metric(name: str, mtype: str, help_text: str,
               samples: list[tuple[str, float]]) -> None:
        lines.append(f"# HELP {name} {help_text}")
        lines.append(f"# TYPE {name} {mtype}")
        for labels, value in samples:
            lines.append(f"{name}{labels} {value}")

    metric("papi_engine_iterations_total", "counter",
           "Engine iterations executed.", [("", c.get("iteration", 0))])
    # one labelled sample per EVENT_KINDS member, zero-filled, so the
    # exposition always covers the full event vocabulary (PL005's runtime
    # counterpart: a new kind shows up here without any exporter edit)
    metric("papi_engine_events_total", "counter",
           "Telemetry events recorded, by event kind.",
           [(f'{{kind="{_prom_escape(k)}"}}', c.get(k, 0))
            for k in sorted(EVENT_KINDS)])
    metric("papi_engine_tokens_total", "counter",
           "Output tokens committed.", [("", c.get("tokens", 0))])
    reasons = sorted(k.split(":", 1)[1] for k in c if k.startswith("finish:"))
    metric("papi_engine_requests_finished_total", "counter",
           "Requests finished, by finished_reason.",
           [(f'{{reason="{_prom_escape(r)}"}}', c[f"finish:{r}"])
            for r in reasons] or [("", 0)])
    metric("papi_engine_preemptions_total", "counter",
           "In-flight requests preempted under pool pressure.",
           [("", c.get("preempt", 0))])
    metric("papi_engine_deferrals_total", "counter",
           "Iterations the queue head was deferred by the pool.",
           [("", c.get("defer", 0))])
    metric("papi_engine_degraded_steps_total", "counter",
           "Iterations re-run on the oracle path by the finite-logits "
           "guard.", [("", c.get("degraded", 0))])
    kinds = sorted(k.split(":", 1)[1] for k in c if k.startswith("fault:"))
    metric("papi_engine_faults_injected_total", "counter",
           "Injected faults fired, by kind.",
           [(f'{{kind="{_prom_escape(k)}"}}', c[f"fault:{k}"])
            for k in kinds] or [("", 0)])
    metric("papi_engine_scheduler_flips_total", "counter",
           "Scheduler FC-path reschedules (pu<->pim).",
           [("", c.get("scheduler_flip", 0))])
    metric("papi_engine_kv_pages_used", "gauge",
           "KV pool pages holding live KV (latest sample).",
           [("", g.get("kv_pages_used", 0))])
    metric("papi_engine_kv_pages_free", "gauge",
           "KV pool pages on the free list (latest sample).",
           [("", g.get("kv_pages_free", 0))])
    metric("papi_engine_kv_page_watermark", "gauge",
           "Peak KV pool pages mapped over the engine lifetime.",
           [("", g.get("kv_pages_watermark", 0))])
    metric("papi_engine_kv_fragmentation", "gauge",
           "Tail-of-page waste share of mapped rows (latest sample).",
           [("", g.get("kv_pages_fragmentation", 0.0))])
    metric("papi_engine_trace_events_total", "counter",
           "Typed trace events emitted.", [("", tracer.emitted)])
    metric("papi_engine_trace_events_dropped_total", "counter",
           "Events truncated out of the ring buffer.",
           [("", tracer.dropped)])
    return "\n".join(lines) + "\n"


def write_trace(tracer, path, fmt: str = "chrome") -> None:
    """Serialize the trace to ``path``: ``chrome`` (Perfetto-openable JSON)
    or ``jsonl`` (raw typed events)."""
    from pathlib import Path
    p = Path(path)
    if fmt == "chrome":
        p.write_text(json.dumps(export_chrome(tracer), default=_jsonable)
                     + "\n")
    elif fmt == "jsonl":
        p.write_text(export_jsonl(tracer))
    else:
        raise ValueError(f"unknown trace format {fmt!r} "
                         "(choose 'chrome' or 'jsonl')")
