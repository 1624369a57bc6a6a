"""Engine telemetry layer (src/repro/serving/telemetry.py):

  * the `Tracer` ring keeps the NEWEST events and counts what it dropped;
    aggregate counters stay exact under truncation;
  * a traced engine run emits only vocabulary kinds, with non-decreasing
    iteration stamps, and `tools/trace_report.py`'s mirrored vocabulary
    stays in sync with `telemetry.EVENT_KINDS`;
  * the chrome export survives a json round trip with valid ph/ts/pid and
    carries the aggregate tables under "papi"; the Prometheus snapshot is
    line-parseable text exposition;
  * the dispatch spans are hand-countable on a single greedy request (1
    prefill dispatch + max_new-1 decode dispatches), and no dispatch
    blocks under an enabled tracer;
  * tracing is observation only: serve() token streams are BIT-IDENTICAL
    traced vs untraced, and the NullTracer default keeps every hook a
    no-op;
  * IterStats says where the host's time went: host_s <= wall_s, GC
    pauses in gc_s (and as `gc` spans), jit-cache misses on each
    program's first step and none at steady state;
  * scheduler events carry the AI estimate AND the alpha threshold, flips
    match `num_reschedules`; degraded/fault events match the engine's own
    counts on a spec+paged+faults run; a watchdog stall lands a final
    `stall` event before EngineStallError propagates;
  * page map/unmap/reserve events balance to zero on a drained pool;
  * `latency_summary` reports per-metric sample counts and tpot_s only
    over requests with >= 2 tokens.
"""
import gc
import json
import re
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_config
from repro.models import init_params
from repro.serving import (EngineStallError, FaultInjector, PapiEngine,
                           ServeRequest, Tracer, export_chrome, export_jsonl,
                           export_prometheus, latency_summary, write_trace)
from repro.serving.telemetry import (EVENT_KINDS, NULL_TRACER,
                                     format_program_key, phase)

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
import trace_report  # noqa: E402  (tools/ is not a package)


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("qwen2-0.5b").reduced()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def draft_model():
    cfg = get_config("qwen2-0.5b").reduced()
    return cfg, init_params(cfg, jax.random.PRNGKey(9))


NO_EOS = get_config("qwen2-0.5b").reduced().vocab_size - 1


def _engine(cfg, params, **kw):
    defaults = dict(max_slots=4, cache_capacity=64, prefill_len=8,
                    alpha=6.0, eos_token=NO_EOS, fused=True,
                    debug_invariants=True)
    defaults.update(kw)
    return PapiEngine(cfg, params, **defaults)


def _submit_all(eng, n=3, max_new=6):
    for i in range(n):
        eng.submit(ServeRequest(i, [3 + i, 5, 7], max_new_tokens=max_new))


# ------------------------------------------------------------- ring buffer

def test_ring_truncation_keeps_newest_and_counts_dropped():
    tr = Tracer(capacity=10)
    for i in range(25):
        tr.emit("submit", iteration=i, req_id=i, prompt_len=3, max_new=4)
    events = list(tr.events)
    assert len(events) == 10
    assert tr.emitted == 25
    assert tr.dropped == 15
    # newest-wins: the ring holds exactly the last ten submissions
    assert [ev.data["req_id"] for ev in events] == list(range(15, 25))
    # aggregates are maintained OUTSIDE the ring: exact despite truncation
    assert tr.counters["submit"] == 25


def test_null_tracer_is_inert():
    assert NULL_TRACER.emit("finish", req_id=0) is None
    with phase("engine.fetch", NULL_TRACER) as span:
        span.set(rows=3)                  # metadata, recorded nowhere
    assert span.meta == {"rows": 3} and span.start > 0
    assert not hasattr(NULL_TRACER, "timed_call")   # no blocking dispatch
    assert not NULL_TRACER.enabled
    assert list(NULL_TRACER.events) == []
    assert NULL_TRACER.counters == {}


# ------------------------------------------------- traced engine: vocabulary

def test_traced_run_vocabulary_and_iteration_order(small_model):
    cfg, params = small_model
    tr = Tracer()
    eng = _engine(cfg, params, tracer=tr)
    _submit_all(eng)
    eng.run(max_iterations=60)
    events = list(tr.events)
    assert events, "traced run emitted nothing"
    assert {ev.kind for ev in events} <= EVENT_KINDS
    iters = [ev.iteration for ev in events]
    assert iters == sorted(iters), "iteration stamps must be non-decreasing"
    # one scheduler decision and one iteration span per engine step
    assert tr.counters["scheduler"] == eng.iteration
    assert tr.counters["iteration"] == eng.iteration
    assert tr.counters["tokens"] == sum(s.new_tokens for s in eng.stats)
    assert tr.counters["finish:length"] == 3


def test_event_kinds_mirror_enforced_statically():
    """tools/trace_report.py is stdlib-only so it keeps its OWN copy of the
    vocabulary.  Enforcement lives in papilint's PL005 mirror checker,
    which parses both literal sets out of the source (and verifies every
    configured exporter mentions every kind) — so a drifted copy fails the
    lint gate before any test imports run.  One equality stays below as
    the runtime smoke assert."""
    root = Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(root))
    from tools.papilint import checkers, load_config
    cfg = load_config(root / "pyproject.toml")
    assert checkers.check_mirrors(cfg, root) == []
    assert checkers.check_exporters(cfg, root) == []
    assert trace_report.EVENT_KINDS == EVENT_KINDS  # runtime smoke


def test_all_exporters_cover_every_event_kind(tmp_path):
    """Emit one event of every vocabulary kind, then check each of the
    three exporters surfaces all of them: the chrome trace round-trips
    every kind through load_trace, the jsonl export carries one record
    per kind, and the prometheus exposition zero-fills a
    papi_engine_events_total sample for the full vocabulary."""
    tr = Tracer()
    emitters = {
        "submit": dict(req_id=0, prompt_len=3, max_new=4),
        "admit": dict(req_id=0, slot=0, prompt_len=3),
        "first_token": dict(req_id=0),
        "preempt": dict(req_id=1, slot=1, done=2),
        "finish": dict(req_id=0, reason="length", tokens=4, slot=0),
        "defer": dict(req_id=2, age=3),
        "scheduler": dict(ai_estimate=1.0, alpha=6.0, assignment="pim",
                          flipped=True, rlp=1, tlp=2),
        "iteration": dict(new_tokens=1, fc_variant="pu"),
        "pool": dict(used=1, free=7, watermark=2, fragmentation=0.0),
        "fault": dict(fault="logits_nan"),
        "degraded": dict(mode="step"),
        "phase": dict(name="engine.dispatch", key="plain_fused|1|pim|-|-"),
        "page_map": dict(slot=0, pages=2),
        "page_unmap": dict(slot=0, pages=2, cause="finish"),
        "page_reserve": dict(slot=0, budget_pages=4, mapped_pages=2),
        "stall": dict(snapshot={"iteration": 5}),
        "journal": dict(op="open", path="wal.j", records=0,
                        truncated_bytes=0),
        "recover": dict(path="wal.j", resumed=2, finished=1, records=9,
                        torn_bytes=0, next_req_id=3),
    }
    assert set(emitters) == set(EVENT_KINDS), \
        "extend this test when the vocabulary grows"
    for kind, data in emitters.items():
        tr.emit(kind, iteration=1, **data)

    path = tmp_path / "t.trace.json"
    write_trace(tr, path, "chrome")
    events, _summary = trace_report.load_trace(path)
    assert {ev["kind"] for ev in events} == set(EVENT_KINDS)

    jsonl_kinds = {json.loads(line)["kind"]
                   for line in export_jsonl(tr).strip().splitlines()}
    assert jsonl_kinds == set(EVENT_KINDS) | {"summary"}

    samples = dict(re.findall(
        r'papi_engine_events_total\{kind="([^"]+)"\} (\d+)',
        export_prometheus(tr)))
    assert set(samples) == set(EVENT_KINDS)
    assert all(int(v) == 1 for v in samples.values())
    # zero-filled even on an empty tracer: the exposition always shows
    # the full vocabulary
    empty = dict(re.findall(
        r'papi_engine_events_total\{kind="([^"]+)"\} (\d+)',
        export_prometheus(Tracer())))
    assert set(empty) == set(EVENT_KINDS)
    assert all(int(v) == 0 for v in empty.values())


# ---------------------------------------------------------------- exporters

def test_chrome_export_round_trip(small_model, tmp_path):
    cfg, params = small_model
    tr = Tracer()
    eng = _engine(cfg, params, tracer=tr)
    _submit_all(eng)
    eng.run(max_iterations=60)
    path = tmp_path / "t.trace.json"
    write_trace(tr, path, "chrome")
    doc = json.loads(path.read_text())
    assert "traceEvents" in doc and doc["traceEvents"]
    for rec in doc["traceEvents"]:
        assert rec["ph"] in ("M", "X", "i", "C")
        assert rec["pid"] == 1
        assert isinstance(rec["ts"], (int, float)) and rec["ts"] >= 0
        if rec["ph"] == "X":
            assert rec["dur"] >= 0
        if rec["ph"] == "C":       # Perfetto counter tracks: numeric-only
            assert all(isinstance(v, (int, float))
                       for v in rec["args"].values())
    papi = doc["papi"]
    assert papi["counters"]["iteration"] == eng.iteration
    assert papi["events_dropped"] == 0
    assert "programs" not in papi
    # the phase spans sit on the scheduler lane, inside the iteration spans
    phases = [r for r in doc["traceEvents"]
              if r["ph"] == "X" and r["args"].get("kind") == "phase"]
    assert phases and all(r["tid"] == 1 for r in phases)
    assert {r["name"] for r in phases} >= {"engine.admit", "engine.fetch",
                                           "engine.dispatch"}
    # every admitted request got a residency span on a slot lane
    slot_spans = [r for r in doc["traceEvents"]
                  if r["ph"] == "X" and r.get("name", "").startswith("req ")]
    assert len(slot_spans) == 3


def test_prometheus_export_parses(small_model):
    cfg, params = small_model
    tr = Tracer()
    eng = _engine(cfg, params, tracer=tr)
    _submit_all(eng)
    eng.run(max_iterations=60)
    text = export_prometheus(tr)
    line_re = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9eE.+-]+$')
    names = set()
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        assert line_re.match(line), f"unparseable sample line: {line!r}"
        names.add(line.split("{")[0].split(" ")[0])
    for required in ("papi_engine_iterations_total",
                     "papi_engine_tokens_total",
                     "papi_engine_preemptions_total",
                     "papi_engine_degraded_steps_total",
                     "papi_engine_kv_pages_used",
                     "papi_engine_events_total"):
        assert required in names
    # no host-clock program timing: the profiler trace has device time
    assert not any(n.startswith("papi_engine_program_") for n in names)
    assert 'papi_engine_events_total{kind="phase"} 0' not in text
    # values come from the aggregates, not the ring
    assert (f"papi_engine_iterations_total {eng.iteration}"
            in text.splitlines())


def test_jsonl_export_has_trailing_summary(small_model):
    cfg, params = small_model
    tr = Tracer()
    eng = _engine(cfg, params, tracer=tr)
    _submit_all(eng, n=1)
    eng.run(max_iterations=30)
    lines = export_jsonl(tr).strip().splitlines()
    recs = [json.loads(ln) for ln in lines]
    assert all(r["kind"] in EVENT_KINDS for r in recs[:-1])
    assert recs[-1]["kind"] == "summary"
    assert recs[-1]["data"]["counters"]["iteration"] == eng.iteration
    assert "programs" not in recs[-1]["data"]
    names = {r["data"]["name"] for r in recs if r["kind"] == "phase"}
    assert {"engine.admit", "engine.prepare", "engine.dispatch",
            "engine.fetch", "engine.commit", "engine.park",
            "engine.account"} <= names


# ------------------------------------------------------------ phase spans

def test_dispatch_spans_hand_counted(small_model):
    """One greedy request, max_new=5, eos never fires: exactly 1 main
    prefill dispatch and 4 plain_fused decode dispatches (prefill commits
    token 1; each later iteration commits one), each an engine.dispatch
    span with its jit key, and one fetch per dispatch."""
    cfg, params = small_model
    tr = Tracer()
    eng = _engine(cfg, params, tracer=tr)
    eng.submit(ServeRequest(0, [3, 5, 7], max_new_tokens=5))
    res = eng.run(max_iterations=30)
    assert len(res[0].tokens) == 5
    spans = [ev for ev in tr.events if ev.kind == "phase"]
    by_kind = {}
    for ev in spans:
        if ev.data["name"] == "engine.dispatch":
            kind = ev.data["key"].split("|")[0]
            by_kind[kind] = by_kind.get(kind, 0) + 1
    assert by_kind == {"main": 1, "plain_fused": 4}
    fetches = [ev for ev in spans if ev.data["name"] == "engine.fetch"]
    assert len(fetches) == 5
    assert all(ev.dur >= 0 for ev in spans)


def test_named_programs_lower_to_the_same_hlo(small_model):
    """A program's stable name (`jit_papi_admit_prefill` in the profiler's
    trace, where a bare partial reads `jit__unknown`) changes nothing but
    the module's name."""
    from functools import partial

    import jax.numpy as jnp

    from repro.models import init_paged_cache, prefill_to_pages
    from repro.serving.engine import _named

    cfg, params = small_model
    cache = init_paged_cache(cfg, 4, 17, 8, 8)
    args = (params, {"tokens": jnp.zeros((4, 8), jnp.int32),
                     "prompt_lens": jnp.ones(4, jnp.int32)},
            cache, jnp.zeros(4, jnp.int32))
    fn = partial(prefill_to_pages, cfg)
    bare = jax.jit(fn).lower(*args).as_text()
    named = jax.jit(_named("papi_admit_prefill", fn)).lower(*args).as_text()
    assert "@jit_papi_admit_prefill" in named

    def strip(text):
        return re.sub(r"@jit_\w+", "@jit_", text)

    assert strip(named) == strip(bare)


def test_format_program_key_compresses_defaults():
    assert format_program_key(("spec_fused", 4, "pim", None, False)) == \
        "spec_fused|4|pim|-|-"
    assert format_program_key(("main", "pu", True, True)) == "main|pu|True|True"


def test_traced_dispatch_never_blocks(small_model, monkeypatch):
    """An enabled Tracer observes dispatch without serialising it: no
    jax.block_until_ready anywhere on the engine's path."""
    cfg, params = small_model
    blocks = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: blocks.append(1) or real(x))
    tr = Tracer()
    eng = _engine(cfg, params, tracer=tr)
    for ev in eng.serve([[ServeRequest(0, [3, 5, 7], max_new_tokens=4)],
                         [ServeRequest(1, list(range(3, 20)), 3)]]):
        pass
    assert tr.counters["phase"] > 0
    assert blocks == []


# ---------------------------------------------- where the host time went

def _serve_all(eng, schedule):
    return {ev.req_id: ev.result.tokens
            for ev in eng.serve([list(w) for w in schedule]) if ev.finished}


STREAM = [[ServeRequest(0, [3, 5, 7], max_new_tokens=6)], [],
          [ServeRequest(1, list(range(3, 23)), max_new_tokens=5)], [],
          [ServeRequest(2, [5, 7, 9, 11], max_new_tokens=4)]]


def test_host_s_within_wall_s(small_model):
    cfg, params = small_model
    eng = _engine(cfg, params)
    _serve_all(eng, STREAM)
    assert eng.stats
    for st in eng.stats:
        assert 0.0 <= st.host_s <= st.wall_s


def test_gc_pause_counts_in_gc_s_and_as_a_gc_span(small_model, monkeypatch):
    """A full collection forced inside every step's accounting: its pause
    lands in that step's IterStats.gc_s and in a `gc` span, and the hook
    is gone once serve() returns."""
    cfg, params = small_model
    tr = Tracer()
    eng = _engine(cfg, params, tracer=tr)
    check = eng._check_invariants

    def collect_then_check():
        gc.collect()
        check()

    monkeypatch.setattr(eng, "_check_invariants", collect_then_check)
    _serve_all(eng, STREAM)
    assert eng._on_gc not in gc.callbacks
    assert all(st.gc_s > 0 for st in eng.stats)
    spans = [ev for ev in tr.events
             if ev.kind == "phase" and ev.data["name"] == "gc"]
    assert any(ev.data["generation"] == 2 for ev in spans)
    assert sum(ev.dur for ev in spans) == pytest.approx(
        sum(st.gc_s for st in eng.stats), rel=0.05, abs=1e-4)


def test_jit_misses_on_first_use_only(small_model):
    """Each program's first dispatch grows its jit cache (a trace and a
    compile), every later one hits it: the misses add up to the programs
    the engine compiled, and a second identical stream misses nothing."""
    cfg, params = small_model
    eng = _engine(cfg, params)
    _serve_all(eng, STREAM)
    compiled = [*eng._decode_jit.values(), *eng._prefill_jit.values()]
    assert sum(st.jit_misses for st in eng.stats) == sum(
        fn._cache_size() for fn in compiled)
    assert eng.stats[0].jit_misses > 0
    first = len(eng.stats)
    _serve_all(eng, [[ServeRequest(10 + r.req_id, r.prompt,
                                   r.max_new_tokens) for r in w]
                     for w in STREAM])
    assert [st.jit_misses for st in eng.stats[first:]] == \
        [0] * (len(eng.stats) - first)


# ------------------------------------------------- observation only (serve)

def test_serve_streams_bit_identical_traced_vs_untraced(small_model):
    cfg, params = small_model
    schedule = [[ServeRequest(0, [3, 5, 7], max_new_tokens=6)], [],
                [ServeRequest(1, [4, 6], max_new_tokens=5)], [],
                [ServeRequest(2, [5, 7, 9, 11], max_new_tokens=4)]]

    def streams(tracer):
        eng = _engine(cfg, params, tracer=tracer)
        got = {}
        for ev in eng.serve([list(w) for w in schedule]):
            if ev.finished:
                got[ev.req_id] = ev.result.tokens
        return got

    untraced = streams(None)
    tr = Tracer()
    traced = streams(tr)
    assert traced == untraced
    assert tr.counters["finish:length"] == 3
    assert tr.counters["submit"] == 3


# ------------------------------------- scheduler, faults, degraded, stalls

def test_scheduler_events_carry_estimate_and_threshold(small_model,
                                                       draft_model):
    cfg, params = small_model
    dcfg, dparams = draft_model
    tr = Tracer()
    eng = _engine(cfg, params, tracer=tr, spec_len=3, draft=(dcfg, dparams))
    _submit_all(eng, n=4, max_new=8)
    eng.run(max_iterations=80)
    sched = [ev for ev in tr.events if ev.kind == "scheduler"]
    assert sched
    for ev in sched:
        assert ev.data["alpha"] == eng.scheduler.alpha
        assert ev.data["assignment"] in ("pu", "pim")
        assert isinstance(ev.data["ai_estimate"], float)
    flips = [ev for ev in sched if ev.data["flipped"]]
    assert len(flips) == tr.counters["scheduler_flip"]
    assert len(flips) <= eng.scheduler.num_reschedules
    # a spec run exercises >= 2 distinct compiled programs (draft + verify
    # at minimum; pu/pim variants when the scheduler flips)
    keys = {ev.data["key"] for ev in tr.events
            if ev.kind == "phase" and ev.data["name"] == "engine.dispatch"}
    assert len(keys) >= 2


def test_faults_and_degraded_events_match_engine_counts(small_model,
                                                        draft_model):
    cfg, params = small_model
    dcfg, dparams = draft_model
    tr = Tracer()
    eng = _engine(cfg, params, tracer=tr, spec_len=3, draft=(dcfg, dparams),
                  kv_layout="paged", page_size=8,
                  faults=FaultInjector(seed=3, nan_p=0.4, start=1, stop=8))
    _submit_all(eng, n=3, max_new=8)
    eng.run(max_iterations=80)
    assert eng.degraded_steps > 0, "fault seed never fired; test is vacuous"
    assert tr.counters["degraded"] == eng.degraded_steps
    assert tr.counters["fault:nan"] == eng.faults.counts["nan"]
    degraded_iters = {ev.iteration for ev in tr.events
                      if ev.kind == "degraded"}
    # trace events stamp the 0-based step index; IterStats.iteration is
    # recorded post-increment (1-based) — same steps, shifted by one
    flagged = {s.iteration - 1 for s in eng.stats if s.degraded}
    assert degraded_iters == flagged
    # the iteration spans carry the degraded flag too
    spans = {ev.iteration: ev.data["degraded"] for ev in tr.events
             if ev.kind == "iteration"}
    assert all(spans[i] for i in flagged)


def test_stall_event_emitted_before_raise(small_model):
    cfg, params = small_model
    tr = Tracer()
    eng = _engine(cfg, params, tracer=tr, cache_capacity=16,
                  kv_layout="paged", page_size=4, stall_limit=5)
    eng.kv.can_admit = lambda *_: False
    eng.submit(ServeRequest(0, [3, 5, 7], max_new_tokens=4))
    with pytest.raises(EngineStallError):
        eng.run(max_iterations=100)
    assert tr.counters["stall"] == 1
    stall = [ev for ev in tr.events if ev.kind == "stall"][-1]
    assert stall.data["snapshot"]["queue"] == [0]
    # deferral events accumulated while the head starved
    assert tr.counters["defer"] >= 5


def test_page_events_balance_on_drained_pool(small_model):
    cfg, params = small_model
    tr = Tracer()
    eng = _engine(cfg, params, tracer=tr, cache_capacity=32,
                  kv_layout="paged", page_size=4)
    _submit_all(eng, n=3, max_new=6)
    eng.run(max_iterations=60)
    assert eng.kv.alloc.mapped_count == 0, "pool must drain after run()"
    mapped = sum(ev.data["mapped_pages"] for ev in tr.events
                 if ev.kind == "page_reserve")
    mapped += sum(ev.data["pages"] for ev in tr.events
                  if ev.kind == "page_map")
    unmapped = sum(ev.data["pages"] for ev in tr.events
                   if ev.kind == "page_unmap")
    assert mapped > 0
    assert mapped == unmapped
    # occupancy samples never exceed the watermark
    for ev in tr.events:
        if ev.kind == "pool":
            assert ev.data["used"] <= ev.data["watermark"]


# ----------------------------------------------------------- trace_report

def test_trace_report_validates_both_formats(small_model, tmp_path):
    cfg, params = small_model
    tr = Tracer()
    eng = _engine(cfg, params, tracer=tr)
    _submit_all(eng, n=2)
    eng.run(max_iterations=40)
    chrome = tmp_path / "t.trace.json"
    jsonl = tmp_path / "t.jsonl"
    write_trace(tr, chrome, "chrome")
    write_trace(tr, jsonl, "jsonl")
    assert trace_report.main([str(chrome), "--validate"]) == 0
    assert trace_report.main([str(jsonl), "--validate"]) == 0
    # the report (non-validate) path renders without error on both
    assert trace_report.main([str(chrome)]) == 0
    assert trace_report.main([str(jsonl)]) == 0
    # loader normalization: both serializations agree on the aggregates
    _, summ_c = trace_report.load_trace(chrome)
    _, summ_j = trace_report.load_trace(jsonl)
    assert summ_c["counters"] == summ_j["counters"]
    ev_c, _ = trace_report.load_trace(chrome)
    ev_j, _ = trace_report.load_trace(jsonl)
    assert ([e["data"]["name"] for e in ev_c if e["kind"] == "phase"]
            == [e["data"]["name"] for e in ev_j if e["kind"] == "phase"])


def test_trace_report_rejects_bad_traces(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps({"kind": "martian", "iteration": 0,
                               "ts": 0.0, "dur": 0.0, "data": {}}) + "\n")
    assert trace_report.main([str(bad), "--validate"]) == 1
    missing = tmp_path / "nope.json"
    assert trace_report.main([str(missing), "--validate"]) == 1
    # an empty trace fails the liveness gate (no scheduler/iteration events)
    empty = tmp_path / "empty.jsonl"
    tr = Tracer()
    write_trace(tr, empty, "jsonl")
    assert trace_report.main([str(empty), "--validate"]) == 1


# ----------------------------------------------------------------- metrics

def test_latency_summary_counts_and_single_token_tpot(small_model):
    cfg, params = small_model
    eng = _engine(cfg, params)
    eng.submit(ServeRequest(0, [3, 5, 7], max_new_tokens=1))
    eng.submit(ServeRequest(1, [4, 6], max_new_tokens=5))
    res = {r.req_id: r for r in eng.run(max_iterations=30)}
    assert len(res[0].tokens) == 1
    assert res[0].tpot_s is None, "tpot is undefined for a 1-token request"
    assert res[1].tpot_s is not None and res[1].tpot_s >= 0.0
    summ = latency_summary(res.values())
    assert summ["n"] == 2
    assert summ["ttft_s"]["count"] == 2
    assert summ["tpot_s"]["count"] == 1   # only the >= 2-token request
    for field, table in summ.items():
        if field == "n":
            continue
        assert set(table) >= {"p50", "p99", "mean", "count"}
