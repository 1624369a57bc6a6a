"""The serving program's spans in a profiler trace (bench/engine_trace.py)
and the per-layer metrics that read its step counters: a tiny engine served
under `jax.profiler` on the CPU, the committed chip traces, and hand-built
contexts."""
import types

import pytest

from tiny_cell import ROOT
from bench import engine_trace, trace_reduce
from bench.metrics import (device_idle_share, fc_gemv_roofline, gc_pause_ms,
                           host_step_ms, jit_misses)

TRACE = ROOT / "bench/testdata/qwen2-0.5b.creative.xplane.pb.gz"
# 0.34 s of the same cell with the program's spans and program names,
# opened just before a request of 110 prompt tokens arrived (TPU v5 lite)
SPANS_TRACE = ROOT / "bench/testdata/qwen2-0.5b.creative.spans.xplane.pb.gz"
PHASES_ONCE = ("engine.admit", "engine.prepare", "engine.commit",
               "engine.park", "engine.account")


@pytest.fixture(scope="module")
def served_under_profiler(tmp_path_factory):
    """A tiny paged engine serving three requests (one longer than its
    prefill window) under jax.profiler: (the engine, the trace's spans)."""
    import jax

    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving import PapiEngine, ServeRequest

    cfg = get_config("qwen2-0.5b").reduced()
    eng = PapiEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                     max_slots=4, cache_capacity=64, prefill_len=8,
                     eos_token=cfg.vocab_size - 1, kv_layout="paged",
                     page_size=8)
    schedule = [[ServeRequest(0, [3, 5, 7], 6)], [],
                [ServeRequest(1, list(range(3, 23)), 5)],
                [ServeRequest(2, [5, 7, 9], 4)]]
    out = tmp_path_factory.mktemp("profile")
    with jax.profiler.trace(str(out)):
        for _ in eng.serve(schedule):
            pass
    data = trace_reduce._load(trace_reduce.find_xplane(out))
    _, spans, steps = engine_trace.host_spans(data)
    return eng, spans, steps


def _children(spans, step):
    a, b, _ = step
    return [n for x, y, n in spans if a <= x and y <= b and (x, y) != (a, b)]


def test_engine_spans_nest_in_their_step(served_under_profiler):
    """Every IterStats line has its engine.step span, with step_num equal
    to its iteration; inside it, each phase once, and the step's one
    dispatch and fetch of the decode or wave program."""
    eng, spans, steps = served_under_profiler
    by_num = {steps[a]: (a, b, n) for a, b, n in spans
              if n == engine_trace.STEP_SPAN}
    assert {st.iteration for st in eng.stats} <= set(by_num)
    for st in eng.stats:
        inside = _children(spans, by_num[st.iteration])
        for name in PHASES_ONCE:
            assert inside.count(name) == 1, (st.iteration, name, inside)
        assert inside.count("engine.dispatch") >= 1
        assert inside.count("engine.fetch") >= 1
        if st.admitted:
            assert "engine.admit.prefill" in inside
    names = {n for _, _, n in spans}
    assert "serve.arrivals" in names
    assert "engine.admit.prefill" in names


def test_spans_nest_properly(served_under_profiler):
    """The labelling walks enclosing spans: each span lies wholly inside
    the span found as its parent."""
    _, spans, _ = served_under_profiler
    parent = engine_trace.parents(spans)
    for (a, b, _), p in zip(spans, parent):
        if p >= 0:
            assert spans[p][0] <= a and b <= spans[p][1]
    starts = [a for a, _, _ in spans]
    a, b, name = next(s for s in spans if s[2] == "engine.fetch")
    assert engine_trace.label((a + b) // 2, spans, starts, parent) == name


def test_program_names():
    assert engine_trace.program_name(
        "jit_papi_decode_step(10020814532099580120)") == "papi_decode_step"
    assert engine_trace.program_name("jit__where(17)") == "_where"
    assert engine_trace.program_name("papi_mixed_wave") == "papi_mixed_wave"


def test_committed_trace_reads_as_before():
    """The reduction of the first committed trace, and the per-layer
    metrics it feeds, read what they read before the program's spans
    existed; the span reader labels its gaps exactly as trace_reduce."""
    summary = trace_reduce.reduce_file(TRACE)
    ctx = types.SimpleNamespace(trace=summary, device_kind="TPU v5 lite")
    assert device_idle_share.read(ctx) == pytest.approx(9.723156436872305,
                                                        rel=1e-12)
    assert fc_gemv_roofline.read(ctx) == pytest.approx(58.31587762142687,
                                                       rel=1e-12)
    assert len(summary.gaps) == 407
    t = engine_trace.read(TRACE)
    assert t.gaps == summary.gaps
    assert t.busy_s == pytest.approx(summary.busy_s, rel=1e-12)
    idle = summary.window_s - summary.busy_s
    assert t.idle_by_gap_label() == {"serve.next": pytest.approx(idle,
                                                                 rel=1e-9)}
    # split exactly, a few gaps reach out of serve.next
    assert sum(t.idle.values()) == pytest.approx(idle, rel=1e-9)
    assert t.idle["serve.next"] > 0.95 * idle
    # its programs carry the names they had before they were named
    assert sorted(t.programs) == ["_unknown", "_where",
                                  "convert_element_type", "plain_step",
                                  "wave"]


@pytest.fixture(scope="module")
def spans_trace():
    return engine_trace.read(SPANS_TRACE)


def test_chip_trace_names_its_programs(spans_trace):
    """Ten steps: nine decode steps, the admission prefill of the arriving
    request and the mixed wave of its second window, one park each."""
    runs = spans_trace.programs
    assert {"papi_decode_step", "papi_mixed_wave", "papi_admit_prefill",
            "papi_park_slots"} <= set(runs)
    assert not {"plain_step", "wave", "_unknown", "_where"} & set(runs)
    assert len(runs["papi_decode_step"]) == 9
    assert len(runs["papi_mixed_wave"]) == 1
    assert len(runs["papi_admit_prefill"]) == 1
    assert len(runs["papi_park_slots"]) == 10
    decode = sorted(runs["papi_decode_step"])[4]
    assert 0.020 < decode < 0.030
    assert 0.050 < runs["papi_mixed_wave"][0] < 0.080
    assert runs["papi_admit_prefill"][0] < runs["papi_mixed_wave"][0]


def test_chip_trace_splits_idle_time_by_engine_span(spans_trace):
    """The device's idle time falls under the engine's phases: every gap
    of the ten longest is labelled with one, and the program's spans plus
    the harness's bookkeeping and arrivals hold at least 80% of it."""
    t = spans_trace
    idle = t.window_s - t.busy_s
    assert sum(t.idle.values()) == pytest.approx(idle, rel=1e-9)
    longest = sorted(t.gaps, key=lambda g: -g[1])[:10]
    assert all(name.startswith("engine.") for name, _ in longest)
    held = sum(s for name, s in t.idle.items()
               if name in engine_trace.ENGINE_SPANS
               or name in ("bookkeeping", "arrivals"))
    assert held >= 0.8 * idle
    for name in ("engine.step", "engine.admit", "engine.admit.prefill",
                 "engine.prepare", "engine.dispatch", "engine.fetch",
                 "engine.commit", "engine.park", "engine.account",
                 "serve.arrivals"):
        assert t.spans[name], name
    assert len(t.spans["engine.step"]) == 10


def _ctx(**fields):
    iters = [types.SimpleNamespace(**{k: v[i] for k, v in fields.items()})
             for i in range(len(next(iter(fields.values()))))]
    return types.SimpleNamespace(iters=iters, device_kind="TPU v5 lite")


def test_host_step_ms_is_the_median_host_time():
    ctx = _ctx(host_s=[0.004, 0.002, 0.003, 0.010, 0.0035])
    assert host_step_ms.read(ctx) == pytest.approx(3.5)


def test_gc_pause_ms_sums_the_window():
    ctx = _ctx(gc_s=[0.0, 0.0012, 0.0, 0.1])
    assert gc_pause_ms.read(ctx) == pytest.approx(101.2)


def test_jit_misses_sums_the_window():
    assert jit_misses.read(_ctx(jit_misses=[0, 0, 0])) == 0.0
    assert jit_misses.read(_ctx(jit_misses=[0, 2, 1])) == 3.0


def test_counter_readers_report_nothing_without_the_counters():
    """A program from before the counters (no such IterStats field), an
    empty window, or a CPU rehearsal: no reading, and no error."""
    old = _ctx(wall_s=[0.03, 0.03])
    for reader in (host_step_ms, gc_pause_ms, jit_misses):
        assert reader.read(old) is None
        assert reader.read(types.SimpleNamespace(
            iters=[], device_kind="TPU v5 lite")) is None
    cpu = _ctx(host_s=[0.1], gc_s=[0.0], jit_misses=[0])
    cpu.device_kind = None
    for reader in (host_step_ms, gc_pause_ms, jit_misses):
        assert reader.read(cpu) is None
