"""Continuous-batching streaming front end: serve() vs the offline oracle.

The tentpole contract of the serve loop (`PapiEngine.serve`):

  * token streams under live Poisson-ish arrivals are BIT-IDENTICAL to the
    offline ``submit()`` + ``run()`` batch oracle for the same request set
    — greedy and speculative, dense and paged KV;
  * every committed token is streamed exactly once, in order, with
    contiguous indices, and the final event carries the full `ServeResult`;
  * per-request latencies (queue delay / TTFT / TPOT) are stamped, and the
    iteration-valued ones are deterministic for a fixed arrival schedule;
  * admission stays FIFO under arbitrary arrival/deferral/preemption/
    cancel interleavings, and every submitted request terminates with a
    valid ``finished_reason`` (property-tested via tests/_propcompat.py).
"""
import jax
import numpy as np
import pytest

from _propcompat import given, settings, st
from repro.configs import get_config
from repro.models import init_params
from repro.serving import (FaultInjector, PapiEngine, ServeRequest,
                           latency_summary, percentile)

VALID_REASONS = {"eos", "length", "rejected", "cancelled", "timeout",
                 "aborted"}


_MODEL_CACHE: dict = {}


def _model():
    """Module-lazy model: shared with the fixture AND the property test
    (the _propcompat fallback runner can't mix fixtures with @given)."""
    if "m" not in _MODEL_CACHE:
        cfg = get_config("qwen2-0.5b").reduced()
        _MODEL_CACHE["m"] = (cfg, init_params(cfg, jax.random.PRNGKey(0)))
    return _MODEL_CACHE["m"]


@pytest.fixture(scope="module")
def small_model():
    return _model()


@pytest.fixture(scope="module")
def draft_model():
    cfg = get_config("qwen2-0.5b").reduced()
    return cfg, init_params(cfg, jax.random.PRNGKey(9))


def _mk_engine(cfg, params, **kw):
    defaults = dict(max_slots=4, cache_capacity=64, prefill_len=8,
                    alpha=6.0, eos_token=1, debug_invariants=True)
    defaults.update(kw)
    return PapiEngine(cfg, params, **defaults)


def _requests(seed, n, vocab, max_prompt=30, max_new=10):
    """Mixed workload: prompts straddling the prefill window (some chunk)."""
    rng = np.random.default_rng(seed)
    return [ServeRequest(i,
                         [int(t) for t in rng.integers(3, vocab - 1,
                                                       rng.integers(3, max_prompt))],
                         int(rng.integers(2, max_new)))
            for i in range(n)]


def _schedule(reqs, gaps):
    """Arrival trace: gaps[i] quiet iterations before request i arrives."""
    sched = []
    for req, gap in zip(reqs, gaps):
        sched.extend([[]] * gap)
        sched.append([ServeRequest(req.req_id, list(req.prompt),
                                   req.max_new_tokens,
                                   deadline_s=req.deadline_s)])
    return sched


def _offline(cfg, params, reqs, **kw):
    eng = _mk_engine(cfg, params, **kw)
    for r in reqs:
        eng.submit(ServeRequest(r.req_id, list(r.prompt), r.max_new_tokens))
    return {r.req_id: r.tokens for r in eng.run(max_iterations=500)}


def _serve(cfg, params, reqs, gaps, **kw):
    eng = _mk_engine(cfg, params, **kw)
    streams: dict[int, list[int]] = {}
    finals = {}
    for ev in eng.serve(_schedule(reqs, gaps)):
        if ev.finished:
            assert ev.token == -1 and ev.result is not None
            assert ev.index == len(ev.result.tokens)
            assert ev.reason == ev.result.finished_reason
            finals[ev.req_id] = ev.result
        else:
            streams.setdefault(ev.req_id, []).append(ev.token)
            # contiguous 0-based indices: exactly-once, in-order streaming
            assert ev.index == len(streams[ev.req_id]) - 1
    assert set(finals) == {r.req_id for r in reqs}
    for rid, res in finals.items():
        assert streams.get(rid, []) == res.tokens
    return {rid: res.tokens for rid, res in finals.items()}, finals, eng


GAPS = [0, 0, 2, 0, 1, 3, 0, 5]


@pytest.mark.parametrize("kv", ["dense", "paged"])
def test_serve_greedy_identity_vs_offline(small_model, kv):
    cfg, params = small_model
    kw = dict(kv_layout=kv, page_size=4) if kv == "paged" else {}
    reqs = _requests(7, 8, cfg.vocab_size)
    offline = _offline(cfg, params, reqs, **kw)
    live, finals, _ = _serve(cfg, params, reqs, GAPS, **kw)
    assert live == offline


@pytest.mark.parametrize("kv", ["dense", "paged"])
def test_serve_speculative_identity_vs_offline(small_model, draft_model, kv):
    cfg, params = small_model
    kw = dict(spec_len=3, draft=draft_model)
    if kv == "paged":
        kw.update(kv_layout="paged", page_size=4)
    reqs = _requests(11, 6, cfg.vocab_size)
    offline = _offline(cfg, params, reqs, **kw)
    live, _, _ = _serve(cfg, params, reqs, GAPS, **kw)
    assert live == offline


def test_serve_mixes_prefill_and_decode_waves(small_model):
    """A long prompt arriving mid-decode must NOT stall the running
    decodes: iterations with both prefill_slots and decode_slots > 0
    exist, and under TLP=1 those mixed iterations dispatch ONE device
    program (no extra transfers vs a plain decode iteration)."""
    cfg, params = small_model
    long_prompt = [int(t) for t in
                   np.random.default_rng(3).integers(3, cfg.vocab_size - 1, 40)]
    eng = _mk_engine(cfg, params)
    sched = [[ServeRequest(0, [3, 5, 7], 30)],
             [], [],
             [ServeRequest(1, long_prompt, 4)]]
    for _ in eng.serve(sched):
        pass
    mixed = [s for s in eng.stats if s.prefill_slots and s.decode_slots]
    assert mixed, "no mixed prefill/decode iterations recorded"
    plain = [s for s in eng.stats
             if s.decode_slots and not s.prefill_slots and not s.arrivals]
    assert plain
    # one fused program -> same host-transfer count as a pure-decode step
    assert min(m.transfers for m in mixed) <= max(p.transfers for p in plain)


def test_serve_latency_metrics_deterministic(small_model):
    """Iteration-valued latencies are a pure function of the arrival
    schedule; wall-clock ones are positive and ordered sanely."""
    cfg, params = small_model
    reqs = _requests(5, 6, cfg.vocab_size)

    def run():
        _, finals, _ = _serve(cfg, params, reqs, GAPS)
        return finals

    a, b = run(), run()
    for rid, res in a.items():
        assert res.queue_delay_iters is not None
        assert res.ttft_iters is not None
        assert res.ttft_iters >= res.queue_delay_iters >= 0
        assert res.ttft_s >= res.queue_delay_s >= 0.0
        # no inter-token gap below 2 tokens: tpot_s is None there
        if len(res.tokens) < 2:
            assert res.tpot_s is None
        else:
            assert res.tpot_s >= 0.0
        assert b[rid].queue_delay_iters == res.queue_delay_iters
        assert b[rid].ttft_iters == res.ttft_iters
    summary = latency_summary(a.values())
    assert summary["n"] == len(reqs)
    assert summary["ttft_iters"]["p99"] >= summary["ttft_iters"]["p50"]


def test_serve_iterstats_counters(small_model):
    cfg, params = small_model
    reqs = _requests(9, 5, cfg.vocab_size, max_prompt=20)
    _, _, eng = _serve(cfg, params, reqs, [0, 1, 1, 2, 0])
    assert sum(s.arrivals for s in eng.stats) == len(reqs)
    assert any(s.queued > 0 for s in eng.stats) or len(reqs) <= 4
    assert any(s.prefill_slots > 0 for s in eng.stats)
    assert any(s.decode_slots > 0 for s in eng.stats)


def test_serve_idle_gaps_and_trailing_drain(small_model):
    """Quiet ticks between arrivals don't stall the watchdog, and the loop
    drains everything after the arrival stream closes."""
    cfg, params = small_model
    eng = _mk_engine(cfg, params, stall_limit=16)
    sched = [[ServeRequest(0, [3, 5], 3)]] + [[]] * 30 + \
            [[ServeRequest(1, [7, 11], 3)]]
    finals = [ev for ev in eng.serve(sched) if ev.finished]
    assert sorted(ev.req_id for ev in finals) == [0, 1]


def test_serve_offline_engines_unchanged(small_model):
    """run() after serve() on the same engine behaves offline again (the
    stream_chunks flag is scoped to the generator's lifetime)."""
    cfg, params = small_model
    eng = _mk_engine(cfg, params)
    for _ in eng.serve([[ServeRequest(0, [3, 5, 7], 3)]]):
        pass
    assert eng.stream_chunks is False
    eng.submit(ServeRequest(1, [5, 7, 11], 3))
    res = eng.run(max_iterations=100)
    assert {r.req_id for r in res} == {0, 1}


def test_serve_nan_fault_degrades_but_streams_identically(small_model):
    """A NaN fault during mixed waves degrades onto the oracle wave; the
    stream still matches the fault-free serve run (greedy oracle = same
    argmax)."""
    cfg, params = small_model
    reqs = _requests(13, 5, cfg.vocab_size)
    clean, _, _ = _serve(cfg, params, reqs, GAPS)
    faults = FaultInjector(seed=5, nan_p=0.3)
    noisy, _, eng = _serve(cfg, params, reqs, GAPS, faults=faults)
    assert noisy == clean
    assert eng.degraded_steps > 0


def test_percentile_nearest_rank():
    assert percentile([], 99) == 0.0
    assert percentile([5.0], 50) == 5.0
    vals = list(range(1, 101))
    assert percentile(vals, 50) == 50
    assert percentile(vals, 99) == 99
    assert percentile(vals, 100) == 100
    assert percentile([3, 1, 2], 0) == 1


# --------------------------------------------------------------------------
# FIFO-fairness property: under random arrival/deferral/preemption/cancel
# interleavings, no request is ever FIRST-admitted before an older
# still-admissible one, and every submitted request terminates.
# --------------------------------------------------------------------------
@settings(max_examples=4, deadline=None)
@given(st.integers(0, 10_000))
def test_serve_fifo_fairness_property(seed):
    cfg, params = _model()
    rng = np.random.default_rng(seed)
    n = int(rng.integers(4, 9))
    reqs = _requests(seed, n, cfg.vocab_size, max_prompt=24, max_new=8)
    gaps = [int(g) for g in rng.integers(0, 3, n)]
    # a tight paged pool so deferral + pool-pressure preemption fire, plus
    # injected admission faults for extra deferral interleavings
    eng = _mk_engine(cfg, params, kv_layout="paged", page_size=4,
                     num_pages=24, preempt_after=2,
                     faults=FaultInjector(seed=seed, admit_p=0.2))
    cancel_at = {int(rng.integers(2, 30)): int(rng.integers(0, n))
                 for _ in range(int(rng.integers(0, 3)))}
    finals = {}
    gen = eng.serve(_schedule(reqs, gaps))
    for ev in gen:
        if ev.finished:
            finals[ev.req_id] = ev.result
        rid = cancel_at.pop(eng.iteration, None)
        if rid is not None:
            eng.cancel(rid)
    # termination: one result per submitted request, valid reason
    assert set(finals) == {r.req_id for r in reqs}
    for res in finals.values():
        assert res.finished_reason in VALID_REASONS
    # FIFO first-admission order: submission order is req_id order here
    # (arrivals are scheduled in id order); preempted requests keep their
    # original admit_iteration, so requeues can't reorder this
    admits = [eng.admit_iteration[r.req_id] for r in reqs
              if r.req_id in eng.admit_iteration]
    assert admits == sorted(admits), (
        f"younger request first-admitted before an older admissible one: "
        f"{admits} (seed {seed})")
