"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's files (bench/spec.py) give the model, the traffic and the
engine's sizes.  One run, in order:

  1. makes the weights on the device from the seed and builds the engine;
  2. keeps JAX's compilation cache inside the checkout;
  3. warms the cell's programs: the admission prefill, and the mixed
     prefill/decode wave and the decode step on both FC routes, so that a
     route flip never compiles inside the window;
  4. offers the cell's open-loop load until the slots reach their steady
     occupancy (the cell's `ramp_s`);
  5. measures for `--seconds` (with `--trace 1`, under the profiler, and
     reports the per-layer metrics instead of the end-to-end ones);
  6. frees the program, runs the float32 reference over a seeded sample of
     the finished requests, and decides `correct`;
  7. prints the compared numbers beside their limits on stderr, and the
     result as one JSON line, last, on stdout.

Without a TPU (or with fewer chips than the cell asks for) it exits 1 and
prints no result.  `--rehearsal` runs the same path on the CPU for tests:
the device is labelled `cpu` and no device metric is reported.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import loadgen, spec, stats, system  # noqa: E402

WARM_ID = 1 << 30          # request ids of the warm-up requests
OUT_DIR = ROOT / ".bench_out"


class NoDevice(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def check_devices(chips: int, rehearsal: bool):
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if rehearsal:
        if platform != "cpu":
            raise NoDevice(f"--rehearsal runs on the CPU; JAX found {platform}")
        return devices
    if platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {len(devices)} {platform} "
                       "device(s)")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chip(s); JAX found "
                       f"{len(devices)}")
    return devices


class CompileCounter:
    """Counts XLA compilations (persistent-cache hits do not count)."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def _request(a: loadgen.Arrival):
    from repro.serving import ServeRequest
    return ServeRequest(a.idx, a.prompt, a.max_new_tokens)


def warm_up(eng, cell: dict, vocab: int) -> list[dict]:
    """Serve a few short requests on each FC route, so that every program
    the window can run is compiled before it opens.  The route policy is
    the program's own; only during warm-up is its threshold moved (and then
    put back) so that the other route runs too.  Returns the warm-up
    requests, which the correctness check compares like any other."""
    import numpy as np

    from repro.serving import ServeRequest

    p = cell["engine"]["prefill_len"]
    rng = np.random.default_rng(0)
    alpha = eng.scheduler.alpha
    done = []
    for k, threshold in enumerate((float("-inf"), alpha)):
        eng.scheduler.alpha = threshold
        # a prompt of three windows runs chunk waves on three iterations
        # (the first on the route set before this batch) beside a short
        # prompt that decodes meanwhile; both then decode a few steps
        reqs = [ServeRequest(WARM_ID + 2 * k, rng.integers(
                    0, vocab, 3 * p + 1).tolist(), 6),
                ServeRequest(WARM_ID + 2 * k + 1, rng.integers(
                    0, vocab, 5).tolist(), 10)]
        prompts = {r.req_id: r.prompt for r in reqs}
        for ev in eng.serve(iter([reqs])):
            if ev.finished:
                done.append({"prompt": prompts[ev.req_id],
                             "tokens": list(ev.result.tokens),
                             "reason": ev.reason})
    eng.scheduler.alpha = alpha
    ran = _routes_ran(eng.stats)
    want = {(r, w) for r in ("pu", "pim") for w in (True, False)}
    if not want <= ran:
        raise RuntimeError(f"warm-up ran (route, wave) {sorted(ran)}, "
                           f"not all of {sorted(want)}")
    return done


def _routes_ran(iters) -> set:
    """(FC route, was a mixed wave) of each iteration.  IterStats records
    the route decided for the NEXT iteration, so iteration i ran on the
    route recorded by iteration i - 1."""
    return {(prev.fc_variant, cur.prefill_slots > 0)
            for prev, cur in zip(iters, iters[1:])}


class Recorder:
    """Host-clock stamps of everything the window's metrics need."""

    def __init__(self, arrivals):
        self.due = {a.idx: a.due for a in arrivals}
        self.prompt = {a.idx: a.prompt for a in arrivals}
        self.stamps: dict[int, list[float]] = {}
        self.iters: dict[int, list[int]] = {}
        self.results: dict[int, object] = {}

    def event(self, ev, now: float) -> None:
        if ev.req_id >= WARM_ID:
            return
        if ev.finished:
            self.results[ev.req_id] = ev.result
        else:
            self.stamps.setdefault(ev.req_id, []).append(now)
            self.iters.setdefault(ev.req_id, []).append(ev.iteration)


def _span_factory(enabled: bool):
    if not enabled:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def serve_window(eng, arrivals, cell: dict, seconds: float, trace_dir,
                 clock=time.perf_counter):
    """Offer the load, open the window after the cell's ramp, measure for
    `seconds`.  Returns (recorder, window dict)."""
    import jax

    span = _span_factory(trace_dir is not None)
    idle = lambda: not (eng.queue or eng.active_slots)  # noqa: E731
    loop = loadgen.OpenLoop(arrivals, _request, idle, span, clock)
    rec = Recorder(arrivals)
    t0 = clock()
    loop.start(t0)
    t_open_at = t0 + float(cell["ramp_s"])
    win = {"t0": t0}
    gen = eng.serve(loop)
    window_span = None
    try:
        while True:
            if "t_open" not in win and clock() >= t_open_at:
                if trace_dir is not None:
                    # host spans and device ops; no Python function tracer
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    opts.host_tracer_level = 1
                    jax.profiler.start_trace(str(trace_dir),
                                             profiler_options=opts)
                    window_span = span("window")
                    window_span.__enter__()
                win["t_open"] = clock()
                win["t_close"] = win["t_open"] + seconds
                win["stats_open"] = len(eng.stats)
                loop.stop_at = win["t_close"]
            try:
                with span("serve.next"):
                    ev = next(gen)
            except (StopIteration, loadgen.WindowClosed):
                break
            with span("bookkeeping"):
                rec.event(ev, clock())
            if "t_close" in win and clock() >= win["t_close"]:
                break
    finally:
        win["stats_close"] = len(eng.stats)
        if window_span is not None:
            window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        gen.close()
    if "t_open" not in win:
        raise RuntimeError("the load ended before the window opened")
    # requests cut off by the close: their results (queue delays) too
    for res in eng.results:
        if res.req_id < WARM_ID and res.req_id not in rec.results:
            rec.results[res.req_id] = res
    win["submitted"] = dict(loop.submitted)
    return rec, win


def end_to_end(rec: Recorder, win: dict) -> tuple[dict, int, int]:
    """The cell's end-to-end metrics over the whole window, and
    (attempted, failed)."""
    t0, t_open, t_close = win["t0"], win["t_open"], win["t_close"]
    due_in = [i for i, d in rec.due.items() if t_open <= t0 + d < t_close]
    failed = 0
    ttft = []
    for i in due_in:
        res = rec.results.get(i)
        bad = (res is not None and res.finished_reason not in
               ("eos", "length", "aborted"))
        stamps = rec.stamps.get(i, [])
        first = stamps[0] if stamps else None
        failed += bad
        ttft.append(stats.censored_ttft(t0 + rec.due[i], first, bad,
                                        t_open, t_close))
    gaps = []
    tokens = 0
    for stamps in rec.stamps.values():
        gaps.extend(stats.token_gaps(stamps, t_open, t_close))
        tokens += sum(t_open <= t < t_close for t in stamps)
    metrics = {
        "output_tok_s": tokens / (t_close - t_open),
        "ttft_p90_ms": _ms(stats.percentile(ttft, 90)),
        "itl_p50_ms": _ms(stats.percentile(gaps, 50)),
        "itl_p99_ms": _ms(stats.percentile(gaps, 99)),
    }
    return metrics, len(due_in), failed


def _ms(x):
    return None if x is None else 1e3 * x


def sample_finished(cell: dict, seed: int, rec: Recorder,
                    warm: list[dict]) -> list[dict]:
    """The requests the reference checks: the warm-up requests (which ran
    both FC routes), the longest finished request, and others drawn from
    the seed until `sample_tokens` served tokens."""
    import numpy as np

    finished = [{"prompt": rec.prompt[i], "tokens": list(r.tokens)}
                for i, r in sorted(rec.results.items())
                if r.finished_reason in ("eos", "length") and r.tokens]
    sample = [w for w in warm if w["tokens"]]
    if finished:
        order = sorted(range(len(finished)),
                       key=lambda k: -len(finished[k]["tokens"]))
        rest = order[1:]
        picks = [order[0]] + [rest[k] for k in
                              np.random.default_rng(seed).permutation(
                                  len(rest))]
        served = 0
        for k in picks:
            sample.append(finished[k])
            served += len(finished[k]["tokens"])
            if served >= cell["correct"]["sample_tokens"]:
                break
    return sample


def compare(config: dict, seed: int, sample: list[dict],
            control: bool = False) -> dict:
    """The widest gap by which a served token's float32 reference logit
    lies below the reference's best, over the sample; with `control`, also
    that of the tokens the fp8 reference would have served."""
    from bench import reference

    got, alt = reference.served_gaps(config, seed,
                                     [s["prompt"] for s in sample],
                                     [s["tokens"] for s in sample], control)
    out = {"widest_logit_gap": float(max(g.max() for g in got)),
           "compared_tokens": int(sum(len(g) for g in got)),
           "compared_requests": len(sample)}
    if control:
        out["control_widest_logit_gap"] = float(max(a.max() for a in alt))
    return out


def per_layer(cell_spec, ctx) -> dict:
    out = {}
    for m in cell_spec.per_layer:
        value = spec.metric_reader(m["name"], cell_spec.base)(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(args) -> dict:
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cell_spec = spec.load_cell(args.workload,
                               Path(args.benchmark) if args.benchmark else None)
    devices = check_devices(cell_spec.chips, args.rehearsal)
    enable_compile_cache()
    compiles = CompileCounter()
    dev = devices[0]
    config, cell, traffic = cell_spec.config, cell_spec.cell, cell_spec.traffic

    from bench import weights

    cfg = system.model_config(config)
    params = system.program_params(config, cfg,
                                   weights.model(config, args.seed))
    eng = system.build_engine(cfg, params, cell)
    warm = warm_up(eng, cell, config["vocab_size"])
    arrivals = loadgen.schedule(traffic, float(cell["rate_per_s"]),
                                float(cell["ramp_s"]) + args.seconds,
                                config["vocab_size"], args.seed)
    trace_dir = None
    if args.trace and not args.rehearsal:
        trace_dir = OUT_DIR / f"{args.workload}.seed{args.seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    compiles_before = compiles.count
    rec, win = serve_window(eng, arrivals, cell, args.seconds, trace_dir)
    setup_s = win["t_open"] - PROCESS_START
    compiles_in_window = compiles.count - compiles_before
    before = eng.stats[win["stats_open"] - 1:win["stats_close"]]
    window_iters = before[1:]
    routes = [prev.fc_variant for prev in before[:-1]]
    mem = dev.memory_stats() if not args.rehearsal else None
    peak = mem.get("peak_bytes_in_use") if mem else None

    e2e, attempted, failed = end_to_end(rec, win)
    e2e["setup_s"] = setup_s
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"attempted": attempted, "failed": failed}
    breakdown = None
    if args.trace:
        from bench import trace_reduce
        summary = None
        if not args.rehearsal:
            summary = trace_reduce.reduce_dir(trace_dir)
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            breakdown = summary.breakdown()
            if not args.keep_trace:
                shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = types.SimpleNamespace(
            config=config, cell=cell, chips=cell_spec.chips,
            device_kind=None if args.rehearsal else dev.device_kind,
            t0=win["t0"], t_open=win["t_open"], t_close=win["t_close"],
            iters=window_iters, routes=routes, rec=rec,
            submitted=win["submitted"],
            trace=summary)
        metrics = per_layer(cell_spec, ctx)
    else:
        units = {m["name"]: m["unit"] for m in cell_spec.end_to_end}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in e2e.items() if k in units and v is not None}

    # the reference runs once the program's state is freed
    del eng, params
    gc.collect()
    check = compare(config, args.seed,
                    sample_finished(cell, args.seed, rec, warm))
    limit = float(cell["correct"]["widest_logit_gap_limit"])
    result.update(correct=check["widest_logit_gap"] <= limit,
                  metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {"widest_logit_gap": {
        "value": check["widest_logit_gap"], "limit": limit}}
    notes = dict(check, compiles_in_window=compiles_in_window,
                 window_iterations=len(window_iters),
                 setup_s=setup_s)
    return result, notes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--benchmark", default=None,
                    help="BENCHMARK.json to read (default: the checkout's)")
    ap.add_argument("--rehearsal", action="store_true",
                    help="run on the CPU, label the device cpu, report no "
                         "device metric (for tests)")
    ap.add_argument("--keep-trace", action="store_true",
                    help="keep the profiler's trace under .bench_out/")
    args = ap.parse_args(argv)
    try:
        result, notes = run(args)
    except NoDevice as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 1
    print("bench: " + json.dumps(notes), file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
