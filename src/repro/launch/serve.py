"""Serving launcher: runs the PAPI engine against a synthetic request trace.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b-smoke \
        --requests 16 --spec-len 3 --alpha 6

Prints per-iteration scheduler decisions (RLP, TLP, AI estimate, chosen FC
path) — the runtime view of Figure 5(d).

Prompts of any length are served: admission chunks prompts longer than the
compiled 32-token prefill window through `models.prefill_chunk` (KV written
at running offsets, first output token from the final chunk), so the trace's
long-prompt tail is no longer truncated.  A prompt the KV budget cannot hold
at all is rejected honestly and reported.

Mesh serving (§5.3): ``--mesh dp,tp`` builds a (data, model) mesh and runs
the engine sharded — FC weights split one FC-PIM bank per `model` shard, KV
cache sliced one Attn-PIM unit per shard.  On a CPU host the launcher forces
dp*tp host devices automatically, so

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b-smoke \
        --requests 16 --mesh 1,8

runs the full 8-way tensor-parallel engine on one machine (token streams
are identical to the 1-device run — greedy argmax is invariant to the
partitioning's ulp-level logit shifts).  ``--attn-pim`` additionally routes
EVERY decode-path attention through the Pallas flash-decode kernel: plain
decode, the TLP>1 verify windows ``--spec-len`` produces (the windowed
kernel applies the intra-window causal mask), and chunked-prefill waves —
so the flag composes with speculative decoding and ``--kv paged`` instead
of silently reverting to the XLA path outside plain decode.

``--kv paged`` switches the KV cache to the Attn-PIM bank-row layout:
pooled fixed-size pages + per-slot block tables, page-budgeted admission
(a request enters iff pages for prompt + max_new + spec window are
available) — per-request context is bounded by the pool, not a uniform
slot.  Token streams are identical to ``--kv dense`` on any workload both
layouts can hold.  Composes with ``--attn-pim`` (block-table Pallas
kernel) and ``--mesh`` (KV-head-sharded paged pools).

Failure model: ``--deadline S`` bounds every request's wall clock (expired
requests finish with ``finished_reason="timeout"`` and their tokens-so-far),
and ``--fault kind[:prob]`` (repeatable; ``--fault-seed``) injects a
deterministic schedule of admission failures / NaN logits / kernel
corruption / step latency / engine crashes to exercise the engine's
graceful-degradation paths — see docs/ARCHITECTURE.md, "Failure model &
graceful degradation".

Durability: ``--journal PATH`` write-ahead-journals every submit / admit /
token commit / finish to PATH (append-only, checksummed records), and
``--resume PATH`` cold-starts the engine from a journal or snapshot left
by a crashed run — every unfinished request re-admits as
``prompt + committed-tokens`` and its stream continues bit-identically
(deadlines resume with their remaining budget).  Crash one run with
``--journal wal.j --fault crash:0.05``, then recover it with
``--journal wal.j --resume wal.j``.

Continuous batching: ``--arrivals RATE`` turns the trace into a LIVE
Poisson arrival stream served by `PapiEngine.serve` — requests are admitted
as they arrive, their prompt chunks ride the SAME device waves as running
decodes (no prefill stall), tokens stream as they commit, and the launcher
reports per-request queue delay / TTFT / TPOT plus p50/p99 aggregates.
Composes with every flag above (--kv paged, --spec-len, --mesh, --fault,
--deadline).
"""
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-slots", type=int, default=8)
    ap.add_argument("--alpha", type=float, default=6.0)
    ap.add_argument("--spec-len", type=int, default=1)
    ap.add_argument("--draft-arch", default=None)
    ap.add_argument("--task", default="general-qa")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default=None, metavar="DP,TP",
                    help="run mesh-sharded, e.g. '1,8' = 8-way tensor "
                         "parallel (FC-PIM banks / Attn-PIM KV shards)")
    ap.add_argument("--attn-pim", action="store_true",
                    help="decode attention through the Pallas flash-decode "
                         "kernel — plain decode, speculative verify "
                         "windows, and chunked-prefill waves alike "
                         "(sharded per KV shard under --mesh; block-table "
                         "kernel under --kv paged)")
    ap.add_argument("--kv", choices=("dense", "paged"), default="dense",
                    help="KV-cache layout: 'dense' per-slot slabs, or "
                         "'paged' Attn-PIM bank-row pages with block tables "
                         "and page-budgeted admission (long contexts share "
                         "one pooled budget instead of uniform slots)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (--kv paged; one Attn-PIM "
                         "bank row)")
    ap.add_argument("--max-blocks", type=int, default=None,
                    help="block-table width (--kv paged): caps per-request "
                         "context at max_blocks*page_size tokens and bounds "
                         "the XLA oracle path's gathered KV view (the "
                         "--attn-pim kernel never gathers); default = "
                         "the whole pool")
    ap.add_argument("--deadline", type=float, default=None, metavar="SECONDS",
                    help="per-request wall-clock budget from submit(); an "
                         "expired request finishes honestly with "
                         "finished_reason='timeout' and its tokens-so-far")
    ap.add_argument("--fault", action="append", default=[],
                    metavar="KIND[:PROB]",
                    help="inject a deterministic fault schedule (repeatable): "
                         "kinds admit / nan / kernel / latency / crash, "
                         "per-iteration probability PROB (default 1.0).  "
                         "E.g. '--fault nan:0.2 --fault admit:0.5'.  The "
                         "engine degrades gracefully instead of emitting "
                         "garbage ('crash' kills it mid-trace — recover "
                         "with --journal + --resume) — see "
                         "docs/ARCHITECTURE.md, 'Failure model'")
    ap.add_argument("--sanitize", action="store_true",
                    help="run under the tracing-discipline sanitizer "
                         "(repro.debug.sanitize): transfer-guard around "
                         "every step, rank-promotion-raise, a hard "
                         "one-transfer-per-steady-iteration budget, and "
                         "a zero-retrace compile census — aborts on the "
                         "first violated invariant")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed of the fault schedule (a pure function of "
                         "(seed, iteration), so runs replay exactly)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record the engine's typed event trace and write "
                         "it to PATH on exit — iteration and phase spans, "
                         "scheduler decisions (AI estimate vs alpha), "
                         "preemptions/deferrals/faults, page-pool "
                         "occupancy.  Summarize with "
                         "tools/trace_report.py")
    ap.add_argument("--trace-format", choices=("chrome", "jsonl"),
                    default="chrome",
                    help="trace serialization: 'chrome' opens in Perfetto / "
                         "chrome://tracing (one lane per slot + scheduler + "
                         "pool + programs), 'jsonl' is the raw typed events")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a Prometheus text-exposition snapshot of "
                         "the papi_engine_* counters/gauges on exit "
                         "(implies tracing even without --trace)")
    ap.add_argument("--log-level", default=None,
                    metavar="DEBUG|INFO|WARNING|ERROR",
                    help="wire the 'repro.serving' logger to stderr at this "
                         "level (deferral=DEBUG, preemption/unhappy "
                         "finishes=INFO, degraded steps=WARNING, "
                         "stalls=ERROR)")
    ap.add_argument("--journal", default=None, metavar="PATH",
                    help="write-ahead request journal: append-only "
                         "checksummed records (submit/admit/token-commit/"
                         "finish/cancel/preempt) to PATH, torn tail "
                         "auto-truncated on reopen; a crashed run recovers "
                         "with --resume PATH and its streams continue "
                         "bit-identically")
    ap.add_argument("--resume", default=None, metavar="PATH",
                    help="cold-start recovery: re-admit every unfinished "
                         "request from the journal or engine snapshot at "
                         "PATH (finished requests are never re-run; "
                         "deadlines keep their remaining budget) and serve "
                         "them instead of generating a fresh trace")
    ap.add_argument("--arrivals", type=float, default=None, metavar="RATE",
                    help="continuous-batching mode: the trace arrives LIVE "
                         "as a seeded Poisson process (RATE requests per "
                         "iteration expected) streaming through "
                         "PapiEngine.serve() — new prompts chunk-prefill in "
                         "the same waves as running decodes; prints "
                         "per-request queue-delay/TTFT/TPOT and the "
                         "p50/p99 latency summary")
    args = ap.parse_args()

    if args.log_level:
        import logging
        logging.basicConfig(
            level=getattr(logging, args.log_level.upper()),
            format="%(asctime)s %(levelname)-7s %(name)s: %(message)s")

    # Mesh sizing must happen before the first jax backend touch, hence the
    # deferred repro/jax imports below.
    from repro.launch.mesh import force_host_device_count, parse_mesh
    mesh_shape = parse_mesh(args.mesh) if args.mesh else None
    if mesh_shape is not None:
        force_host_device_count(mesh_shape[0] * mesh_shape[1])

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core.traces import generate_trace
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_serving_mesh
    from repro.models import init_params
    from repro.serving import (EngineCrashError, PapiEngine, ServeRequest,
                               Tracer, export_prometheus, parse_fault_specs,
                               write_trace)

    enable_compile_cache()
    mesh = None
    if mesh_shape is not None:
        dp, tp = mesh_shape
        n = len(jax.devices())
        if n < dp * tp:
            raise SystemExit(
                f"--mesh {dp},{tp} needs {dp * tp} devices, have {n} "
                "(set XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{dp * tp} before launch)")
        mesh = make_serving_mesh(dp, tp)
        print(f"mesh: {dict(mesh.shape)} over {dp * tp} of {n} "
              f"{jax.default_backend()} devices")

    cfg = get_config(args.arch)
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    draft = None
    if args.draft_arch:
        dcfg = get_config(args.draft_arch)
        draft = (dcfg, init_params(dcfg, jax.random.PRNGKey(args.seed + 1)))

    tracer = (Tracer() if (args.trace or args.metrics_out) else None)
    eng = PapiEngine(
        cfg, params, max_slots=args.max_slots, cache_capacity=256,
        prefill_len=32, alpha=args.alpha, spec_len=args.spec_len,
        draft=draft, mesh=mesh, attn_pim=args.attn_pim,
        kv_layout=args.kv, page_size=args.page_size,
        max_blocks=args.max_blocks,
        faults=parse_fault_specs(args.fault, seed=args.fault_seed),
        tracer=tracer, sanitize=args.sanitize, journal=args.journal,
    )
    if args.resume:
        info = eng.restore(args.resume)
        print(f"resumed {info['resumed']} unfinished request(s) from "
              f"{args.resume} ({info['finished']} already finished"
              + (f", {info['torn_bytes']} torn byte(s) discarded"
                 if info["torn_bytes"] else "") + ")")
    rng = np.random.default_rng(args.seed)
    # Prompts are no longer clamped to the prefill window — admission chunks
    # any prompt through it (32 tokens/wave here).  The cap below only keeps
    # the synthetic trace inside the dense slab budget (capacity 256 minus
    # the 64-token output cap and the speculative window); `--kv paged`
    # serves the same lengths from the pooled pages.
    max_prompt = 256 - 64 - max(args.spec_len, 1) - 1
    reqs = []
    if not args.resume:
        # a resumed run serves the recovered queue only: the crashed run
        # already journaled this trace's submits, and re-generating it
        # would collide with the recovered req_ids
        for i, req in enumerate(generate_trace(args.task, args.requests,
                                               args.seed)):
            prompt = rng.integers(3, cfg.vocab_size,
                                  size=min(req.input_len, max_prompt))
            reqs.append(ServeRequest(i, prompt.tolist(),
                                     max_new_tokens=min(req.output_len, 64),
                                     deadline_s=args.deadline))

    try:
        results = _run_trace(args, eng, reqs, rng)
    except EngineCrashError as exc:
        print(f"\nengine crashed (injected) at iteration {exc.iteration}"
              + (f"; recover with --resume {args.journal}" if args.journal
                 else " — run with --journal PATH to make crashes "
                      "recoverable"))
        raise SystemExit(1)
    _report(args, eng, results, tracer)


def _run_trace(args, eng, reqs, rng) -> list:
    import numpy as np

    from repro.serving import ServeRequest

    if args.arrivals is not None:
        # live mode: Poisson arrivals on the iteration clock, streamed
        # through the continuous-batching serve loop (a resumed run has an
        # empty arrival schedule — serve() just drains the recovered queue)
        from repro.serving import latency_summary
        sched: list[list[ServeRequest]] = [[]]
        if reqs:
            arrive = np.cumsum(np.floor(
                rng.exponential(1.0 / max(args.arrivals, 1e-9),
                                len(reqs))).astype(int))
            sched = [[] for _ in range(int(arrive[-1]) + 1)]
            for r, it in zip(reqs, arrive):
                sched[int(it)].append(r)
        results = []
        streamed = 0
        for ev in eng.serve(sched, max_iterations=2000):
            if not ev.finished:
                streamed += 1
                continue
            res = ev.result
            results.append(res)
            line = (f"req {res.req_id:3d}: {len(res.tokens):3d} tokens "
                    f"({res.finished_reason}), queue "
                    f"{res.queue_delay_iters} iters, ttft "
                    f"{res.ttft_iters} iters")
            if res.ttft_s is not None:
                line += f" / {res.ttft_s * 1e3:.0f}ms"
            if res.tpot_s is not None:
                line += f", tpot {res.tpot_s * 1e3:.1f}ms"
            print(line)
        summ = latency_summary(results)
        print(f"\nstreamed {streamed} tokens live over "
              f"{summ['n']} requests; latency percentiles:")
        for field in ("queue_delay_iters", "ttft_iters", "ttft_s", "tpot_s"):
            st = summ.get(field)
            if st is not None:
                unit = "iters" if field.endswith("iters") else "s"
                print(f"  {field:17s} p50 {st['p50']:9.3f}  "
                      f"p99 {st['p99']:9.3f}  ({unit})")
        return results
    for r in reqs:
        eng.submit(r)
    return eng.run(max_iterations=2000)


def _report(args, eng, results, tracer) -> None:
    by_reason: dict[str, int] = {}
    for r in results:
        by_reason[r.finished_reason] = by_reason.get(r.finished_reason, 0) + 1
    unhappy = sum(by_reason.get(k, 0)
                  for k in ("rejected", "timeout", "cancelled", "aborted"))
    print(f"\ncompleted {len(results) - unhappy} requests in "
          f"{eng.iteration} iterations"
          + (f" (unhappy: { {k: v for k, v in sorted(by_reason.items()) if k not in ('eos', 'length')} })"
             if unhappy else ""))
    if eng.preemptions or eng.degraded_steps or args.fault:
        fired = (dict(eng.faults.counts) if eng.faults is not None else {})
        print(f"resilience: {eng.preemptions} preemptions, "
              f"{eng.degraded_steps} degraded steps, faults fired {fired}")
    tok = sum(len(r.tokens) for r in results)
    wall = sum(s.wall_s for s in eng.stats)
    print(f"tokens: {tok}  wall: {wall:.2f}s  tok/s: {tok / max(wall, 1e-9):.1f}")
    print(f"reschedules: {eng.scheduler.num_reschedules}")
    rep = eng.sanitize_report()
    if rep is not None:
        print(f"sanitize: {rep.steady_iterations}/{rep.iterations} steady "
              f"iterations at {rep.transfers_per_steady_iter:.2f} "
              f"transfers/iter (budget {rep.transfer_budget}), "
              f"{rep.programs} programs, {rep.recompiles} steady-state "
              "recompiles")
    if eng.kv is not None:
        st = eng.kv.stats()
        frag = max((s.kv_fragmentation for s in eng.stats), default=0.0)
        print(f"kv pages: watermark {st.watermark}/{st.num_pages} "
              f"({st.page_size} tokens/page), peak fragmentation "
              f"{frag:.1%}")
    print("\niter  rlp tlp    AI  fc_path  new_toks")
    for s in eng.stats[:: max(len(eng.stats) // 20, 1)]:
        print(f"{s.iteration:5d} {s.rlp:4d} {s.tlp:3d} {s.ai_estimate:5.1f}  "
              f"{s.fc_variant:7s} {s.new_tokens:5d}")

    if tracer is not None:
        if args.trace:
            write_trace(tracer, args.trace, args.trace_format)
        if args.metrics_out:
            from pathlib import Path
            Path(args.metrics_out).write_text(export_prometheus(tracer))
        c = tracer.counters
        print(f"\ntelemetry: {tracer.emitted} events "
              f"({tracer.dropped} dropped), {c.get('scheduler_flip', 0)} "
              f"scheduler flips, {c.get('phase', 0)} phase spans"
              + (f" -> {args.trace}" if args.trace else "")
              + (f", metrics -> {args.metrics_out}"
                 if args.metrics_out else ""))
        if args.trace:
            print(f"  summarize: python tools/trace_report.py {args.trace}")


if __name__ == "__main__":
    main()
