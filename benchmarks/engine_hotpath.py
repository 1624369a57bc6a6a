"""Engine hot-path microbenchmark: per-iteration wall time and host-device
transfer counts, device-resident (fused) vs the seed's host-looped reference.

Measures what PR 1 changed:
  * plain decode  — fused argmax-on-device vs eager greedy + fetch;
  * speculative   — one jitted scan (draft k steps + verify + accept +
    rewind, ONE fetch) vs k per-step fetches + a verify fetch + a per-slot
    Python accept loop.

Transfers are counted by the engine itself: every device->host sync goes
through `PapiEngine._fetch` (see engine.py docstring), so the numbers are
actual round-trip counts, not estimates.  Wall times are medians over
post-warmup iterations with `jax.block_until_ready` semantics implied by the
fetch in every iteration.

Writes BENCH_engine.json next to the repo root so the perf trajectory is
tracked from this PR onward.

``--mesh dp,tp`` switches to mesh mode: ONLY the unsharded-vs-sharded
engine A/B runs (§5.3 layout: FC-PIM banks on the tensor axis, KV sharded
per Attn-PIM unit), on dp*tp forced host devices, and the result is MERGED
into an existing BENCH_engine.json under a "sharded" key — the fused/legacy
baselines are never remeasured under forced devices (they timeshare the
cores and would silently inflate).  Mesh mode exits 1 if the sharded token
streams diverge from the unsharded engine's; on CPU its throughput delta
measures partitioning overhead, not speedup.

``--kv paged`` A/Bs the dense per-slot slab layout against the paged
Attn-PIM bank-row layout (`serving/kv_pages.py`): decode throughput and
peak *resident* KV bytes (dense always holds its full slabs; paged
residency is the page-pool watermark) on a mixed-length greedy +
speculative workload.  The paged engine's block tables are capped at the
dense slab's context (``max_blocks = cache_capacity / page_size``) so both
sides bound per-request context identically — the pool-wide default table
makes the XLA path gather a pool-sized view per decode step, which charges
the LAYOUT for a 4x context-bound mismatch (speculative pays it 5x per
iteration: k draft steps + the verify).  The section merges into
BENCH_engine.json under a "paged" key and the run exits 1 if the paged
token streams diverge from the dense engine's — the same identity gate as
``--mesh``.

The same invocation then A/Bs the paged SPECULATIVE engine's two
attention routes — XLA page-gather vs the windowed block-table Pallas
kernel (``attn_pim=True``: draft steps, TLP=k verify windows, and chunk
waves all resolve pages inside the kernel index_map; `gather_kv_pages`
never traces) — under the same exit-1 token-identity gate, merged under
"paged_spec_attn_pim".  On CPU both kernels run in interpret mode, so the
throughput delta measures interpret overhead, not the kernel: the win
(one streaming pass, no materialized pool view) is a TPU property; the
gate here is token identity.

``--long-prompt`` A/Bs chunked admission against the one-shot window: the
same engine code runs long prompts (up to 6x) through an 8-token prefill
window (chunk waves via `models.prefill_chunk`) and through a 128-token
window that holds every prompt one-shot — the pre-chunking admission path.
The section merges under a "long_prompt" key and the run exits 1 unless
every token stream (a short <= window prompt included) is bit-identical
across the two: chunking must change compile-shape economics, never
tokens.

``--pressure`` runs an oversubscribed paged trace (6x more page demand
than the pool holds, preemption enabled) against the unconstrained dense
reference: every request must COMPLETE with a bounded first-admission
delay — the pre-preemption engine deferred the head of the queue
indefinitely under a held pool — and the token streams must stay
bit-identical to the reference, both for requests that were never
preempted (the gate `tools/check_bench.py` enforces) and for the
preempted ones (requeue recomputes `prompt + tokens-so-far` through
chunked prefill).  Merges a "pressure" section into BENCH_engine.json.

``--arrivals RATE`` drives the continuous-batching streaming front end
(`PapiEngine.serve`) with a seeded Poisson arrival process (RATE requests
per iteration expected) across all four serving combos — greedy/speculative
x dense/paged — and checks each against the OFFLINE oracle (same requests,
`submit()` + `run()`): streamed tokens must be bit-identical, every request
must complete, and the iteration-valued latency percentiles (queue delay,
TTFT; deterministic for a fixed seed) plus wall-clock TTFT/TPOT p50/p99 are
merged under an "arrivals" key.  `tools/check_bench.py` gates completion,
identity, and a bounded p99 TTFT.  Exits 1 on any divergence or lost
request.

``--trace PATH`` (arrivals mode only) re-runs the spec_dense combo with a
live `repro.serving.telemetry.Tracer`, writes the Chrome trace to PATH,
and merges a "telemetry" section: traced vs untraced tok/s and median
per-iteration wall (the overhead_frac `tools/check_bench.py` gates at
TELEMETRY_OVERHEAD_CEIL) plus a tokens_bit_identical flag proving the
observation layer never perturbs the streams.

``--crash-recovery`` gates the durability layer (`serving/journal.py`):
each serving combo — greedy/speculative x dense/paged — runs against a
write-ahead journal, is killed mid-trace by the deterministic `crash`
fault at several iterations k, and a FRESH engine `restore()`s from the
journal and completes the trace.  The union of pre-crash durable finishes
(reconstructed from the journal alone) and post-crash results must cover
every request exactly once with token streams bit-identical to an
uninterrupted oracle run.  Merges a "recovery" section into
BENCH_engine.json (`tools/check_bench.py` gates completion, identity, and
zero duplicate finishes) and exits 1 on any divergence.

Usage:  PYTHONPATH=src python benchmarks/engine_hotpath.py [--spec-len 4]
        PYTHONPATH=src python benchmarks/engine_hotpath.py --mesh 1,8
        PYTHONPATH=src python benchmarks/engine_hotpath.py --kv paged
        PYTHONPATH=src python benchmarks/engine_hotpath.py --long-prompt
        PYTHONPATH=src python benchmarks/engine_hotpath.py --pressure
        PYTHONPATH=src python benchmarks/engine_hotpath.py --arrivals 0.5
        PYTHONPATH=src python benchmarks/engine_hotpath.py --crash-recovery
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

ROOT = Path(__file__).resolve().parent.parent


def run_engine(cfg, params, draft_params, *, fused: bool, spec_len: int,
               n_requests: int = 6, max_new: int = 20, mesh=None,
               max_new_fn=None, eos_token: int = 1, prefill_len: int = 8,
               cache_capacity: int = 64, prompt_fn=None, **engine_kw):
    from repro.serving import PapiEngine, ServeRequest
    draft = (cfg, draft_params) if spec_len > 1 else None
    eng = PapiEngine(
        cfg, params,
        max_slots=4, cache_capacity=cache_capacity, prefill_len=prefill_len,
        alpha=6.0, eos_token=eos_token, spec_len=spec_len, draft=draft,
        fused=fused, mesh=mesh, **engine_kw,
    )
    for i in range(n_requests):
        n = max_new_fn(i) if max_new_fn is not None else max_new
        prompt = prompt_fn(i) if prompt_fn is not None else [3 + i, 5, 7]
        eng.submit(ServeRequest(i, prompt, max_new_tokens=n))
    results = eng.run(max_iterations=400)

    # decode-only iterations after compile warmup (first 2 iterations carry
    # trace+compile time; admission iterations carry the prefill fetch)
    decode_iters = [s for s in eng.stats[2:] if s.new_tokens > 0]
    if not decode_iters:
        decode_iters = [s for s in eng.stats if s.new_tokens > 0]
    walls = [s.wall_s for s in decode_iters]
    transfers = [s.transfers for s in decode_iters]
    # KV memory accounting: dense reserves its full slabs for the whole
    # run; paged residency is the page-pool watermark (peak pages actually
    # mapped), the utilization win the paged layout exists for
    def cache_bytes(c):
        return sum(c[k2].size * c[k2].dtype.itemsize
                   for k2 in ("k", "v") if c is not None and k2 in c)

    reserved = cache_bytes(eng.cache) + cache_bytes(eng.draft_cache)
    if eng.kv is not None:
        per_page = reserved // (eng.kv.alloc.num_pages + 1)
        resident = eng.kv.alloc.watermark * per_page
    else:
        resident = reserved
    metrics = {
        "fused": fused,
        "spec_len": spec_len,
        "iterations": len(eng.stats),
        "decode_iterations_measured": len(decode_iters),
        "wall_s_per_iter_median": statistics.median(walls),
        "wall_s_per_iter_mean": statistics.fmean(walls),
        "transfers_per_iter_mean": statistics.fmean(transfers),
        "transfers_per_iter_max": max(transfers),
        "total_host_transfers": eng.host_transfers,
        "mean_accepted": statistics.fmean(
            s.accepted for s in decode_iters) if decode_iters else 0.0,
        "tokens": sum(len(r.tokens) for r in results),
        "tok_per_s": sum(s.new_tokens for s in decode_iters)
        / max(sum(walls), 1e-9),
        "kv_bytes_reserved": reserved,
        "kv_bytes_resident_peak": resident,
        "token_streams": [r.tokens for r in sorted(results,
                                                   key=lambda r: r.req_id)],
    }
    rep = eng.sanitize_report()
    if rep is not None:
        metrics["sanitize"] = rep.asdict()
    return metrics


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec-len", type=int, default=4)
    ap.add_argument("--mesh", default=None, metavar="DP,TP",
                    help="also A/B the mesh-sharded engine on dp*tp forced "
                         "host devices (e.g. 1,8)")
    ap.add_argument("--kv", choices=("dense", "paged"), default="dense",
                    help="'paged' A/Bs the dense vs paged KV layout "
                         "(throughput + resident KV bytes, equal context "
                         "bounds, token-identity gate) AND the paged "
                         "speculative engine's XLA-gather vs windowed "
                         "Pallas kernel routes; merges 'paged' + "
                         "'paged_spec_attn_pim' sections into the "
                         "existing BENCH_engine.json")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--long-prompt", action="store_true",
                    help="A/B chunked admission (8-token prefill window, "
                         "long prompts chunk through it) against a one-shot "
                         "window wide enough for every prompt; merges a "
                         "'long_prompt' section into --out and exits 1 if "
                         "any token stream differs (short prompts included "
                         "— they must be bit-identical to the pre-chunking "
                         "path)")
    ap.add_argument("--pressure", action="store_true",
                    help="oversubscribed paged trace (pool holds ~1/6 of "
                         "the requested pages, preemption enabled) vs the "
                         "unconstrained dense reference; merges a "
                         "'pressure' section into --out and exits 1 unless "
                         "every request completes with its reference token "
                         "stream (never-preempted AND preempted)")
    ap.add_argument("--arrivals", type=float, default=None, metavar="RATE",
                    help="drive the continuous-batching serve() loop with a "
                         "seeded Poisson arrival schedule (RATE requests "
                         "per iteration) across greedy/speculative x "
                         "dense/paged; gates streamed-token identity vs the "
                         "offline oracle and records queue-delay/TTFT/TPOT "
                         "p50/p99; merges an 'arrivals' section into --out")
    ap.add_argument("--trace", type=str, default=None, metavar="PATH",
                    help="(with --arrivals) re-run the spec_dense combo "
                         "under a live Tracer, write the Chrome trace to "
                         "PATH, and merge a 'telemetry' section (traced vs "
                         "untraced throughput + bit-identity) into --out")
    ap.add_argument("--crash-recovery", action="store_true",
                    help="durability gate: run greedy/speculative x "
                         "dense/paged against a write-ahead journal, kill "
                         "each with the deterministic 'crash' fault at "
                         "several iterations, restore() a fresh engine from "
                         "the journal, and require the union of pre/post-"
                         "crash streams to match the uninterrupted oracle "
                         "exactly-once; merges a 'recovery' section into "
                         "--out and exits 1 on any divergence")
    ap.add_argument("--sanitize", action="store_true",
                    help="run the plain-fused and speculative-fused engines "
                         "under the runtime sanitizer (transfer-guard allow-"
                         "scopes, rank-promotion-raise, per-iteration "
                         "transfer budget, zero-steady-state-recompile "
                         "census); merges a 'sanitize' section into --out "
                         "and exits 1 on any SanitizeError")
    ap.add_argument("--out", type=str, default=str(ROOT / "BENCH_engine.json"))
    args = ap.parse_args()

    if args.trace is not None and args.arrivals is None:
        print("--trace composes with --arrivals only (the telemetry A/B "
              "rides the continuous-batching trace)")
        return 2

    if sum((bool(args.mesh), args.kv == "paged", args.long_prompt,
            args.pressure, args.arrivals is not None,
            args.sanitize, args.crash_recovery)) > 1:
        # each mode is its own early-returning A/B section; combining them
        # would silently skip the other mode's identity gate
        print("--mesh / --kv paged / --long-prompt / --pressure / --arrivals "
              "/ --sanitize / --crash-recovery are separate A/B modes: run "
              "one per invocation (each merges its own section into --out)")
        return 2

    # mesh sizing must precede the first jax backend touch
    from repro.launch.mesh import (force_host_device_count, make_serving_mesh,
                                   parse_mesh)
    mesh_shape = parse_mesh(args.mesh) if args.mesh else None
    if mesh_shape is not None:
        force_host_device_count(mesh_shape[0] * mesh_shape[1])

    import jax

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import init_params

    enable_compile_cache()

    if mesh_shape is not None:
        # validate BEFORE any measurement so a short device count can't
        # waste the whole run
        dp, tp = mesh_shape
        if len(jax.devices()) < dp * tp:
            print(f"--mesh {dp},{tp} needs {dp * tp} devices, have "
                  f"{len(jax.devices())} (is xla_force_host_platform_"
                  "device_count already set lower in XLA_FLAGS?)")
            return 1

    cfg = get_config("qwen2-0.5b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    draft_params = init_params(cfg, jax.random.PRNGKey(9))

    if args.sanitize:
        # Sanitized smoke: the plain and speculative fused engines under
        # the runtime gates.  A SanitizeError (budget overrun, steady-state
        # retrace, guarded transfer, implicit rank promotion) exits 1; the
        # recorded section lets check_bench re-verify the budget numbers.
        from repro.debug import SanitizeError
        section = {}
        try:
            for mode, spec_len in (("plain_fused", 1),
                                   ("spec_fused", args.spec_len)):
                r = run_engine(cfg, params, draft_params,
                               fused=True, spec_len=spec_len, sanitize=True)
                section[mode] = r["sanitize"]
        except SanitizeError as exc:
            print(f"sanitize FAILED: {exc}")
            return 1
        out = Path(args.out)
        results = json.loads(out.read_text()) if out.exists() else {}
        results["sanitize"] = section
        out.write_text(json.dumps(results, indent=2) + "\n")
        for mode, rep in section.items():
            print(f"sanitize {mode}: {rep['steady_iterations']}/"
                  f"{rep['iterations']} steady iterations at "
                  f"{rep['transfers_per_steady_iter']:.2f} transfers/iter "
                  f"(budget {rep['transfer_budget']}), {rep['programs']} "
                  f"programs, {rep['recompiles']} steady-state recompiles")
        print(f"wrote {out}")
        return 0

    if args.crash_recovery:
        # Durability gate: crash each serving combo mid-trace at several
        # iterations k (deterministic `crash` fault), restore a FRESH
        # engine from the write-ahead journal, and require the union of
        # pre-crash durable finishes (reconstructed from the journal
        # alone) + post-crash results to cover every request exactly once,
        # bit-identical to the uninterrupted oracle.
        import tempfile

        from repro.serving import (EngineCrashError, FaultInjector,
                                   PapiEngine, ServeRequest, recover)

        eos = cfg.vocab_size - 1      # never fires with random-init weights
        crash_points = (2, 6, 11)
        n_requests = 5

        def build(spec_len, paged, submit=True, **kw):
            d = dict(max_slots=4, cache_capacity=64, prefill_len=8,
                     alpha=6.0, eos_token=eos, spec_len=1,
                     debug_invariants=True)
            if spec_len > 1:
                d.update(spec_len=spec_len, draft=(cfg, draft_params))
            if paged:
                d.update(kv_layout="paged", page_size=args.page_size)
            d.update(kw)
            eng = PapiEngine(cfg, params, **d)
            if submit:
                for i in range(n_requests):
                    eng.submit(ServeRequest(i, [3 + i, 5, 7],
                                            max_new_tokens=8 + 2 * i))
            return eng

        section = {"crash_points": list(crash_points), "modes": {}}
        failures: list[str] = []
        combos = {"greedy_dense": (1, False),
                  "spec_dense": (args.spec_len, False),
                  "greedy_paged": (1, True),
                  "spec_paged": (args.spec_len, True)}
        with tempfile.TemporaryDirectory() as td:
            for name, (spec_len, paged) in combos.items():
                oracle_eng = build(spec_len, paged)
                oracle = {r.req_id: r.tokens
                          for r in oracle_eng.run(max_iterations=400)}
                dup_total = resumed_total = torn_total = 0
                completed = identical = True
                for k in crash_points:
                    wal = str(Path(td) / f"{name}_{k}.wal")
                    eng = build(spec_len, paged, journal=wal,
                                faults=FaultInjector(seed=0, crash_p=1.0,
                                                     start=k, stop=k + 1))
                    try:
                        eng.run(max_iterations=400)
                        failures.append(
                            f"{name} k={k}: crash fault never fired")
                        continue
                    except EngineCrashError:
                        pass
                    # pre-crash durable finishes, from the journal ALONE
                    durable = {rid: f.tokens for rid, f in
                               recover(wal, eos_token=eos).finished.items()}
                    fresh = build(spec_len, paged, submit=False, journal=wal)
                    info = fresh.restore(wal)
                    resumed_total += info["resumed"]
                    torn_total += info["torn_bytes"]
                    after = {r.req_id: r.tokens
                             for r in fresh.run(max_iterations=400)}
                    dups = sorted(set(durable) & set(after))
                    dup_total += len(dups)
                    if dups:
                        failures.append(f"{name} k={k}: duplicate finishes "
                                        f"for req(s) {dups}")
                    union = dict(durable)
                    union.update(after)
                    if set(union) != set(oracle):
                        completed = False
                        failures.append(
                            f"{name} k={k}: lost request(s) "
                            f"{sorted(set(oracle) - set(union))}")
                    elif union != oracle:
                        identical = False
                        bad = sorted(r for r in oracle
                                     if union[r] != oracle[r])
                        failures.append(f"{name} k={k}: stream(s) diverged "
                                        f"from oracle for req(s) {bad}")
                section["modes"][name] = {
                    "requests": n_requests,
                    "completed": completed,
                    "duplicate_finishes": dup_total,
                    "tokens_bit_identical": identical and completed,
                    "resumed_requests_total": resumed_total,
                    "torn_bytes_total": torn_total,
                }
                print(f"crash-recovery {name}: crashes at {crash_points}, "
                      f"{resumed_total} resumed, {dup_total} duplicate "
                      f"finishes, union identical: "
                      f"{identical and completed}")
        out = Path(args.out)
        results = json.loads(out.read_text()) if out.exists() else {}
        results["recovery"] = section
        out.write_text(json.dumps(results, indent=2) + "\n")
        print(f"wrote {out}")
        if failures:
            for f in failures:
                print(f"crash-recovery FAILED: {f}")
            return 1
        return 0

    if args.long_prompt:
        # Chunked-prefill A/B: the SAME engine code with an 8-token window
        # (prompts >= 4x the window chunk through `models.prefill_chunk`)
        # vs a 128-token window (every prompt one-shot = the pre-chunking
        # admission path).  Request 0 is a short (<= window) prompt, so the
        # gate covers BOTH acceptance clauses: long prompts complete
        # untruncated AND short prompts stay bit-identical to the
        # pre-chunking engine.  Exits 1 on any stream divergence.
        vocab = cfg.vocab_size
        def prompt_fn(i):
            if i == 0:
                return [3, 5, 7]
            return [3 + (7 * i + j) % (vocab - 3) for j in range(32 + 4 * i)]
        eos = vocab - 1               # never fires with random-init weights
        common = dict(fused=True, spec_len=1, n_requests=5, max_new=12,
                      eos_token=eos, cache_capacity=256, prompt_fn=prompt_fn)
        chunked = run_engine(cfg, params, draft_params, prefill_len=8,
                             **common)
        oneshot = run_engine(cfg, params, draft_params, prefill_len=128,
                             **common)
        identical = chunked["token_streams"] == oneshot["token_streams"]
        longest = max(len(prompt_fn(i)) for i in range(5))
        section = {
            "window_chunked": 8,
            "window_oneshot": 128,
            "longest_prompt": longest,
            "chunked_tok_per_s": chunked["tok_per_s"],
            "oneshot_tok_per_s": oneshot["tok_per_s"],
            "tokens_bit_identical": identical,
        }
        out = Path(args.out)
        results = json.loads(out.read_text()) if out.exists() else {}
        results["long_prompt"] = section
        out.write_text(json.dumps(results, indent=2) + "\n")
        print(f"long-prompt (<= {longest} tokens through an 8-token "
              f"window): {chunked['tok_per_s']:.1f} tok/s chunked vs "
              f"{oneshot['tok_per_s']:.1f} tok/s one-shot, tokens "
              f"identical: {identical}")
        print(f"wrote {out}")
        if not identical:
            print("WARNING: chunked admission diverged from the one-shot "
                  "prefill token streams")
            return 1
        return 0

    if args.pressure:
        # Oversubscribed serving: 12 requests whose page budgets total 6x
        # the pool.  The pre-preemption engine deferred the queue head
        # indefinitely while two long-running requests held the pool; with
        # pool-pressure preemption the head is admitted within
        # `preempt_after` iterations of its first deferral, every request
        # completes, and the streams stay bit-identical to the
        # unconstrained dense reference — preempted requests included
        # (their requeue recomputes prompt + tokens-so-far through chunked
        # prefill).  First-admission delay (admit iteration - submit
        # iteration) is the bounded-wait metric check_bench gates.
        from repro.serving import PapiEngine, ServeRequest
        eos = cfg.vocab_size - 1      # never fires with random-init weights
        reqs = [([3 + i, 5, 7], 20) for i in range(12)]

        def serve(**kw):
            eng = PapiEngine(cfg, params, max_slots=4, prefill_len=8,
                             alpha=6.0, eos_token=eos, fused=True, **kw)
            for i, (prompt, n) in enumerate(reqs):
                eng.submit(ServeRequest(i, list(prompt), max_new_tokens=n))
            return {r.req_id: r for r in eng.run(max_iterations=2000)}, eng

        want, _ = serve(cache_capacity=64)
        got, eng = serve(cache_capacity=16, kv_layout="paged", page_size=4,
                         preempt_after=3, debug_invariants=True)

        completed = sum(r.finished_reason == "length" and len(r.tokens) == 20
                        for r in got.values())
        never = [i for i in got if i not in eng.preempted_ids]
        never_ok = all(got[i].tokens == want[i].tokens for i in never)
        preempted_ok = all(got[i].tokens == want[i].tokens
                           for i in eng.preempted_ids)
        delays = sorted(eng.admit_iteration[i] - eng.submit_iteration[i]
                        for i in got)
        pct = lambda q: delays[min(len(delays) - 1,
                                   int(q * (len(delays) - 1) + 0.999))]
        section = {
            "requests": len(reqs),
            "pool_pages": eng.kv.alloc.num_pages,
            "pages_demanded": len(reqs) * eng.kv.pages_for(3 + 20 + 1),
            "preempt_after": 3,
            "preemptions": eng.preemptions,
            "completed": completed,
            "iterations": eng.iteration,
            "admission_delay_p50": pct(0.50),
            "admission_delay_p99": pct(0.99),
            "admission_delay_max": delays[-1],
            # the check_bench-gated flag: never-preempted requests match
            # the unconstrained reference bit for bit
            "tokens_bit_identical": never_ok,
            "preempted_tokens_bit_identical": preempted_ok,
        }
        out = Path(args.out)
        results = json.loads(out.read_text()) if out.exists() else {}
        results["pressure"] = section
        out.write_text(json.dumps(results, indent=2) + "\n")
        print(f"pressure: {completed}/{len(reqs)} completed over a "
              f"{section['pages_demanded']}/{section['pool_pages']}-page "
              f"oversubscription, {eng.preemptions} preemptions, admission "
              f"delay p50/p99/max = {pct(0.5)}/{pct(0.99)}/{delays[-1]} "
              f"iterations, identical (never-preempted/preempted): "
              f"{never_ok}/{preempted_ok}")
        print(f"wrote {out}")
        if completed < len(reqs) or not (never_ok and preempted_ok):
            print("WARNING: oversubscribed trace lost requests or diverged "
                  "from the reference streams")
            return 1
        return 0

    if args.arrivals is not None:
        # Continuous-batching acceptance: a seeded Poisson arrival trace
        # through `serve()` must stream, for every combo the engine serves
        # (greedy/speculative x dense/paged), exactly the tokens the offline
        # batch oracle commits — live admission, mixed prefill/decode waves,
        # and the latency bookkeeping must be invisible to the streams.
        # Iteration-valued queue-delay/TTFT percentiles are deterministic
        # for the fixed seed (check_bench gates the p99 TTFT bound);
        # wall-clock TTFT/TPOT ride along for the perf trajectory.
        import numpy as np

        from repro.serving import PapiEngine, ServeRequest, latency_summary
        rate = args.arrivals
        if rate <= 0:
            print("--arrivals RATE must be > 0")
            return 2
        eos = cfg.vocab_size - 1      # never fires with random-init weights
        rng = np.random.default_rng(0)
        n_req = 10
        prompts = [[int(t) for t in
                    rng.integers(3, cfg.vocab_size - 1, int(rng.integers(3, 28)))]
                   for _ in range(n_req)]
        budgets = [int(rng.integers(4, 14)) for _ in range(n_req)]
        # Poisson process: exponential inter-arrival gaps, floored to the
        # engine's iteration clock (the serve loop polls once per iteration)
        arrive = np.cumsum(np.floor(
            rng.exponential(1.0 / rate, n_req)).astype(int))

        def requests():
            return [ServeRequest(i, list(prompts[i]),
                                 max_new_tokens=budgets[i])
                    for i in range(n_req)]

        def schedule():
            sched = [[] for _ in range(int(arrive[-1]) + 1)]
            for i, it in enumerate(arrive):
                sched[int(it)].append(ServeRequest(i, list(prompts[i]),
                                                   max_new_tokens=budgets[i]))
            return sched

        def engine(**kw):
            return PapiEngine(cfg, params, max_slots=4, cache_capacity=64,
                              prefill_len=8, alpha=6.0, eos_token=eos,
                              fused=True, debug_invariants=True, **kw)

        combos = [
            ("greedy_dense", {}),
            ("greedy_paged", dict(kv_layout="paged", page_size=args.page_size,
                                  max_blocks=64 // args.page_size)),
            ("spec_dense", dict(spec_len=args.spec_len,
                                draft=(cfg, draft_params))),
            ("spec_paged", dict(spec_len=args.spec_len,
                                draft=(cfg, draft_params),
                                kv_layout="paged", page_size=args.page_size,
                                max_blocks=64 // args.page_size)),
        ]
        section = {"rate": rate, "requests": n_req,
                   "arrival_span_iters": int(arrive[-1]), "modes": {}}
        all_ok = True
        spec_dense_ref = None     # (kw, oracle streams, live streams, engine)
        for label, kw in combos:
            oracle = engine(**kw)
            for r in requests():
                oracle.submit(r)
            want = {r.req_id: r.tokens
                    for r in oracle.run(max_iterations=2000)}

            eng = engine(**kw)
            streams, finals = {}, {}
            for ev in eng.serve(schedule()):
                if ev.finished:
                    finals[ev.req_id] = ev.result
                else:
                    streams.setdefault(ev.req_id, []).append(ev.token)
            live = {rid: res.tokens for rid, res in finals.items()}
            streamed_ok = all(streams.get(rid, []) == res.tokens
                              for rid, res in finals.items())
            same = live == want and streamed_ok
            completed = len(finals)
            summ = latency_summary(finals.values())
            section["modes"][label] = {
                "completed": completed,
                "tokens_bit_identical": same,
                "iterations": eng.iteration,
                "queue_delay_iters_p50": summ["queue_delay_iters"]["p50"],
                "queue_delay_iters_p99": summ["queue_delay_iters"]["p99"],
                "ttft_iters_p50": summ["ttft_iters"]["p50"],
                "ttft_iters_p99": summ["ttft_iters"]["p99"],
                "ttft_s_p50": summ["ttft_s"]["p50"],
                "ttft_s_p99": summ["ttft_s"]["p99"],
                "tpot_s_p50": summ["tpot_s"]["p50"],
                "tpot_s_p99": summ["tpot_s"]["p99"],
            }
            all_ok = all_ok and same and completed == n_req
            if label == "spec_dense":
                spec_dense_ref = (kw, want, live, eng)
            print(f"{label}: {completed}/{n_req} completed in "
                  f"{eng.iteration} iterations, ttft p50/p99 = "
                  f"{summ['ttft_iters']['p50']:.0f}/"
                  f"{summ['ttft_iters']['p99']:.0f} iters "
                  f"({summ['ttft_s']['p99'] * 1e3:.0f}ms p99), tpot p99 = "
                  f"{summ['tpot_s']['p99'] * 1e3:.1f}ms, tokens identical: "
                  f"{same}")

        # Telemetry overhead A/B: the SAME spec_dense arrival trace once
        # more, now with a live Tracer (typed events and phase spans, no
        # blocking), against the untraced run already measured above.
        # Identity proves observation never perturbs tokens; the median
        # per-iteration wall ratio is the overhead check_bench gates.
        telemetry = None
        if args.trace is not None:
            from repro.serving import Tracer, write_trace
            kw, want_sd, live_sd, eng_un = spec_dense_ref
            tracer = Tracer()
            eng_tr = engine(tracer=tracer, **kw)
            finals_tr = {}
            for ev in eng_tr.serve(schedule()):
                if ev.finished:
                    finals_tr[ev.req_id] = ev.result
            live_tr = {rid: res.tokens for rid, res in finals_tr.items()}
            t_same = live_tr == want_sd and live_tr == live_sd
            write_trace(tracer, args.trace, "chrome")

            def decode_walls(e):
                its = ([s for s in e.stats[2:] if s.new_tokens > 0]
                       or [s for s in e.stats if s.new_tokens > 0])
                walls = [s.wall_s for s in its]
                return (sum(s.new_tokens for s in its)
                        / max(sum(walls), 1e-9), statistics.median(walls))

            un_tps, un_med = decode_walls(eng_un)
            tr_tps, tr_med = decode_walls(eng_tr)
            # Overhead estimator: both runs execute the SAME deterministic
            # iteration sequence (identical tokens), so pair iterations by
            # index and take the median of per-iteration wall RATIOS —
            # workload variation cancels pairwise and a compile/GC spike in
            # either run is a single outlier ratio the median discards
            # (a ratio of unpaired medians flaked at ±7% on shared runners).
            pairs = [(u.wall_s, t.wall_s)
                     for u, t in zip(eng_un.stats[2:], eng_tr.stats[2:])
                     if u.new_tokens > 0 and t.new_tokens > 0]
            overhead = (statistics.median(t / u for u, t in pairs) - 1.0
                        if pairs else tr_med / un_med - 1.0)
            telemetry = {
                "mode": "spec_dense",
                "untraced_tok_per_s": un_tps,
                "traced_tok_per_s": tr_tps,
                "untraced_wall_s_per_iter_median": un_med,
                "traced_wall_s_per_iter_median": tr_med,
                "overhead_frac": overhead,
                "tokens_bit_identical": t_same,
                "events": tracer.emitted,
                "events_dropped": tracer.dropped,
                "phase_spans": tracer.counters.get("phase", 0),
                "trace_file": str(args.trace),
            }
            all_ok = all_ok and t_same
            print(f"telemetry: {un_tps:.1f} tok/s untraced vs "
                  f"{tr_tps:.1f} tok/s traced (median-wall overhead "
                  f"{telemetry['overhead_frac']:+.1%}), {tracer.emitted} "
                  f"events, {tracer.counters.get('phase', 0)} phase spans, "
                  f"tokens identical: {t_same}")
            print(f"wrote {args.trace}")

        out = Path(args.out)
        results = json.loads(out.read_text()) if out.exists() else {}
        results["arrivals"] = section
        if telemetry is not None:
            results["telemetry"] = telemetry
        out.write_text(json.dumps(results, indent=2) + "\n")
        print(f"wrote {out}")
        if not all_ok:
            print("WARNING: streamed tokens diverged from the offline "
                  "oracle or requests were lost under live arrivals")
            return 1
        return 0

    if args.kv == "paged":
        # Paged mode A/Bs dense-vs-paged (greedy + speculative, mixed
        # request lengths so admission/growth/rewind all run) and MERGES a
        # "paged" section into the tracked BENCH_engine.json — the
        # fused/legacy baselines are not remeasured.  Exit 1 if the paged
        # token streams diverge from the dense engine's (same gate as
        # --mesh): the layout must change memory economics, never tokens.
        # Both sides bound per-request context at the dense slab (64
        # tokens): max_blocks caps the table width so the XLA decode path
        # gathers a 64-token view, not the whole pool (see module
        # docstring).
        ragged = lambda i: 8 + 5 * i
        eos = cfg.vocab_size - 1      # never fires with random-init weights
        cache_capacity = 64           # the dense slab = the context bound
        common = dict(fused=True, max_new_fn=ragged, eos_token=eos,
                      cache_capacity=cache_capacity)
        max_blocks = max(cache_capacity // args.page_size, 1)
        paged_kw = dict(kv_layout="paged", page_size=args.page_size,
                        max_blocks=max_blocks)
        section = {"page_size": args.page_size, "max_blocks": max_blocks,
                   "modes": {}}
        identical = True
        dense_streams = gather = None
        for label, spec in (("plain", 1), ("speculative", args.spec_len)):
            dense = run_engine(cfg, params, draft_params, spec_len=spec,
                               **common)
            paged = run_engine(cfg, params, draft_params, spec_len=spec,
                               **common, **paged_kw)
            same = paged["token_streams"] == dense["token_streams"]
            identical = identical and same
            if label == "speculative":
                dense_streams = dense["token_streams"]
                gather = paged    # the XLA-gather side of the pim A/B below
            section["modes"][label] = {
                "dense_tok_per_s": dense["tok_per_s"],
                "paged_tok_per_s": paged["tok_per_s"],
                "dense_kv_bytes_resident": dense["kv_bytes_resident_peak"],
                "paged_kv_bytes_resident": paged["kv_bytes_resident_peak"],
                "paged_kv_bytes_reserved": paged["kv_bytes_reserved"],
                "tokens_bit_identical": same,
            }
            print(f"{label}: {dense['tok_per_s']:.1f} tok/s dense vs "
                  f"{paged['tok_per_s']:.1f} tok/s paged; resident KV "
                  f"{dense['kv_bytes_resident_peak'] / 1e6:.2f}MB -> "
                  f"{paged['kv_bytes_resident_peak'] / 1e6:.2f}MB, "
                  f"tokens identical: {same}")

        # Same run, second A/B: the paged SPECULATIVE engine's two attention
        # routes — XLA page-gather (the loop's paged speculative run,
        # reused, not remeasured) vs the windowed block-table Pallas kernel
        # (attn_pim=True: k draft steps + the TLP=k verify window all
        # resolve pages inside the index_map, gather_kv_pages never
        # traces).  Identity gated against BOTH the XLA-path paged engine
        # and the dense engine.  On CPU the kernel runs interpreted, so the
        # delta measures interpret overhead (see module docstring).
        kernel = run_engine(cfg, params, draft_params,
                            spec_len=args.spec_len, **common, **paged_kw,
                            attn_pim=True)
        pim_same = (kernel["token_streams"] == gather["token_streams"]
                    and kernel["token_streams"] == dense_streams)
        identical = identical and pim_same
        results_key = {
            "spec_len": args.spec_len,
            "page_size": args.page_size,
            "max_blocks": max_blocks,
            "xla_gather_tok_per_s": gather["tok_per_s"],
            "attn_pim_kernel_tok_per_s": kernel["tok_per_s"],
            "backend": jax.default_backend(),
            "kernel_interpreted": jax.default_backend() != "tpu",
            "tokens_bit_identical": pim_same,
        }
        print(f"paged_spec_attn_pim: {gather['tok_per_s']:.1f} tok/s "
              f"XLA-gather vs {kernel['tok_per_s']:.1f} tok/s windowed "
              f"kernel, tokens identical: {pim_same}")

        out = Path(args.out)
        results = json.loads(out.read_text()) if out.exists() else {}
        results["paged"] = section
        results["paged_spec_attn_pim"] = results_key
        out.write_text(json.dumps(results, indent=2) + "\n")
        print(f"wrote {out}")
        if not identical:
            print("WARNING: paged engine diverged from the dense token "
                  "streams")
            return 1
        return 0

    if mesh_shape is not None:
        # Mesh mode measures ONLY the unsharded-vs-sharded engine A/B —
        # both under the same forced-device environment, apples to apples —
        # and merges the section into an existing BENCH_engine.json, so the
        # tracked fused/legacy baselines stay genuine 1-device numbers
        # (forced host devices timeshare the cores and would inflate them).
        dp, tp = mesh_shape
        mesh = make_serving_mesh(dp, tp)
        single = run_engine(cfg, params, draft_params,
                            fused=True, spec_len=1)
        sharded = run_engine(cfg, params, draft_params,
                             fused=True, spec_len=1, mesh=mesh)
        section = {
            "mesh": {"data": dp, "model": tp},
            "devices": len(jax.devices()),
            "one_device_tok_per_s": single["tok_per_s"],
            "mesh_tok_per_s": sharded["tok_per_s"],
            "tokens_bit_identical":
                sharded["token_streams"] == single["token_streams"],
        }
        out = Path(args.out)
        results = json.loads(out.read_text()) if out.exists() else {}
        results["sharded"] = section
        out.write_text(json.dumps(results, indent=2) + "\n")
        print(f"mesh {dp}x{tp}: {single['tok_per_s']:.1f} tok/s (unsharded) "
              f"vs {sharded['tok_per_s']:.1f} tok/s (sharded), "
              f"tokens identical: {section['tokens_bit_identical']}")
        print(f"wrote {out}")
        if not section["tokens_bit_identical"]:
            print("WARNING: sharded engine diverged from the unsharded "
                  "token streams")
            return 1
        return 0

    results = {
        "backend": jax.default_backend(),
        "model": cfg.name,
        "plain": {
            "fused": run_engine(cfg, params, draft_params,
                                fused=True, spec_len=1),
            "legacy": run_engine(cfg, params, draft_params,
                                 fused=False, spec_len=1),
        },
        "speculative": {
            "fused": run_engine(cfg, params, draft_params,
                                fused=True, spec_len=args.spec_len),
            "legacy": run_engine(cfg, params, draft_params,
                                 fused=False, spec_len=args.spec_len),
        },
    }
    spec_f = results["speculative"]["fused"]
    spec_l = results["speculative"]["legacy"]
    results["summary"] = {
        "spec_transfer_reduction":
            spec_l["transfers_per_iter_mean"] / spec_f["transfers_per_iter_mean"],
        "spec_wall_speedup":
            spec_l["wall_s_per_iter_median"] / spec_f["wall_s_per_iter_median"],
        "plain_transfer_reduction":
            results["plain"]["legacy"]["transfers_per_iter_mean"]
            / results["plain"]["fused"]["transfers_per_iter_mean"],
    }

    # token streams feed the mesh-mode A/B; keep the JSON to the metrics
    for section in (results["plain"], results["speculative"]):
        for r in section.values():
            r.pop("token_streams", None)

    Path(args.out).write_text(json.dumps(results, indent=2) + "\n")
    s = results["summary"]
    print(f"spec_len={args.spec_len}: "
          f"transfers/iter {spec_l['transfers_per_iter_mean']:.2f} -> "
          f"{spec_f['transfers_per_iter_mean']:.2f} "
          f"({s['spec_transfer_reduction']:.1f}x reduction), "
          f"wall/iter {spec_l['wall_s_per_iter_median']*1e3:.1f}ms -> "
          f"{spec_f['wall_s_per_iter_median']*1e3:.1f}ms "
          f"({s['spec_wall_speedup']:.2f}x)")
    print(f"wrote {args.out}")
    ok = s["spec_transfer_reduction"] >= 2.0
    if not ok:
        print("WARNING: transfer reduction below the 2x acceptance bar")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
