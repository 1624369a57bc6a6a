"""BENCH regression gate: fail CI when the tracked benchmark file regresses.

Checks over BENCH_engine.json (written/merged by
`benchmarks/engine_hotpath.py`):

  1. every ``tokens_bit_identical`` flag, anywhere in the file, is true —
     the A/B sections (--mesh, --kv paged, --long-prompt, the
     paged_spec_attn_pim kernel A/B) gate their own runs, but this catches
     a stale file whose sections were merged across runs;
  2. ``paged.modes.speculative.paged_tok_per_s`` stays at or above
     PAGED_SPEC_FLOOR of the dense speculative baseline recorded in the
     same section — the regression this guards is the one ISSUE 5 closed:
     speculative verify windows falling off the kernel/equal-context path
     and back onto a pool-wide `gather_kv_pages` view per decode step;
  3. the ``pressure`` section (the --pressure oversubscribed trace) shows
     every request COMPLETED and a p99 first-admission delay at or below
     PRESSURE_DELAY_CEIL iterations — the regression this guards is
     pool-pressure preemption silently dying and the queue head deferring
     indefinitely behind long-running requests (its
     ``tokens_bit_identical`` flag rides check 1);
  4. the ``arrivals`` section (the --arrivals continuous-batching trace)
     shows, for EVERY serving combo (greedy/speculative x dense/paged),
     all requests completed and a p99 TTFT at or below ARRIVALS_TTFT_CEIL
     iterations — the regressions this guards are the serve loop losing or
     stalling queued requests under live load and admission waves starving
     first tokens (streamed-vs-oracle identity rides check 1);
  5. the ``telemetry`` section (the --arrivals --trace observation A/B)
     shows a traced median per-iteration wall within
     TELEMETRY_OVERHEAD_CEIL of the untraced run and zero events dropped
     from the ring — the regressions this guards are the tracer hooks
     creeping onto the untraced hot path and the traced path growing a
     real per-dispatch cost (its ``tokens_bit_identical`` flag — tracing
     must never perturb streams — rides check 1);
  6. the ``sanitize`` section (the --sanitize runtime-sanitizer smoke)
     shows, for every recorded mode, at least one steady-state iteration,
     EXACTLY ``transfer_budget`` host transfers per steady fused decode
     iteration, and zero steady-state recompiles — the regressions this
     guards are an un-batched sync creeping onto the hot path and a flag
     flip retracing under an existing jit-cache key (the seed bug PL003
     checks statically);
  7. the ``recovery`` section (the --crash-recovery durability gate)
     shows, for EVERY serving combo (greedy/speculative x dense/paged),
     all requests completed across the crash, ZERO duplicate finishes,
     and recovered token streams bit-identical to the uninterrupted
     oracle — the regressions this guards are the write-ahead journal
     losing committed tokens, replay re-emitting a finished request, and
     the resumed-prefill path drifting off the deterministic re-decode.

A missing or truncated section is reported as a named-section failure
("BENCH section 'X' missing ...") with the engine_hotpath invocation that
produces it — never as a raw KeyError traceback.

Usage:  python tools/check_bench.py [path/to/BENCH_engine.json]
Exits non-zero with a message on the first violated check.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

# paged speculative must hold >= 80% of the dense speculative tok/s
# recorded in the same BENCH section (acceptance measured ~0.98x; 0.8
# leaves headroom for CI-runner noise without letting the gather creep
# back)
PAGED_SPEC_FLOOR = 0.8

# p99 first-admission delay ceiling (iterations) for the --pressure trace.
# Measured 27 on the 6x-oversubscribed 12-request trace (preempt_after=3);
# the trace is deterministic, so 60 is pure headroom against future trace
# tweaks — a dead preemption path shows up as hundreds of iterations (the
# head waits for full pool drains) or an outright incomplete run.
PRESSURE_DELAY_CEIL = 60

# p99 TTFT ceiling (iterations) for the --arrivals Poisson trace.  The
# schedule is seeded, so the iteration-valued TTFT is deterministic:
# measured p99 of 2 iterations across all four combos at rate 0.5 with 4
# slots; 16 is pure headroom against trace tweaks — a starved admission
# path (prefill stalling behind decodes, or waves never draining the
# queue) shows up as tens of iterations.
ARRIVALS_TTFT_CEIL = 16

# Traced-vs-untraced overhead ceiling for the --trace telemetry A/B: the
# traced spec_dense run's median per-iteration wall may exceed the
# untraced run's by at most this fraction.  The traced path adds one
# perf_counter pair + block_until_ready per dispatch — the untraced
# engine already syncs every iteration through `_fetch`, so the honest
# cost is bookkeeping, not a device sync.  Median over post-warmup decode
# iterations keeps CI-runner noise out of the ratio.
TELEMETRY_OVERHEAD_CEIL = 0.05


def iter_identity_flags(node, path=""):
    if isinstance(node, dict):
        for key, val in node.items():
            sub = f"{path}.{key}" if path else key
            if key == "tokens_bit_identical":
                yield sub, val
            else:
                yield from iter_identity_flags(val, sub)
    elif isinstance(node, list):
        for i, val in enumerate(node):
            yield from iter_identity_flags(val, f"{path}[{i}]")


def get_section(bench, dotted: str, hint: str, failures: list):
    """Walk a dotted path into the bench dict.

    On the first missing component, append a named-section failure (which
    component of which section, plus the invocation that writes it) and
    return None — callers never see a KeyError.
    """
    node = bench
    seen: list[str] = []
    for part in dotted.split("."):
        if not isinstance(node, dict) or part not in node:
            at = ".".join(seen + [part])
            failures.append(
                f"BENCH section '{dotted}' missing ('{at}' not found) — "
                f"run {hint}")
            return None
        seen.append(part)
        node = node[part]
    return node


def need_keys(section, name: str, keys: list, hint: str,
              failures: list) -> bool:
    """Require leaf keys inside an already-located section."""
    missing = [k for k in keys if k not in section]
    if missing:
        failures.append(
            f"BENCH section '{name}' incomplete (missing "
            f"{', '.join(missing)}) — run {hint}")
        return False
    return True


def main() -> int:
    path = Path(sys.argv[1]) if len(sys.argv) > 1 else (
        Path(__file__).resolve().parent.parent / "BENCH_engine.json")
    if not path.exists():
        print(f"check_bench: {path} not found (run "
              "benchmarks/engine_hotpath.py first)")
        return 1
    bench = json.loads(path.read_text())

    failures = []
    flags = list(iter_identity_flags(bench))
    if not flags:
        failures.append("no tokens_bit_identical flags found — the A/B "
                        "sections are missing")
    for where, ok in flags:
        if ok is not True:
            failures.append(f"{where} is {ok!r} (token streams diverged)")

    hint = "benchmarks/engine_hotpath.py --kv paged"
    spec = get_section(bench, "paged.modes.speculative", hint, failures)
    if spec is not None and need_keys(
            spec, "paged.modes.speculative",
            ["paged_tok_per_s", "dense_tok_per_s"], hint, failures):
        paged, dense = spec["paged_tok_per_s"], spec["dense_tok_per_s"]
        if paged < PAGED_SPEC_FLOOR * dense:
            failures.append(
                f"paged speculative regressed: {paged:.1f} tok/s < "
                f"{PAGED_SPEC_FLOOR:.0%} of the dense baseline "
                f"{dense:.1f} tok/s (ratio {paged / dense:.2f})")
        else:
            print(f"paged speculative: {paged:.1f} tok/s = "
                  f"{paged / dense:.2f}x dense ({dense:.1f} tok/s), floor "
                  f"{PAGED_SPEC_FLOOR:.2f} — OK")

    hint = "benchmarks/engine_hotpath.py --pressure"
    pressure = get_section(bench, "pressure", hint, failures)
    if pressure is not None and need_keys(
            pressure, "pressure",
            ["completed", "requests", "admission_delay_p99"],
            hint, failures):
        done, total = pressure["completed"], pressure["requests"]
        p99 = pressure["admission_delay_p99"]
        ok = True
        if done < total:
            failures.append(f"pressure trace lost requests: {done}/{total} "
                            "completed")
            ok = False
        if p99 > PRESSURE_DELAY_CEIL:
            failures.append(
                f"pressure admission delay unbounded: p99 {p99} iterations "
                f"> ceiling {PRESSURE_DELAY_CEIL} (preemption not relieving "
                "the deferring head?)")
            ok = False
        if ok:
            print(f"pressure: {done}/{total} completed, admission delay "
                  f"p99 {p99} <= {PRESSURE_DELAY_CEIL} iterations — OK")

    hint = "benchmarks/engine_hotpath.py --arrivals 0.5"
    arrivals = get_section(bench, "arrivals", hint, failures)
    if arrivals is not None and need_keys(
            arrivals, "arrivals", ["requests", "modes"], hint, failures):
        total = arrivals["requests"]
        modes = arrivals["modes"]
        bad = False
        for label, mode in sorted(modes.items()):
            done = mode.get("completed", 0)
            ttft = mode.get("ttft_iters_p99")
            if done < total:
                failures.append(f"arrivals/{label} lost requests: "
                                f"{done}/{total} completed under live load")
                bad = True
            if ttft is None or ttft > ARRIVALS_TTFT_CEIL:
                failures.append(
                    f"arrivals/{label} TTFT unbounded: p99 {ttft} iterations "
                    f"> ceiling {ARRIVALS_TTFT_CEIL} (admission waves "
                    "starving first tokens?)")
                bad = True
        if not bad and modes:
            worst = max(m["ttft_iters_p99"] for m in modes.values())
            print(f"arrivals: {len(modes)} combos completed {total}/{total}, "
                  f"worst p99 TTFT {worst:.0f} <= {ARRIVALS_TTFT_CEIL} "
                  "iterations — OK")
        elif not modes:
            failures.append("BENCH section 'arrivals' has no modes — run "
                            f"{hint}")

    hint = ("benchmarks/engine_hotpath.py --arrivals 0.5 "
            "--trace trace.telemetry.json")
    tel = get_section(bench, "telemetry", hint, failures)
    if tel is not None and need_keys(
            tel, "telemetry", ["overhead_frac", "events_dropped"],
            hint, failures):
        overhead = tel["overhead_frac"]
        dropped = tel["events_dropped"]
        if overhead > TELEMETRY_OVERHEAD_CEIL:
            failures.append(
                f"tracing overhead regressed: traced median wall "
                f"{overhead:+.1%} over untraced > ceiling "
                f"{TELEMETRY_OVERHEAD_CEIL:.0%} (a tracer hook grew a "
                "real per-step cost?)")
        if dropped:
            failures.append(
                f"telemetry ring dropped {dropped} events on the bench "
                "trace — capacity no longer covers a short serve run")
        if overhead <= TELEMETRY_OVERHEAD_CEIL and not dropped:
            print(f"telemetry: traced wall {overhead:+.1%} vs untraced "
                  f"(ceiling {TELEMETRY_OVERHEAD_CEIL:.0%}), "
                  f"{tel.get('events', '?')} events, 0 dropped — OK")

    hint = "benchmarks/engine_hotpath.py --sanitize"
    san = get_section(bench, "sanitize", hint, failures)
    if san is not None:
        if not san:
            failures.append(f"BENCH section 'sanitize' has no modes — run "
                            f"{hint}")
        for label, rep in sorted(san.items()):
            name = f"sanitize.{label}"
            if not need_keys(rep, name,
                             ["transfer_budget", "steady_iterations",
                              "transfers_per_steady_iter", "recompiles"],
                             hint, failures):
                continue
            ok = True
            if rep["steady_iterations"] <= 0:
                failures.append(
                    f"{name}: no steady-state iterations recorded — the "
                    "sanitized run never reached fused decode-only steps")
                ok = False
            if rep["transfers_per_steady_iter"] != rep["transfer_budget"]:
                failures.append(
                    f"{name}: {rep['transfers_per_steady_iter']:.2f} host "
                    f"transfers per steady iteration != budget "
                    f"{rep['transfer_budget']} — an un-batched sync crept "
                    "onto the hot path")
                ok = False
            if rep["recompiles"] != 0:
                failures.append(
                    f"{name}: {rep['recompiles']} steady-state recompiles "
                    "(a flag flip retraced under an existing jit-cache key)")
                ok = False
            if ok:
                print(f"{name}: {rep['steady_iterations']} steady "
                      f"iterations at exactly {rep['transfer_budget']} "
                      "transfer(s)/iter, 0 recompiles — OK")

    hint = "benchmarks/engine_hotpath.py --crash-recovery"
    rec = get_section(bench, "recovery", hint, failures)
    if rec is not None and need_keys(
            rec, "recovery", ["crash_points", "modes"], hint, failures):
        modes = rec["modes"]
        if not modes:
            failures.append(f"BENCH section 'recovery' has no modes — run "
                            f"{hint}")
        bad = False
        for label, mode in sorted(modes.items()):
            name = f"recovery.{label}"
            if not need_keys(mode, name,
                             ["completed", "duplicate_finishes",
                              "tokens_bit_identical"], hint, failures):
                bad = True
                continue
            if mode["completed"] is not True:
                failures.append(
                    f"{name}: requests lost across the crash (journal "
                    "replay dropped a submit/commit?)")
                bad = True
            if mode["duplicate_finishes"] != 0:
                failures.append(
                    f"{name}: {mode['duplicate_finishes']} duplicate "
                    "finish(es) — a request was re-emitted after its "
                    "finish record was already durable")
                bad = True
            # tokens_bit_identical itself rides check 1; report the
            # per-mode context here so the failure names the combo.
            if mode["tokens_bit_identical"] is not True:
                failures.append(
                    f"{name}: recovered streams diverged from the "
                    "uninterrupted oracle")
                bad = True
        if not bad and modes:
            print(f"recovery: {len(modes)} combos survived crashes at "
                  f"{rec['crash_points']} with exactly-once finishes and "
                  "bit-identical streams — OK")

    if failures:
        for f in failures:
            print(f"check_bench FAIL: {f}")
        return 1
    print(f"check_bench: {len(flags)} identity flags true, paged "
          "speculative above floor, pressure trace bounded, arrivals "
          "trace completed within the TTFT ceiling, telemetry overhead "
          "under the ceiling, sanitize budgets exact, crash recovery "
          "exactly-once and bit-identical")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
