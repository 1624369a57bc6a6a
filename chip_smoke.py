"""Smoke run of the PAPI serving path on a TPU — a start-up proof, not a
benchmark.

With no arguments it serves a seeded batch of requests through
`PapiEngine.serve()` at the full published width of qwen2-0.5b (random
weights from ``--seed``) on one chip, on the kernel routes: paged KV, the
block-table Attn-PIM kernel for every decode-path attention, and the
scheduler moving FC between "pu" (XLA matmul) and "pim" (the `fc_gemv`
kernel) as RLP decays.  The same requests are then served on the XLA
routes (dense KV, XLA attention, FC pinned to "pu"), and one decode step's
logits on the kernel routes are compared with the XLA routes on the same
cache.  Any failed check exits non-zero; the last line of a passing run is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Usage::

    python chip_smoke.py                 # one TPU
    python chip_smoke.py --mesh 2,2      # four TPUs: (data 2, model 2) mesh
                                         # against one device, nothing else
    JAX_PLATFORMS=cpu python chip_smoke.py --cpu --arch qwen2-0.5b-smoke

Without a TPU the script exits non-zero unless ``--cpu`` asks for a CPU
rehearsal (interpret-mode kernels, tiny configs only).  It runs in one
process and starts no children: a chip belongs to one process at a time.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Largest root-mean-square difference between the kernel-route and the
# XLA-route logits of one decode step, as a fraction of the XLA logits'
# standard deviation.  Both routes run in bf16, which keeps 8 significant
# bits, and they round at different points (the kernels accumulate in f32
# and round once per call; XLA fuses differently).  At qwen2-0.5b's depth on
# the CPU, in bf16 with the same bf16 weights, either route sits about 0.03
# of the logits' deviation from an f32 evaluation, and the two routes about
# 0.03 from each other (0.044 on a v5e chip).  A wrong block, head or page
# mapping gives logits that are uncorrelated with the reference: a
# difference of about 1.4.
LOGIT_RTOL = 0.1
TPU_KERNELS = ("papi_fc_gemv", "papi_paged_decode_attention")
# 8 slots, prompts of one to three prefill windows, 16-token KV pages; with
# budgets spread over 16-44 tokens RLP decays from 8 to 1, and alpha 4 puts
# the FC on "pu" above RLP 4 and on "pim" at or below it.
REQUESTS, PREFILL_LEN, PAGE_SIZE, ALPHA = 8, 64, 16, 4.0


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def make_requests(cfg, n: int, prefill_len: int, seed: int):
    """Seeded requests: prompts from one to three prefill windows long and a
    few dozen new tokens each, budgets spread so RLP decays step by step."""
    import numpy as np

    from repro.serving import ServeRequest
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(prefill_len // 2, 3 * prefill_len))
        prompt = rng.integers(3, cfg.vocab_size, size=plen).tolist()
        reqs.append(ServeRequest(i, prompt, max_new_tokens=16 + 4 * i))
    return reqs


def serve_all(eng, reqs, id_offset: int = 0):
    """Serve `reqs` (all arriving at once) to completion; returns
    ({req_id: ServeResult}, wall seconds, iterations)."""
    import dataclasses
    batch = [dataclasses.replace(r, req_id=r.req_id + id_offset)
             for r in reqs]
    it0 = eng.iteration
    t0 = time.perf_counter()
    done = {ev.req_id - id_offset: ev.result
            for ev in eng.serve(iter([batch])) if ev.finished}
    return done, time.perf_counter() - t0, eng.iteration - it0


def check_finished(done, reqs, eng, label: str) -> None:
    check(sorted(done) == [r.req_id for r in reqs],
          f"{label}: requests lost: {sorted(done)}")
    bad = {i: r.finished_reason for i, r in done.items()
           if r.finished_reason not in ("eos", "length")}
    check(not bad, f"{label}: requests finished badly: {bad}")
    check(eng.degraded_steps == 0,
          f"{label}: {eng.degraded_steps} step(s) had non-finite logits and "
          "were re-run on the XLA oracle path")


def agreement(a, b) -> tuple[int, int, int]:
    """(identical streams, tokens on a common prefix, tokens in a)."""
    same = sum(a[i].tokens == b[i].tokens for i in a)
    prefix = 0
    for i in a:
        for x, y in zip(a[i].tokens, b[i].tokens):
            if x != y:
                break
            prefix += 1
    return same, prefix, sum(len(r.tokens) for r in a.values())


class CompileClock:
    """Counts XLA compilations and sums their seconds.  Tracing is left
    out: a jitted call traced inside another program reports its own trace
    time within the outer one's, so trace durations overlap."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


def kernel_calls(compiled_text: str) -> list[str]:
    """Names of the compiled Pallas kernels (tpu_custom_call) in an HLO."""
    names = []
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.search(r"(papi_[a-z_]+)", line)
            names.append(m.group(1) if m else "?")
    return names


def prefilled_cache(cfg, params, reqs, prefill_len: int, page_size: int):
    """A paged cache holding each request's first prefill window, and the
    first output token of each (the input of the next decode step)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import init_paged_cache, prefill_to_pages

    b = len(reqs)
    blocks = -(-(prefill_len + 1) // page_size)
    cache = init_paged_cache(cfg, b, 1 + b * blocks, page_size, blocks)
    cache["block_tables"] = jnp.arange(1, 1 + b * blocks,
                                       dtype=jnp.int32).reshape(b, blocks)
    toks = np.zeros((b, prefill_len), np.int32)
    lens = np.zeros((b,), np.int32)
    for i, r in enumerate(reqs):
        p = r.prompt[:prefill_len]
        toks[i, :len(p)], lens[i] = p, len(p)
    batch = {"tokens": jnp.asarray(toks), "prompt_lens": jnp.asarray(lens)}
    first, cache = jax.jit(lambda p, bt, c: prefill_to_pages(
        cfg, p, bt, c, jnp.arange(b, dtype=jnp.int32)))(params, batch, cache)
    return cache, first


def step_logits(cfg, params, cache, first, fc: str, attn: str, mesh=None):
    """Logits of one decode step from `cache` with FC on route `fc` and
    attention on `attn`; under `mesh`, placed and partitioned as the
    engine does it.  Returns (logits, names of the compiled kernels)."""
    import contextlib

    import jax
    import numpy as np

    from jax.sharding import NamedSharding, PartitionSpec

    from repro.distributed.sharding import axis_rules, serve_rules
    from repro.models import (decode_step, paged_cache_shardings,
                              param_shardings)
    from repro.models.layers import attn_impl
    from repro.models.linear import fc_variant

    scope = contextlib.nullcontext()
    if mesh is not None:
        rules = serve_rules(attn_pim=True)
        _, num_pages, page_size = cache["k"].shape[:3]
        slots, blocks = cache["block_tables"].shape
        params = jax.device_put(params, param_shardings(cfg, rules, mesh))
        cache = jax.device_put(cache, paged_cache_shardings(
            cfg, slots, num_pages, page_size, blocks, rules, mesh))
        first = jax.device_put(first, NamedSharding(mesh, PartitionSpec()))
        scope = axis_rules(rules, mesh)

    # a new function per call: jit caches traces by function, and the
    # routes are read while tracing
    def step(p, c, t):
        return decode_step(cfg, p, c, t[:, None])[0]

    with scope, fc_variant(fc), attn_impl(attn):
        compiled = jax.jit(step).lower(params, cache, first).compile()
        logits = np.asarray(compiled(params, cache, first), np.float32)
    return logits, kernel_calls(compiled.as_text())


def compare_logits(got, ref, label: str) -> dict:
    """Check `got` against `ref` within LOGIT_RTOL (see there)."""
    import numpy as np

    check(bool(np.all(np.isfinite(got))), f"{label}: logits not finite")
    diff = got - ref
    err = float(np.sqrt(np.mean(diff ** 2)) / np.std(ref))
    check(err <= LOGIT_RTOL, f"{label}: logits off the reference by {err:.3e}"
          f" of the logits' deviation (tolerance {LOGIT_RTOL})")
    return {"rms": err,
            "max": float(np.max(np.abs(diff)) / np.max(np.abs(ref))),
            "argmax_same": int(np.sum(got.argmax(-1) == ref.argmax(-1))),
            "rows": int(np.prod(ref.shape[:-1]))}


def describe(lc: dict) -> str:
    return (f"differ by {lc['rms']:.4e} (rms, over the logits' deviation; "
            f"tolerance {LOGIT_RTOL}), {lc['max']:.4e} (max, over max "
            f"|logit|); argmax equal in {lc['argmax_same']}/{lc['rows']} rows")


def engine(cfg, params, *, kernels: bool, mesh=None, **kw):
    from repro.serving import PapiEngine
    if kernels:
        return PapiEngine(cfg, params, kv_layout="paged", attn_pim=True,
                          page_size=PAGE_SIZE, mesh=mesh, **kw)
    # XLA routes: dense KV, XLA attention, FC pinned to "pu" (any
    # estimate beats a negative threshold)
    return PapiEngine(cfg, params, mesh=mesh, **dict(kw, alpha=-1.0))


def peak_bytes(dev):
    stats = dev.memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default=None, metavar="DP,TP",
                    help="run only the mesh phase: the kernel-route engine "
                         "on a (data, model) mesh against one device")
    ap.add_argument("--cpu", action="store_true",
                    help="allow a run without a TPU (CPU rehearsal with "
                         "interpret-mode kernels)")
    args = ap.parse_args(argv)

    from repro.launch.mesh import force_host_device_count, parse_mesh
    mesh_shape = parse_mesh(args.mesh) if args.mesh else None
    if args.cpu and mesh_shape is not None:
        force_host_device_count(mesh_shape[0] * mesh_shape[1])

    import jax

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_serving_mesh
    from repro.models import init_params

    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.cpu:
        print(f"no TPU: JAX found {dev.platform} devices (pass --cpu for a "
              "CPU rehearsal)", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    cfg = get_config(args.arch)
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"{cfg.name} ({cfg.num_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.dtype}); compile cache {cache_dir}", flush=True)
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    reqs = make_requests(cfg, REQUESTS, PREFILL_LEN, args.seed)
    kw = dict(max_slots=REQUESTS, cache_capacity=256,
              prefill_len=PREFILL_LEN, alpha=ALPHA,
              debug_invariants=True)

    if mesh_shape is not None:
        dp, tp = mesh_shape
        check(len(devices) >= dp * tp,
              f"--mesh {args.mesh} needs {dp * tp} devices, have "
              f"{len(devices)}")
        one = engine(cfg, params, kernels=True, **kw)
        want, _, _ = serve_all(one, reqs)
        check_finished(want, reqs, one, "one device")
        mesh = make_serving_mesh(dp, tp)
        sharded = engine(cfg, params, kernels=True, mesh=mesh, **kw)
        got, wall, iters = serve_all(sharded, reqs)
        check_finished(got, reqs, sharded, f"mesh {dp}x{tp}")
        same, prefix, total = agreement(got, want)
        print(f"mesh {dict(mesh.shape)}: {same}/{len(reqs)} streams "
              f"identical to the one-device run, {prefix}/{total} tokens "
              f"on a common prefix; {iters} iterations in {wall:.3f} s "
              "(compile included)", flush=True)
        cache, first = prefilled_cache(cfg, params, reqs, PREFILL_LEN,
                                       PAGE_SIZE)
        ref, _ = step_logits(cfg, params, cache, first, "pim", "pim")
        for fc in ("pim", "pu"):
            out, _ = step_logits(cfg, params, cache, first, fc, "pim", mesh)
            lc = compare_logits(out, ref, f"mesh, fc {fc!r}")
            print(f"one decode step on the mesh, fc {fc!r} + Attn-PIM, vs one "
                  f"device: {describe(lc)}", flush=True)
    else:
        eng = engine(cfg, params, kernels=True, **kw)
        t0, c0 = clock.seconds, clock.count
        done, first_wall, first_iters = serve_all(eng, reqs)
        compile_s, n_compiles = clock.seconds - t0, clock.count - c0
        check_finished(done, reqs, eng, "kernel routes")
        routes = {s.fc_variant for s in eng.stats}
        check(routes >= {"pu", "pim"}, f"FC routes taken: {sorted(routes)}; "
              f"alpha {ALPHA} should give both")
        pim_iters = sum(s.fc_variant == "pim" for s in eng.stats)
        c0 = clock.count
        again, wall, iters = serve_all(eng, reqs, id_offset=len(reqs))
        check_finished(again, reqs, eng, "kernel routes, second pass")
        check(agreement(again, done)[0] == len(reqs),
              "second pass of the same requests changed the token streams")
        tokens = sum(len(r.tokens) for r in again.values())
        recompiles = clock.count - c0

        ref = engine(cfg, params, kernels=False, **kw)
        want, _, _ = serve_all(ref, reqs)
        check_finished(want, reqs, ref, "XLA routes")
        check({s.fc_variant for s in ref.stats} == {"pu"},
              "XLA-route engine left the 'pu' FC route")
        same, prefix, total = agreement(done, want)
        print(f"token streams: {same}/{len(reqs)} identical between kernel "
              f"and XLA routes, {prefix}/{total} tokens on a common prefix",
              flush=True)

        cache, first = prefilled_cache(cfg, params, reqs, PREFILL_LEN,
                                       PAGE_SIZE)
        got, calls = step_logits(cfg, params, cache, first, "pim", "pim")
        want, _ = step_logits(cfg, params, cache, first, "pu", "xla")
        lc = compare_logits(got, want, "kernel routes")
        if on_tpu:
            missing = [k for k in TPU_KERNELS if k not in calls]
            check(not missing, f"kernels not compiled for the TPU: {missing}"
                  f" (compiled custom calls: {sorted(set(calls))})")
        print(f"one decode step: kernel-route logits vs the XLA route "
              f"{describe(lc)}; compiled kernels "
              f"{sorted(set(calls)) or 'none (interpret)'}", flush=True)
        print(f"kernel routes: {first_iters} iterations ({pim_iters} on "
              f"'pim'); first pass {first_wall:.3f} s, of which "
              f"{compile_s:.3f} s compiling {n_compiles} XLA program(s)",
              flush=True)
        print(f"smoke timing, one {dev.device_kind} (not a benchmark): "
              f"{iters} iterations, {tokens} tokens in {wall:.4f} s = "
              f"{wall / iters * 1e3:.3f} ms/iteration, "
              f"{tokens / wall:.1f} tok/s; {recompiles} compile(s) in the "
              "timed pass", flush=True)

    print(f"peak_bytes_in_use: {peak_bytes(dev)}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as exc:
        print(f"FAILED: {exc}", file=sys.stderr)
        sys.exit(1)
