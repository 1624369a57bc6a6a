"""Find a cell's knee once: offer Poisson load of the cell's traffic at a
list of rates, each for a fixed time on a fresh engine (same weights), and
report whether the queue and the in-flight count grow.  The knee is the
highest rate at which they do not; the cell file records 0.8 x it.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds 40 \\
        --rates 2,3,4,5
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import loadgen, run, spec, system, weights  # noqa: E402


def thirds(values):
    n = len(values)
    a, b = n // 3, 2 * n // 3
    mean = lambda xs: sum(xs) / len(xs) if xs else 0.0  # noqa: E731
    return mean(values[a:b]), mean(values[b:])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    cell_spec = spec.load_cell(args.workload)
    run.check_devices(cell_spec.chips, False)
    enable_compile_cache()
    config, cell = cell_spec.config, dict(cell_spec.cell, ramp_s=0.0)
    cfg = system.model_config(config)
    params = system.program_params(config, cfg,
                                   weights.model(config, args.seed))
    for rate in [float(r) for r in args.rates.split(",")]:
        eng = system.build_engine(cfg, params, cell)
        run.warm_up(eng, cell, config["vocab_size"])
        arrivals = loadgen.schedule(cell_spec.traffic, rate, args.seconds,
                                    config["vocab_size"], args.seed)
        rec, win = run.serve_window(eng, arrivals, cell, args.seconds, None)
        iters = eng.stats[win["stats_open"]:win["stats_close"]]
        queued = [st.queued for st in iters]
        inflight = [st.prefill_slots + st.decode_slots for st in iters]
        e2e, attempted, failed = run.end_to_end(rec, win)
        q_mid, q_end = thirds(queued)
        f_mid, f_end = thirds(inflight)
        print(json.dumps(dict(
            rate=rate, attempted=attempted, failed=failed,
            queued_mid=q_mid, queued_last=q_end, queued_final=queued[-1],
            inflight_mid=f_mid, inflight_last=f_end,
            iterations=len(iters), **e2e)), flush=True)
        del eng
    return 0


if __name__ == "__main__":
    sys.exit(main())
