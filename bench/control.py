"""The control of the correctness check, on the chip at a cell's own size.

For each seed it serves the cell's load for a short window exactly as a
benchmark run does, frees the program, and reads over the same sample of
served requests both the program's widest logit gap and that of the
control: the float32 reference computed with every matrix product's
operands rounded to fp8 (e4m3), the precision below the configuration's
bf16.  The limit in the cell file lies between the two.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import loadgen, run, spec, system, weights  # noqa: E402


def readings(cell_spec, seed: int, seconds: float) -> dict:
    config, cell = cell_spec.config, cell_spec.cell
    cfg = system.model_config(config)
    eng = system.build_engine(
        cfg, system.program_params(config, cfg,
                                    weights.model(config, seed)), cell)
    warm = run.warm_up(eng, cell, config["vocab_size"])
    arrivals = loadgen.schedule(cell_spec.traffic, float(cell["rate_per_s"]),
                                float(cell["ramp_s"]) + seconds,
                                config["vocab_size"], seed)
    rec, _ = run.serve_window(eng, arrivals, cell, seconds, None)
    del eng
    gc.collect()
    sample = run.sample_finished(cell, seed, rec, warm)
    return dict(seed=seed, **run.compare(config, seed, sample, control=True))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--benchmark", default=None)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    cell_spec = spec.load_cell(
        args.workload, Path(args.benchmark) if args.benchmark else None)
    run.check_devices(cell_spec.chips, args.rehearsal)
    enable_compile_cache()
    for seed in [int(s) for s in args.seeds.split(",")]:
        print(json.dumps(readings(cell_spec, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
