"""A tiny benchmark cell for CPU tests: a copy of bench/ data layout in a
temporary directory, with a 2-layer model small enough for interpret-mode
kernels, and helpers to run the harness in this process."""
from __future__ import annotations

import io
import json
import shutil
import sys
from contextlib import redirect_stderr, redirect_stdout
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

WORKLOAD = "tiny.short"

CONFIG = {
    "name": "tiny", "family": "dense", "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
    "hidden_act": "silu", "rms_norm_eps": 1e-06, "rope_theta": 10000.0,
    "tie_word_embeddings": True, "attention_bias": True,
    "torch_dtype": "bfloat16", "reduced": []}

TRAFFIC = {
    "name": "short",
    "prompt_tokens": {"median": 12, "sigma": 0.8, "min": 4, "max": 64},
    "output_tokens": {"median": 10, "sigma": 0.5, "min": 4, "max": 40},
    "arrivals": {"process": "poisson"}, "pool_seed": 0}

# widest logit gap of sound tiny runs about 0.003-0.005, of the fp8
# control about 0.08 (CPU, this size): the limit sits between
CELL = {
    "name": WORKLOAD,
    "engine": {"max_slots": 4, "page_size": 16, "prefill_len": 16,
               "max_blocks": 8, "num_pages": 33},
    "rate_per_s": 4.0, "ramp_s": 0.5,
    "correct": {"sample_tokens": 64, "widest_logit_gap_limit": 0.02}}


def make_bench(tmp: Path, extra_metrics: dict[str, str] | None = None) -> Path:
    """Write a BENCHMARK.json with the tiny cell under `tmp`, with the
    real bench/metrics readers (plus `extra_metrics`: name -> source) and
    bench/families; returns its path."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [{"name": WORKLOAD, "config": "tiny",
                          "traffic": "short", "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    bench = tmp / "bench"
    for sub in ("metrics", "families"):
        shutil.copytree(ROOT / "bench" / sub, bench / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for sub, name, data in (("configs", "tiny", CONFIG),
                            ("traffic", "short", TRAFFIC),
                            ("cells", WORKLOAD, CELL)):
        (bench / sub).mkdir(parents=True, exist_ok=True)
        (bench / sub / f"{name}.json").write_text(json.dumps(data))
    for name, source in (extra_metrics or {}).items():
        (bench / "metrics" / f"{name}.py").write_text(source)
        spec["per_layer"].append({
            "name": name, "unit": "%", "better": "higher",
            "source": "program_counter", "layer": "test",
            "moves": "output_tok_s"})
    path = tmp / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return path


def run_harness(bench_file: Path, seed: int = 7, trace: int = 0,
                seconds: float = 1.5, rehearsal: bool = True):
    """bench/run.py's main in this process: (exit code, result or None,
    stderr text)."""
    from bench import run

    argv = ["--workload", WORKLOAD, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace), "--benchmark",
            str(bench_file)]
    if rehearsal:
        argv.append("--rehearsal")
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = run.main(argv)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()
