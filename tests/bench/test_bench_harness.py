"""bench/run.py end to end on a tiny cell on the CPU, through the
rehearsal switch; and its refusal to run without a TPU."""
import json

from tiny_cell import ROOT, make_bench, run_harness

E2E = {"setup_s", "output_tok_s", "ttft_p90_ms", "itl_p50_ms", "itl_p99_ms"}
HOST_LAYER = {"submit_lag_p99_ms", "queue_wait_p90_ms", "slot_occupancy",
              "pim_step_share", "kv_pages_peak_share", "useful_row_share"}


def test_refuses_to_run_without_a_tpu(tmp_path):
    rc, result, err = run_harness(make_bench(tmp_path), rehearsal=False)
    assert rc == 1 and result is None
    assert "no TPU" in err


def test_refuses_a_directory_without_the_program(tmp_path):
    import shutil
    import subprocess
    import sys

    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-0.5b.creative",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_rehearsal_end_to_end(tmp_path):
    rc, result, err = run_harness(make_bench(tmp_path), seed=2**33 + 1)
    assert rc == 0, err
    assert result["correct"] is True, err
    assert set(result["metrics"]) == E2E
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["memory_peak_bytes"] is None
    assert list(result)[-1] == "checks"
    check = result["checks"]["widest_logit_gap"]
    assert 0 <= check["value"] <= check["limit"]
    assert err.strip().splitlines()[-1].startswith("check widest_logit_gap")


def test_traced_rehearsal_finds_a_new_metric_file(tmp_path):
    """A per-layer metric is a file under bench/metrics plus an entry in
    BENCHMARK.json: the harness finds it by name, and leaves out what reads
    nothing (device metrics, which a rehearsal cannot give)."""
    bench = make_bench(tmp_path, extra_metrics={
        "throwaway_share": "def read(ctx):\n    return 42.0\n"})
    rc, result, err = run_harness(bench, seed=5, trace=1)
    assert rc == 0, err
    assert result["correct"] is True, err
    assert set(result["metrics"]) == HOST_LAYER | {"throwaway_share"}
    assert result["metrics"]["throwaway_share"] == {"value": 42.0,
                                                    "unit": "%"}
    assert "busy_s" not in result["device"]


def test_benchmark_json_names_every_file():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        family = json.loads((ROOT / c["file"]).read_text())["family"]
        assert (ROOT / "bench/families" / f"{family}.py").exists()
    for w in spec["workloads"]:
        assert (ROOT / "bench/cells" / f"{w['name']}.json").exists()
        assert (ROOT / "bench/traffic" / f"{w['traffic']}.json").exists()
        assert w["chips"] == 1
    for m in spec["per_layer"]:
        assert (ROOT / "bench/metrics" / f"{m['name']}.py").exists()
