"""Dry-run machinery test at reduced scale: 16 fake devices, reduced archs,
full lower+compile through the real build_step/dryrun path.

Runs in a subprocess because XLA_FLAGS must be set before jax initializes
(the main pytest process keeps its single CPU device)."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import json
import jax
from repro.configs import get_config, SHAPES
from repro.configs.base import ShapeCell
from repro.launch.steps import build_step, lower_step
from repro.launch.hlo import HLOAnalysis
from repro.launch.mesh import auto_mesh

mesh = auto_mesh((4, 4), ("data", "model"))
out = {}
for arch, kind in [("qwen2-0.5b", "train"), ("mamba2-1.3b", "decode"),
                   ("olmoe-1b-7b", "prefill")]:
    cfg = get_config(arch).reduced()
    cell = ShapeCell("t", 64, 8, kind)
    built = build_step(cfg, cell, mesh)
    lowered = lower_step(built, mesh)
    compiled = lowered.compile()
    h = HLOAnalysis(compiled.as_text(), 16)
    out[arch] = {
        "flops": h.entry_cost.flops,
        "wire": h.entry_cost.collective_bytes,
        "mem": int(compiled.memory_analysis().temp_size_in_bytes),
    }
print(json.dumps(out))
"""


@pytest.mark.slow
def test_small_mesh_dryrun_all_kinds():
    repo = pathlib.Path(__file__).resolve().parents[1]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(PYTHONPATH=str(repo / "src"), JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=540, env=env, cwd=repo,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    for arch, stats in out.items():
        assert stats["flops"] > 0, arch          # dots found + counted
        assert stats["wire"] > 0, arch           # sharded => collectives
