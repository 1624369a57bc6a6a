"""Finds a cell's files by the names in BENCHMARK.json.

Everything that belongs to one configuration, traffic mix, cell or per-layer
metric sits in a file of its own, named after it:

    bench/configs/<config>.json    model sizes, as run, with their source
    bench/traffic/<traffic>.json   length profile and arrival process
    bench/cells/<workload>.json    engine sizes and offered rate of one cell
    bench/metrics/<metric>.py      reader of one per-layer metric

so a later change adds a cell, a model or a metric by adding files and
entries, and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


class SpecError(Exception):
    """The benchmark's files do not describe the cell asked for."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict        # bench/configs/<config>.json
    traffic: dict       # bench/traffic/<traffic>.json
    cell: dict          # bench/cells/<workload>.json
    end_to_end: list    # BENCHMARK.json metric entries this cell reports
    per_layer: list
    base: Path          # directory that holds BENCHMARK.json


def _load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing {path}") from None


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, benchmark: Path | None = None) -> Cell:
    bench_file = benchmark or ROOT / "BENCHMARK.json"
    spec = _load_json(bench_file)
    base = bench_file.resolve().parent
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in {bench_file.name}; "
                        f"known: {sorted(cells)}")
    w = cells[workload]
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=_load_json(base / "bench" / "configs" / f"{w['config']}.json"),
        traffic=_load_json(base / "bench" / "traffic" / f"{w['traffic']}.json"),
        cell=_load_json(base / "bench" / "cells" / f"{workload}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, workload)],
        base=base,
    )


def metric_reader(name: str, base: Path = ROOT):
    """The `read(ctx)` function of <base>/bench/metrics/<name>.py."""
    path = base / "bench" / "metrics" / f"{name}.py"
    if not path.exists():
        raise SpecError(f"no reader for per-layer metric {name!r}: {path}")
    mod_name = "bench_metric_" + "".join(
        c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
