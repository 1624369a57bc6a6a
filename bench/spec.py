"""Finds a cell's files by the names in BENCHMARK.json.

Everything that belongs to one configuration, traffic mix, cell or per-layer
metric sits in a file of its own, named after it:

    bench/configs/<config>.json    model sizes, as run, with their source
                                   and the `family` they belong to
    bench/families/<family>.py     one model family: the program's config
                                   and parameter tree, the weights' laws,
                                   the plain reference's layer and logits,
                                   and the work counts
    bench/traffic/<traffic>.json   length profile and arrival process
    bench/cells/<workload>.json    engine sizes and offered rate of one cell
    bench/metrics/<metric>.py      reader of one per-layer metric

so a later change adds a cell, a model, a model family or a metric by
adding files and entries, and edits none.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
# key that `load_cell` adds to a configuration: the directory that holds the
# BENCHMARK.json it came from, whose bench/families/ holds its family
BASE_KEY = "bench_base"


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
    config = _load_json(base / "bench" / "configs" / f"{w['config']}.json")
    family_module(config, base)
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=dict(config, **{BASE_KEY: str(base)}),
        traffic=_load_json(base / "bench" / "traffic" / f"{w['traffic']}.json"),
        cell=_load_json(base / "bench" / "cells" / f"{workload}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, workload)],
        base=base,
    )


def _load_module(prefix: str, name: str, path: Path):
    mod_name = prefix + "".join(c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_reader(name: str, base: Path = ROOT):
    """The `read(ctx)` function of <base>/bench/metrics/<name>.py."""
    path = base / "bench" / "metrics" / f"{name}.py"
    if not path.exists():
        raise SpecError(f"no reader for per-layer metric {name!r}: {path}")
    return _load_module("bench_metric_", name, path).read


@functools.lru_cache(maxsize=None)
def _family_at(name: str, path: str):
    if not Path(path).exists():
        raise SpecError(f"no file for model family {name!r}: {path}")
    return _load_module("bench_family_", name, Path(path))


def family_module(config: dict, base: Path = ROOT):
    """The module <base>/bench/families/<family>.py of the family that the
    configuration names under `family`, loaded once per path."""
    if "family" not in config:
        raise SpecError(f"configuration {config.get('name')!r} names no "
                        "family: add a \"family\" key, such as \"dense\", "
                        "that names a file in bench/families/")
    name = config["family"]
    return _family_at(name, str(Path(base) / "bench" / "families"
                                / f"{name}.py"))


def family(config: dict):
    """The family module of a configuration: under the benchmark that
    `load_cell` read it from, or under this checkout for one made in
    code."""
    return family_module(config, Path(config.get(BASE_KEY, ROOT)))
