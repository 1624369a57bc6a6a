"""The cells' files: qwen2-0.5b.creative's pool holds the longest request
its traffic can send and about twice the pages that full slots reserve on
average, and a cell file carries only what the harness reads."""
import json
import math

import pytest

from tiny_cell import ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
KEYS = {"name", "engine", "pool_sizing", "rate_per_s", "ramp_s", "correct"}


def _files(name):
    w = next(w for w in SPEC["workloads"] if w["name"] == name)
    cell = json.loads((ROOT / "bench/cells" / f"{name}.json").read_text())
    traffic = json.loads(
        (ROOT / "bench/traffic" / f"{w['traffic']}.json").read_text())
    return cell, traffic


def test_pool_holds_the_longest_request_and_the_mean_load_twice():
    from bench import loadgen

    cell, traffic = _files("qwen2-0.5b.creative")
    e = cell["engine"]
    longest = traffic["prompt_tokens"]["max"] + traffic["output_tokens"]["max"]
    assert e["max_blocks"] * e["page_size"] >= longest
    # one page is the shared garbage page; admission reserves the prompt
    # and the whole output budget
    usable = e["num_pages"] - 1
    assert usable >= e["max_blocks"]
    arrivals = loadgen.schedule(traffic, 1.0, 4000.0, 1000, seed=1)
    mean = sum(math.ceil((len(a.prompt) + a.max_new_tokens) / e["page_size"])
               for a in arrivals) / len(arrivals)
    assert 1.9 <= usable / (e["max_slots"] * mean) <= 2.5


@pytest.mark.parametrize("name", CELLS)
def test_cell_file_holds_only_what_the_harness_reads(name):
    cell, _ = _files(name)
    assert set(cell) == KEYS and cell["name"] == name
    assert set(cell["engine"]) == {"max_slots", "page_size", "prefill_len",
                                   "max_blocks", "num_pages"}
    assert set(cell["correct"]) == {"sample_tokens",
                                    "widest_logit_gap_limit"}
    assert cell["rate_per_s"] > 0 and cell["ramp_s"] > 0
