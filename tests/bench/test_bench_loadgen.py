"""The traffic generator: deterministic per seed, the same work for every
seed, and the length profiles and arrival processes it is given."""
import json
import math
import statistics

import numpy as np
import pytest

from tiny_cell import ROOT
from bench import loadgen

CREATIVE = json.loads((ROOT / "bench/traffic/creative.json").read_text())


def test_same_seed_same_schedule():
    a = loadgen.schedule(CREATIVE, 3.0, 30.0, 1000, 2**33 + 5)
    b = loadgen.schedule(CREATIVE, 3.0, 30.0, 1000, 2**33 + 5)
    assert a == b


def test_seeds_share_the_schedule_and_differ_in_tokens():
    a = loadgen.schedule(CREATIVE, 3.0, 30.0, 1000, 1)
    b = loadgen.schedule(CREATIVE, 3.0, 30.0, 1000, 2)
    shape = lambda s: [(x.due, len(x.prompt), x.max_new_tokens) for x in s]  # noqa: E731
    assert shape(a) == shape(b)
    assert all(x.prompt != y.prompt for x, y in zip(a, b))


def test_creative_profile():
    s = loadgen.schedule(CREATIVE, 50.0, 100.0, 1000, 3)
    p = [len(x.prompt) for x in s]
    o = [x.max_new_tokens for x in s]
    assert len(s) == loadgen.request_count(50.0, 100.0)
    assert 4 <= min(p) and max(p) <= 2048 and 4 <= min(o) and max(o) <= 2048
    assert statistics.median(p) == pytest.approx(64, rel=0.05)
    assert statistics.median(o) == pytest.approx(320, rel=0.05)
    assert statistics.stdev(np.log(p)) == pytest.approx(0.6, rel=0.1)
    assert statistics.stdev(np.log(o)) == pytest.approx(0.7, rel=0.1)
    assert all(0 <= t < 1000 for x in s[:50] for t in x.prompt)


@pytest.mark.parametrize("process,cv", [({"process": "poisson"}, 1.0),
                                        ({"process": "gamma", "cv": 4.0}, 4.0)])
def test_arrival_processes(process, cv):
    traffic = dict(CREATIVE, arrivals=process)
    s = loadgen.schedule(traffic, 10.0, 2000.0, 1000, 4)
    gaps = np.diff([x.due for x in s])
    assert np.mean(gaps) == pytest.approx(0.1, rel=0.15)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(cv, rel=0.2)


def test_open_loop_hands_over_due_requests_and_closes():
    s = loadgen.schedule(CREATIVE, 5.0, 2.0, 100, 6)
    now = [0.0]
    clock = lambda: now[0]  # noqa: E731
    loop = loadgen.OpenLoop(s, lambda a: a.idx, lambda: False,
                            lambda name: __import__("contextlib").nullcontext(),
                            clock)
    loop.start(0.0)
    it = iter(loop)
    now[0] = s[3].due
    got = next(it)
    assert got == [0, 1, 2, 3]
    assert next(it) is None
    loop.stop_at = now[0]
    with pytest.raises(loadgen.WindowClosed):
        next(it)
    assert all(loop.submitted[i] == s[3].due for i in range(4))
    assert math.isinf(loadgen.OpenLoop(s, None, None, None).stop_at)
