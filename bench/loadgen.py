"""The one traffic generator: turns a traffic file and a cell's rate into a
seeded open-loop schedule, and hands requests to the engine as they fall
due on the host clock.

Every seed of a cell gets the same schedule of request sizes and arrival
times, drawn once from the traffic file's `pool_seed`; the run's `--seed`
chooses the prompt token ids (and the benchmark's weights).  So two seeds
do the same work in the measured window, which is a slice of the
schedule: a seed that reordered the schedule would change which requests
fall in the window, and with them the window's numbers.
"""
from __future__ import annotations

import dataclasses
import math
import time

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    idx: int
    due: float              # seconds after the load starts
    prompt: list[int]
    max_new_tokens: int


def _lognormal_lengths(rng, profile: dict, n: int) -> np.ndarray:
    x = rng.lognormal(math.log(profile["median"]), profile["sigma"], n)
    return np.clip(x.astype(np.int64), profile["min"], profile["max"])


def _gaps(rng, arrivals: dict, rate: float, n: int) -> np.ndarray:
    process = arrivals["process"]
    if process == "poisson":
        return rng.exponential(1.0 / rate, n)
    if process == "gamma":
        # gamma gaps with coefficient of variation cv: shape 1 / cv^2
        shape = 1.0 / arrivals["cv"] ** 2
        return rng.gamma(shape, 1.0 / (rate * shape), n)
    raise ValueError(f"unknown arrival process {process!r}")


def request_count(rate: float, horizon_s: float) -> int:
    """Requests in a schedule that outlasts `horizon_s` at `rate`."""
    return int(math.ceil(rate * horizon_s * 1.25)) + 16


def schedule(traffic: dict, rate: float, horizon_s: float, vocab: int,
             seed: int) -> list[Arrival]:
    """Seeded arrivals for `horizon_s` seconds of load at `rate` req/s."""
    n = request_count(rate, horizon_s)
    pool = np.random.default_rng(traffic["pool_seed"])
    prompt_lens = _lognormal_lengths(pool, traffic["prompt_tokens"], n)
    output_lens = _lognormal_lengths(pool, traffic["output_tokens"], n)
    gaps = _gaps(pool, traffic["arrivals"], rate, n)
    due = np.cumsum(gaps)
    rng = np.random.default_rng(seed)
    return [Arrival(i, float(due[i]),
                    rng.integers(0, vocab, int(prompt_lens[i])).tolist(),
                    int(output_lens[i]))
            for i in range(n)]


class WindowClosed(Exception):
    """Raised from the arrival stream once the measured window has closed,
    which ends `serve()` without running its queue dry."""


class OpenLoop:
    """The arrival stream `PapiEngine.serve()` polls once per iteration.

    Each poll hands over every request whose due time has passed.  When
    nothing is queued or in flight it sleeps until the next due time, so
    the engine runs no empty steps.  `submitted[i]` is the host time at
    which request i was handed over; due times are `t0 + arrival.due`."""

    def __init__(self, arrivals: list[Arrival], make_request, idle,
                 span, clock=time.perf_counter):
        self.arrivals = arrivals
        self.make_request = make_request
        self.idle = idle
        self.span = span
        self.clock = clock
        self.t0 = 0.0
        self.stop_at = math.inf
        self.submitted: dict[int, float] = {}

    def start(self, t0: float) -> None:
        self.t0 = t0

    def __iter__(self):
        i, n = 0, len(self.arrivals)
        while True:
            with self.span("arrivals"):
                now = self.clock()
                if now >= self.stop_at:
                    raise WindowClosed
                if self.idle():
                    wake = self.t0 + self.arrivals[i].due if i < n else \
                        self.stop_at
                    wake = min(wake, self.stop_at)
                    if math.isinf(wake):
                        return          # nothing left to offer, no window
                    if wake > now:
                        time.sleep(wake - now)
                        now = self.clock()
                    if now >= self.stop_at:
                        raise WindowClosed
                batch = []
                while i < n and self.t0 + self.arrivals[i].due <= now:
                    a = self.arrivals[i]
                    self.submitted[a.idx] = now
                    batch.append(self.make_request(a))
                    i += 1
            yield batch or None
