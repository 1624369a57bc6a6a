"""Reads the serving program's own spans out of a profiler trace, beside what
bench/trace_reduce.py reads: the engine step and its phases
(`serving/telemetry.py` `phase`: `engine.admit`, `engine.prepare`,
`engine.dispatch`, `engine.fetch`, `engine.commit`, `engine.park`,
`engine.account`, ...), the serve loop's `serve.arrivals`, and Python's `gc`
pauses.  From them it gives:

  * the device's idle time in the `window` span split by the innermost
    host span open at each instant, harness span or program span (a gap
    that spans several is split among them), and each idle gap labelled,
    as bench/trace_reduce.py labels it, by the span open at its middle;
  * the device time of each execution of each compiled program, from the
    `XLA Modules` line (`jit_papi_decode_step(<hash>)` -> `papi_decode_step`);
  * the host time of each program span, by name.

    python3 bench/engine_trace.py <trace dir or .xplane.pb>   # summary
"""
from __future__ import annotations

import bisect
import dataclasses
import re
import sys
from collections import defaultdict
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace_reduce  # noqa: E402

STEP_SPAN = "engine.step"
ENGINE_SPANS = (STEP_SPAN, "engine.admit", "engine.admit.prefill",
                "engine.prepare", "engine.dispatch", "engine.fetch",
                "engine.commit", "engine.park", "engine.account",
                "serve.arrivals", "gc")
HOST_SPANS = trace_reduce.HOST_SPANS + ENGINE_SPANS
MODULES_LINE = "XLA Modules"
OUTSIDE = "outside harness spans"
PROGRAM = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")


@dataclasses.dataclass
class EngineTrace:
    window_s: float
    busy_s: float                      # union of device op intervals, chip 0
    gaps: list[tuple[str, float]]      # (span open at its middle, seconds)
    idle: dict[str, float]             # idle seconds by innermost span
    # device seconds of each execution that starts in the window, by
    # compiled program, chip 0
    programs: dict[str, list[float]]
    # host seconds of each program span that starts in the window, by name
    spans: dict[str, list[float]]

    def idle_by_gap_label(self) -> dict[str, float]:
        """Idle seconds by the span open at the middle of each gap."""
        out: dict[str, float] = defaultdict(float)
        for label, s in self.gaps:
            out[label] += s
        return dict(out)


def program_name(event_name: str) -> str:
    """`jit_papi_decode_step(123)` -> `papi_decode_step`: the jitted
    function's name, without the `jit_` prefix and the module's hash."""
    return PROGRAM.match(event_name).group(1)


def host_spans(data) -> tuple[tuple[int, int] | None,
                              list[tuple[int, int, str]], dict[int, int]]:
    """The `window` span, the named host spans (HOST_SPANS) as (start ns,
    end ns, name) sorted by start (the longer first among equal starts),
    and the step number of each `engine.step` span by its start, from every
    plane but the devices'."""
    window = None
    spans: list[tuple[int, int, str]] = []
    steps: dict[int, int] = {}
    names = set(HOST_SPANS)
    for plane in data.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PLANE):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == trace_reduce.WINDOW_SPAN:
                    window = (int(e.start_ns),
                              int(e.start_ns + e.duration_ns))
                elif e.name in names:
                    a = int(e.start_ns)
                    spans.append((a, int(e.start_ns + e.duration_ns),
                                  e.name))
                    if e.name == STEP_SPAN:
                        steps[a] = int(dict(e.stats)["step_num"])
    spans.sort(key=lambda s: (s[0], -s[1], s[2]))
    return window, spans, steps


def parents(spans: list[tuple[int, int, str]]) -> list[int]:
    """Index of the span that encloses each of `spans` (-1 for none); the
    spans of one thread nest."""
    parent: list[int] = []
    open_: list[int] = []
    for a, _, _ in spans:
        while open_ and spans[open_[-1]][1] <= a:
            open_.pop()
        parent.append(open_[-1] if open_ else -1)
        open_.append(len(parent) - 1)
    return parent


def label(t: int, spans: list[tuple[int, int, str]], starts: list[int],
          parent: list[int]) -> str:
    """Innermost host span open at time t: the last span to start before t,
    or the nearest of its enclosing spans that is still open."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0 and not spans[i][0] <= t < spans[i][1]:
        i = parent[i]
    return spans[i][2] if i >= 0 else OUTSIDE


def segments(spans: list[tuple[int, int, str]], lo: int, hi: int
             ) -> list[tuple[int, int, str]]:
    """[lo, hi) cut at every span boundary, each piece labelled with the
    innermost span open over it."""
    starts = [a for a, _, _ in spans]
    parent = parents(spans)
    cuts = sorted({lo, hi} | {x for a, b, _ in spans for x in (a, b)
                              if lo < x < hi})
    return [(a, b, label(a, spans, starts, parent))
            for a, b in zip(cuts, cuts[1:])]


def split(intervals: list[tuple[int, int]],
          pieces: list[tuple[int, int, str]]) -> dict[str, float]:
    """Seconds of the sorted, disjoint `intervals` that lie in each label's
    `pieces` (sorted, disjoint, covering them)."""
    out: dict[str, float] = defaultdict(float)
    j = 0
    for a, b in intervals:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            pa, pb, name = pieces[k]
            out[name] += (min(b, pb) - max(a, pa)) * 1e-9
            k += 1
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def read(path: str | Path) -> EngineTrace:
    data = trace_reduce._load(trace_reduce.find_xplane(path))
    window, spans, _ = host_spans(data)
    if window is None:
        raise ValueError(f"{path}: no '{trace_reduce.WINDOW_SPAN}' span")
    chips = sorted(p for p in data.planes
                   if p.name.startswith(trace_reduce.DEVICE_PLANE))
    if not chips:
        raise ValueError(f"{path}: no '{trace_reduce.DEVICE_PLANE}*' plane")
    lo, hi = window
    ops, modules = [], []
    for line in min(chips, key=lambda p: p.name).lines:
        if line.name == trace_reduce.OPS_LINE:
            ops = [(max(int(e.start_ns), lo),
                    min(int(e.start_ns + e.duration_ns), hi))
                   for e in line.events
                   if e.start_ns + e.duration_ns > lo and e.start_ns < hi]
        elif line.name == MODULES_LINE:
            modules = [(int(e.start_ns), e.duration_ns * 1e-9, e.name)
                       for e in line.events]
    merged = trace_reduce._union(ops)
    starts = [a for a, _, _ in spans]
    parent = parents(spans)
    edges = [lo] + [x for ab in merged for x in ab] + [hi]
    idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    gaps = [(label((a + b) // 2, spans, starts, parent), (b - a) * 1e-9)
            for a, b in idle]
    programs: dict[str, list[float]] = defaultdict(list)
    for a, seconds, name in modules:
        if lo <= a < hi:
            programs[program_name(name)].append(seconds)
    span_s: dict[str, list[float]] = defaultdict(list)
    engine = set(ENGINE_SPANS)
    for a, b, name in spans:
        if name in engine and lo <= a < hi:
            span_s[name].append((b - a) * 1e-9)
    return EngineTrace(window_s=(hi - lo) * 1e-9,
                       busy_s=sum(b - a for a, b in merged) * 1e-9,
                       gaps=gaps, idle=split(idle, segments(spans, lo, hi)),
                       programs=dict(programs),
                       spans=dict(span_s))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    t = read(argv[0])
    idle = t.window_s - t.busy_s
    print(f"window {t.window_s:.6f} s, busy {t.busy_s:.6f} s, "
          f"idle {idle:.6f} s ({100 * idle / t.window_s:.2f}%)")
    at_middle = t.idle_by_gap_label()
    print("idle by host span (split exactly; whole gaps by the span at "
          "their middle):")
    for name in sorted(set(t.idle) | set(at_middle),
                       key=lambda n: -t.idle.get(n, 0.0)):
        s, m = t.idle.get(name, 0.0), at_middle.get(name, 0.0)
        print(f"  {s:.6f} s  {100 * s / idle:6.2f}%  (middle "
              f"{100 * m / idle:6.2f}%)  {name}")
    print("longest gaps:")
    for name, s in sorted(t.gaps, key=lambda g: -g[1])[:10]:
        print(f"  {s:.6f} s  {name}")
    print("device time by program:")
    for name, runs in sorted(t.programs.items(), key=lambda kv: -sum(kv[1])):
        runs = sorted(runs)
        print(f"  {sum(runs):.6f} s  {len(runs):6d}x  median "
              f"{1e3 * runs[len(runs) // 2]:.3f} ms  {name}")
    print("host time by program span:")
    for name, durs in sorted(t.spans.items(), key=lambda kv: -sum(kv[1])):
        durs = sorted(durs)
        print(f"  {sum(durs):.6f} s  {len(durs):6d}x  median "
              f"{1e3 * durs[len(durs) // 2]:.3f} ms  {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
