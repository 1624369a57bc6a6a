"""Reduces a profiler trace (the `.xplane.pb` that `jax.profiler` writes) to
the numbers the per-layer metrics read: device busy time in the window,
device time per kind of operation, and the idle gaps, each labelled with the
harness span (`arrivals`, `serve.next`, `bookkeeping`) open on the host at
the time.

The window is the host span named `window` that bench/run.py opens and
closes around the measured window.  Device operations are the events of
the `XLA Ops` line of each `/device:TPU:<n>` plane.

    python3 bench/trace_reduce.py <trace dir or .xplane.pb>   # summary
"""
from __future__ import annotations

import bisect
import dataclasses
import gzip
import re
import sys
from collections import defaultdict
from pathlib import Path

HOST_SPANS = ("arrivals", "serve.next", "bookkeeping")
WINDOW_SPAN = "window"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = "/device:TPU:"
CONTAINERS = ("while", "conditional", "call")
KERNEL_PREFIX = "papi_"
TENSOR = re.compile(r"\b(bf16|f32|f16|s32|u32|s8|u8|pred)\[([0-9,]*)\]"
                    r"(\{[^}]*\})?")


@dataclasses.dataclass
class TraceSummary:
    window_s: float                 # length of the `window` span
    busy_s: float                   # union of device op intervals, per chip
    op_seconds: dict[str, float]    # device time by op kind, summed
    op_counts: dict[str, int]
    gaps: list[tuple[str, float]]   # (host span open at the gap, seconds)
    chips: int
    # Pallas kernel calls (op kinds starting `papi_`): (seconds, result,
    # operands), each tensor as (dtype, shape, in fast memory)
    kernel_calls: dict[str, list[tuple[float, tuple, list]]] = \
        dataclasses.field(default_factory=dict)
    # ops that copy one slice of a stacked array in HBM (a layer's weight
    # or K/V slab) into fast memory: (dtype, elements of the slice) ->
    # the seconds of each such op
    fast_slices: dict[tuple[str, int], list[float]] = \
        dataclasses.field(default_factory=dict)

    def kernel_seconds(self, kernel: str) -> float:
        """Device time of the calls of a Pallas kernel (an op kind), per
        chip."""
        return self.op_seconds.get(kernel, 0.0) / self.chips

    def kernel_count(self, kernel: str) -> int:
        return self.op_counts.get(kernel, 0)

    def breakdown(self) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:10]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _label(t: int, spans: list[tuple[int, int, str]],
           starts: list[int]) -> str:
    """Innermost harness span open at time t.  `spans` are sorted by start
    and nest at most a few deep, so only the last few that start before t
    can hold it."""
    k = bisect.bisect_right(starts, t)
    best = None
    for a, b, name in spans[max(0, k - 8):k]:
        if a <= t < b and (best is None or b - a < best[1] - best[0]):
            best = (a, b, name)
    return best[2] if best else "outside harness spans"


def _op_name(name: str) -> str | None:
    """The op kind of an `XLA Ops` event: its HLO instruction name up to the
    first dot (`%papi_fc_gemv.92 = ...` -> `papi_fc_gemv`, `%copy.56 = ...`
    -> `copy`); None for the control-flow ops that only contain others
    (`while`, `conditional`, `call`)."""
    kind = name.split(" = ", 1)[0].lstrip("%").split(".", 1)[0]
    if kind in CONTAINERS:
        return None
    return kind


def _tensors(name: str) -> list[tuple[str, tuple[int, ...], bool]]:
    """(dtype, shape, in fast memory) of an op's result and then its
    operands, read from the HLO text of its trace event.  A layout
    marked `S(1)` lives in the chip's fast on-core memory (XLA stages such
    operands there with copies of their own), not in HBM."""
    text = name.split(", custom_call_target", 1)[0]
    return [(m.group(1), tuple(int(d) for d in m.group(2).split(",") if d),
             "S(1)" in (m.group(3) or ""))
            for m in TENSOR.finditer(text)]


def _elements(shape: tuple[int, ...]) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


def _fast_slice(tensors) -> tuple[str, int] | None:
    """(dtype, elements) when an op's result lives in fast memory and its
    first operand is a stacked array in HBM of which the result is one
    slice along the leading axis; else None."""
    if len(tensors) < 2:
        return None
    (dtype, shape, fast), (in_dtype, in_shape, in_fast) = tensors[:2]
    if not fast or in_fast or dtype != in_dtype or len(in_shape) < 2:
        return None
    n = _elements(shape)
    if n < 2 or _elements(in_shape[1:]) != n:
        return None
    return dtype, n


def reduce_file(path: str | Path) -> TraceSummary:
    data = _load(path)
    window = None
    spans: list[tuple[int, int, str]] = []
    device_ops: dict[str, list[tuple[int, int, str]]] = {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (int(e.start_ns), int(e.start_ns + e.duration_ns),
                         _op_name(e.name), e.name) for e in line.events]
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (int(e.start_ns),
                                  int(e.start_ns + e.duration_ns))
                    elif e.name in HOST_SPANS:
                        spans.append((int(e.start_ns),
                                      int(e.start_ns + e.duration_ns),
                                      e.name))
    if window is None:
        raise ValueError(f"{path}: no '{WINDOW_SPAN}' span in the trace")
    if not device_ops:
        raise ValueError(f"{path}: no '{OPS_LINE}' line on any "
                         f"'{DEVICE_PLANE}*' plane")
    lo, hi = window
    spans.sort()
    starts = [a for a, _, _ in spans]
    op_seconds: dict[str, float] = defaultdict(float)
    op_counts: dict[str, int] = defaultdict(int)
    kernel_calls: dict[str, list] = defaultdict(list)
    fast_slices: dict[tuple[str, int], list[float]] = defaultdict(list)
    busy = 0
    gaps: list[tuple[str, float]] = []
    for plane, ops in sorted(device_ops.items()):
        inside = [(max(a, lo), min(b, hi), n, text) for a, b, n, text in ops
                  if b > lo and a < hi]
        for a, b, n, text in inside:
            if n is not None:
                op_seconds[n] += (b - a) * 1e-9
                op_counts[n] += 1
                t = _tensors(text)
                if n.startswith(KERNEL_PREFIX):
                    kernel_calls[n].append(((b - a) * 1e-9, t[0], t[1:]))
                elif (key := _fast_slice(t)) is not None:
                    fast_slices[key].append((b - a) * 1e-9)
        merged = _union([(a, b) for a, b, _, _ in inside])
        busy += sum(b - a for a, b in merged)
        if plane == min(device_ops):
            edges = [lo] + [x for ab in merged for x in ab] + [hi]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    gaps.append((_label((a + b) // 2, spans, starts),
                                 (b - a) * 1e-9))
    chips = len(device_ops)
    return TraceSummary(window_s=(hi - lo) * 1e-9,
                        busy_s=busy * 1e-9 / chips,
                        op_seconds=dict(op_seconds),
                        op_counts=dict(op_counts), gaps=gaps, chips=chips,
                        kernel_calls=dict(kernel_calls),
                        fast_slices=dict(fast_slices))


def _load(path: str | Path):
    """ProfileData of an `.xplane.pb`, or of a gzipped one (`.gz`)."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.suffix == ".gz":
        return ProfileData.from_serialized_xspace(
            gzip.decompress(path.read_bytes()))
    return ProfileData.from_file(str(path))


def find_xplane(path: str | Path) -> Path:
    path = Path(path)
    if path.is_file():
        return path
    found = sorted(path.rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def reduce_dir(path: str | Path) -> TraceSummary:
    return reduce_file(find_xplane(path))


if __name__ == "__main__":
    summary = reduce_dir(sys.argv[1])
    print(f"window {summary.window_s:.6f} s, busy {summary.busy_s:.6f} s, "
          f"idle share {1 - summary.busy_s / summary.window_s:.4f}")
    for name, s in summary.breakdown()["device_ops"]:
        print(f"  {s:.6f} s  {summary.op_counts[name]:6d}x  {name}")
    for name, s in summary.breakdown()["idle_gaps"]:
        print(f"  gap {s:.6f} s during {name}")
