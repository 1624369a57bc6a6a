"""Serving loop: median, over the window's iterations, of the host time of
one engine step (`IterStats.host_s`: the step's wall time less the time it
waited on the device in `_fetch`; program counter).  A program without the
counter, or a CPU rehearsal, reports nothing."""
from statistics import median


def read(ctx):
    if ctx.device_kind is None:
        return None
    host = [getattr(st, "host_s", None) for st in ctx.iters]
    if not host or None in host:
        return None
    return 1e3 * median(host)
