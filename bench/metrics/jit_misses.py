"""Serving loop: dispatches in the window that grew their program's jit
cache, the sum of `IterStats.jit_misses` (a retrace, then a compile or a
load from the persistent cache; program counter).  Warm-up compiles every
program the window runs, so any other reading than 0 explains a stall.  A
program without the counter, or a CPU rehearsal, reports nothing."""


def read(ctx):
    if ctx.device_kind is None:
        return None
    misses = [getattr(st, "jit_misses", None) for st in ctx.iters]
    if not misses or None in misses:
        return None
    return float(sum(misses))
