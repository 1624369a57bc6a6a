"""Serving loop: Python GC pause time over the window, the sum of
`IterStats.gc_s` (each collection's pause, timed by the `gc.callbacks` hook
that the serve loop installs; program counter).  A program without the
counter, or a CPU rehearsal, reports nothing."""


def read(ctx):
    if ctx.device_kind is None:
        return None
    gc_s = [getattr(st, "gc_s", None) for st in ctx.iters]
    if not gc_s or None in gc_s:
        return None
    return 1e3 * sum(gc_s)
