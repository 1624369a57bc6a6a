"""Load generator: 99th percentile of how late requests due in the window
were handed to the engine (due time to submit, host clock).  A late
generator would be read as a fast server; this shows it."""
from bench.stats import percentile
from bench.window import due_in_window


def read(ctx):
    lags = [ctx.submitted[i] - (ctx.t0 + ctx.rec.due[i])
            for i in due_in_window(ctx) if i in ctx.submitted]
    v = percentile(lags, 99)
    return None if v is None else 1e3 * v
