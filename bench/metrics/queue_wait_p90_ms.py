"""Serving loop: 90th percentile, over requests due in the window, of the
wait from submit to first admission (the program's
`ServeResult.queue_delay_s`).  A request still queued when the window
closed counts at its wait so far."""
from bench.stats import percentile
from bench.window import due_in_window


def read(ctx):
    waits = []
    for i in due_in_window(ctx):
        res = ctx.rec.results.get(i)
        if res is not None and res.queue_delay_s is not None:
            waits.append(res.queue_delay_s)
        elif i in ctx.submitted:
            waits.append(ctx.t_close - ctx.submitted[i])
    v = percentile(waits, 90)
    return None if v is None else 1e3 * v
