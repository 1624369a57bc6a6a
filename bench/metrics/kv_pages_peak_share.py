"""Cache manager: peak share of the usable KV pages (all but the shared
garbage page) mapped in the window (program counters, IterStats)."""


def read(ctx):
    if not ctx.iters:
        return None
    usable = ctx.cell["engine"]["num_pages"] - 1
    return 100.0 * max(st.kv_pages_used for st in ctx.iters) / usable
