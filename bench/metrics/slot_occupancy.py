"""Serving loop: mean share of the slots holding a request (prefilling or
decoding) over the window's iterations (program counters, IterStats)."""


def read(ctx):
    if not ctx.iters:
        return None
    slots = ctx.cell["engine"]["max_slots"]
    return 100.0 * sum((st.prefill_slots + st.decode_slots) / slots
                       for st in ctx.iters) / len(ctx.iters)
