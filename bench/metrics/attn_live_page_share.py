"""Kernels: the share of the block tables that paged attention reads.  The
mean, over the window's iterations that ran a decode or prefill step (the
paged attention kernel), of `IterStats.kv_pages_used` over max_slots x
max_blocks table entries (program counter).  The kernel's work follows it:
it reads the live pages, not every table entry.  A CPU rehearsal, or a
window with no such step, reports nothing."""


def read(ctx):
    if ctx.device_kind is None:
        return None
    ran = [st for st in ctx.iters if st.decode_slots or st.prefill_slots]
    if not ran:
        return None
    e = ctx.cell["engine"]
    entries = e["max_slots"] * e["max_blocks"]
    return 100.0 * sum(st.kv_pages_used for st in ran) / (entries * len(ran))
