"""Scheduler: share of the window's iterations whose FC projections ran on
the `pim` route (the fc_gemv kernel) rather than `pu` (XLA matmul)."""


def read(ctx):
    if not ctx.routes:
        return None
    return 100.0 * sum(r == "pim" for r in ctx.routes) / len(ctx.routes)
