"""Plan + stage (the prefetch pipeline): time the scoring thread spends
blocked on the next staged micro-batch (the program's ``prettr.stage_wait``
span, ``ServiceStats.stage_wait_s``) over its ``n_batches`` in the
window, in ms per micro-batch."""


def read(ctx):
    s, n = ctx.stats.get("stage_wait_s"), ctx.stats.get("n_batches")
    return 1e3 * s / n if s is not None and n else None
