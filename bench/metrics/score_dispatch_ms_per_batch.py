"""Scoring step, host side (the program's ``prettr.dispatch`` span: cache
insert issue, page-table ``put`` and the scoring jit's dispatch, up to the
``device_get`` of the scores): ``ServiceStats.dispatch_s`` over its
``n_batches`` in the window, in ms per micro-batch.  A part of
``score_ms_per_batch``."""


def read(ctx):
    s, n = ctx.stats.get("dispatch_s"), ctx.stats.get("n_batches")
    return 1e3 * s / n if s is not None and n else None
