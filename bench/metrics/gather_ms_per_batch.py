"""Index gather (``index/store.py`` ``gather_raw``, the program's
``prettr.gather`` span on the prefetch thread): ``ServiceStats.gather_s``
over its ``n_batches`` in the window, in ms per micro-batch.  A part of
``stage_ms_per_batch``."""


def read(ctx):
    s, n = ctx.stats.get("gather_s"), ctx.stats.get("n_batches")
    return 1e3 * s / n if s is not None and n else None
