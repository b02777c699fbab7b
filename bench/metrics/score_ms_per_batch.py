"""Scoring step (``BatchEngine._score_batch`` -> ``join_and_score``): the
program's ``ServiceStats.combine_s`` (dispatch through ``device_get`` of
the scores) over its ``n_batches`` in the window, in ms per micro-batch."""


def read(ctx):
    n = ctx.stats["n_batches"]
    return 1e3 * ctx.stats["combine_s"] / n if n else None
