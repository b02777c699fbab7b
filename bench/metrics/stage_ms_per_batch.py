"""Plan + stage (``BatchEngine._stage``: index ``gather_raw`` and the H2D
copy, blocked until the copy lands): the program's ``ServiceStats.load_s``
over its ``n_batches`` in the window, in ms per micro-batch."""


def read(ctx):
    n = ctx.stats["n_batches"]
    return 1e3 * ctx.stats["load_s"] / n if n else None
