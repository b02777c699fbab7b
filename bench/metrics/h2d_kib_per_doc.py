"""Plan + stage: bytes shipped host to device per scored candidate in the
window (``ServiceStats.h2d_bytes / n_rows``, exact counters), in KiB."""


def read(ctx):
    n = ctx.stats["n_rows"]
    return ctx.stats["h2d_bytes"] / n / 1024 if n else None
