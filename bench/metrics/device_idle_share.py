"""Device: share of the traced window in which no operation ran on the
chip, ``1 - union(op intervals) / window``, averaged over the chips the
cell uses (``trace_reduce.idle_share``)."""
import trace_reduce


def read(ctx):
    if ctx.trace is None:
        return None
    v = trace_reduce.idle_share(ctx.trace)
    return None if v is None else 100.0 * v
