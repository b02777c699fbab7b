"""Scheduler (``BatchEngine.drain``): mean wait of a request's rows in the
FIFO row pool, from the request's enqueue to the start of staging of the
first micro-batch that holds one of its rows
(``ServiceStats.queue_wait_s / n_queue_waits``), in ms.  It is the wait
behind earlier requests' rows inside ``drain()``."""


def read(ctx):
    s, n = ctx.stats.get("queue_wait_s"), ctx.stats.get("n_queue_waits")
    return 1e3 * s / n if s is not None and n else None
