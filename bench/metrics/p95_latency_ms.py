"""Front door: the 95th percentile of due-to-answer time over every
request due in the window (harness clock).  It stands beside the cell's
end-to-end ``p50_latency_ms`` without a bound: even at half the saturated
rate the tail of a synchronous-drain service swings by a quarter between
seeds, and one drain that snowballs moves it tenfold (PERF.md)."""
import numpy as np


def read(ctx):
    lat = [r.done_s - r.due_s for r in ctx.requests
           if r.done_s is not None and not r.failed]
    return 1e3 * float(np.percentile(lat, 95)) if lat else None
