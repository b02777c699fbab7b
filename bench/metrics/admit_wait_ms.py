"""Front door (``RankingService.submit`` / ``RankingRouter.submit``): mean
wait from a request's due time to the harness's call of ``submit``, over
every request of the window (harness clock).  A request that falls due
while a drain runs waits here."""


def read(ctx):
    w = [r.submit_s - r.due_s for r in ctx.requests if r.submit_s is not None]
    return 1e3 * sum(w) / len(w) if w else None
