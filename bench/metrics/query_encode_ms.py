"""Query encode (``RankingService.submit`` -> ``_query_reps``, the
program's ``prettr.encode`` span): ``ServiceStats.query_encode_s`` over
its ``n_requests`` in the window, in ms per request.  The span ends once
the query reps are ready on the device; a query-rep cache hit reads about
0."""


def read(ctx):
    s, n = ctx.stats.get("query_encode_s"), ctx.stats.get("n_requests")
    return 1e3 * s / n if s is not None and n else None
