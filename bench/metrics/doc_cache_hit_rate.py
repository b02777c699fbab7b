"""Doc cache (``serving/doc_cache.py``): share of the window's candidate
rows served from the device page pools,
``n_doc_cache_hit / (n_doc_cache_hit + n_doc_cache_miss)``; nothing to
read where the service runs without a doc cache."""


def read(ctx):
    seen = ctx.stats["n_doc_cache_hit"] + ctx.stats["n_doc_cache_miss"]
    return 100.0 * ctx.stats["n_doc_cache_hit"] / seen if seen else None
