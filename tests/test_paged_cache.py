"""The paged device doc cache and the int8-fused serving path.

What must hold:

* the paged cache (small token pages, page-table assembly) returns
  **bit-identical** scores to the whole-doc slot configuration and to the
  uncached service, across hit / miss / eviction, including docs that
  span multiple pages;
* ``plan`` is single-pass: a batch that pins many residents examines each
  LRU entry at most once (the O(capacity)-per-miss victim scan must not
  come back);
* the int8 index served through the paged cache decodes nothing on the
  host and dispatches no standalone decode jit — and still matches the
  uncached int8 service bit-for-bit;
* one pool-score call per micro-batch survives paging + bucketing
  (a fixed number of fused device dispatches, never per-doc or
  per-page).
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.prettr import PreTTRConfig, init_prettr, make_backbone
from repro.data.synthetic_ir import pack_query
from repro.index import IndexBuilder, TermRepIndex
from repro.serving import RankingService, RankRequest
from repro.serving.doc_cache import DeviceDocCache

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

MAX_Q, MAX_D = 8, 24
N_DOCS = 48


def _cfg(l=1, compress_dim=16, backend="blocked"):
    from repro.models.backend import impls_for
    attn_impl, compress_impl = impls_for(backend)
    bb = make_backbone(n_layers=4, d_model=64, n_heads=4, d_ff=128,
                       vocab_size=512, l=l, max_len=64,
                       compute_dtype=jnp.float32, block_kv=16, remat_block=2,
                       n_kv_heads=2, attn_impl=attn_impl,
                       compress_impl=compress_impl)
    return PreTTRConfig(backbone=bb, l=l, max_query_len=MAX_Q,
                        max_doc_len=MAX_D, compress_dim=compress_dim,
                        store_dtype=jnp.float16)


@pytest.fixture(scope="module")
def paged_world(tmp_path_factory):
    """Variable-length corpus (so docs span 1..3 pages at page_tokens=8)
    indexed twice: fp16 streams and int8 + int8 K/V."""
    cfg = _cfg()
    params, _ = init_prettr(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    lens = rng.integers(MAX_D // 4, MAX_D - 1, size=N_DOCS)
    docs = [rng.integers(5, cfg.backbone.vocab_size, size=int(n))
            for n in lens]
    root = tmp_path_factory.mktemp("pagedidx")
    IndexBuilder(str(root / "f16"), cfg, params, codec="fp16", n_shards=2,
                 batch_size=16, store_layer_kv=True).build(docs)
    IndexBuilder(str(root / "i8"), cfg, params, codec="int8", n_shards=2,
                 batch_size=16, store_layer_kv=True,
                 kv_codec="int8").build(docs)
    return (cfg, params, TermRepIndex.open(str(root / "f16")),
            TermRepIndex.open(str(root / "i8")))


def _requests(rng, n_queries, candidates, n_docs, alpha=1.3):
    """alpha=None draws candidates uniformly (maximal unique-doc churn);
    otherwise a zipf-skewed hot set."""
    reqs = []
    for qi in range(n_queries):
        q, qv = pack_query(rng.integers(5, 500, size=MAX_Q - 2), MAX_Q)
        if alpha is None:
            cands = list(rng.integers(0, n_docs, size=candidates))
        else:
            cands = list((np.minimum(rng.zipf(alpha, size=candidates),
                                     n_docs) - 1).astype(np.int64))
        reqs.append((q, qv, cands))
    return reqs


def _drain(svc, reqs):
    for i, (q, qv, cands) in enumerate(reqs):
        svc.submit(RankRequest(q, qv, cands, request_id=str(i)))
    return {r.request_id: r.scores for r in svc.drain()}


# ---------------------------------------------------------------------------
# Paged == slot == uncached (the cache-layout equivalence)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("idx_name", ["f16", "i8"])
def test_paged_matches_slot_and_uncached(paged_world, idx_name):
    """Same workload through the uncached service, the whole-doc slot
    cache and the small-page cache: all three must score bit-identically
    on cold and warm passes — every row is the same stored bytes through
    the same in-jit decode, whatever the residency layout."""
    cfg, params, idx_f, idx_q = paged_world
    idx = idx_f if idx_name == "f16" else idx_q
    rng = np.random.default_rng(7)
    reqs = _requests(rng, 8, 8, len(idx))
    plain = RankingService(params, cfg, idx, micro_batch=8)
    slot = RankingService(params, cfg, idx, micro_batch=8, doc_cache_mb=4)
    paged = RankingService(params, cfg, idx, micro_batch=8, doc_cache_mb=4,
                           page_tokens=8)
    assert paged.doc_cache.pages_per_doc == 3
    assert slot.doc_cache.pages_per_doc == 1
    ref = _drain(plain, reqs)
    cold_s, cold_p = _drain(slot, reqs), _drain(paged, reqs)
    warm_p = _drain(paged, reqs)
    assert paged.doc_cache.hits > paged.doc_cache.misses
    for k in ref:
        np.testing.assert_array_equal(ref[k], cold_s[k])
        np.testing.assert_array_equal(ref[k], cold_p[k])
        np.testing.assert_array_equal(ref[k], warm_p[k])
    # nothing on the int8 path ever launches the standalone decode jit
    assert plain.stats.n_decode_dispatch == 0
    assert paged.stats.n_decode_dispatch == 0


def test_paged_eviction_multi_page_docs(paged_world):
    """A paged cache far smaller than the corpus churns multi-page docs
    through eviction and still matches the uncached service bit-for-bit
    (freed pages are recycled across docs of different page counts)."""
    cfg, params, idx_f, _ = paged_world
    probe = RankingService(params, cfg, idx_f, micro_batch=4,
                           doc_cache_mb=64, page_tokens=8)
    # the scheduler minimum: 2*micro_batch worst-case docs + reserved pages
    cap = (probe.doc_cache.page_bytes * (2 * 4)
           * probe.doc_cache.pages_per_doc + 2 * probe.doc_cache.page_bytes)
    svc = RankingService(params, cfg, idx_f, micro_batch=4,
                         doc_cache_mb=cap / 2**20, page_tokens=8,
                         page_bucket=True)
    rng = np.random.default_rng(11)
    reqs = _requests(rng, 8, 8, len(idx_f), alpha=None)
    ref = _drain(RankingService(params, cfg, idx_f, micro_batch=4), reqs)
    got = _drain(svc, reqs)
    assert svc.doc_cache.evictions > 0
    for k in ref:
        np.testing.assert_array_equal(ref[k], got[k])


def test_int8_paged_counters(paged_world):
    """The byte counters tell the int8 story: a warm (all-hit) pass stages
    zero H2D bytes, doc-side HBM traffic is the narrow int8 footprint, and
    the residency gauge tracks the cache."""
    cfg, params, _, idx_q = paged_world
    svc = RankingService(params, cfg, idx_q, micro_batch=8, doc_cache_mb=8,
                         page_tokens=8)
    rng = np.random.default_rng(13)
    reqs = _requests(rng, 6, 6, len(idx_q))
    _drain(svc, reqs)
    cold = svc.stats
    assert cold.h2d_bytes > 0 and cold.doc_hbm_bytes > 0
    assert cold.resident_docs == svc.doc_cache.resident_docs > 0
    svc.reset_stats()
    _drain(svc, reqs)
    warm = svc.stats
    assert warm.h2d_bytes == 0                 # all-hit: nothing staged
    assert warm.doc_hbm_bytes > 0              # the kernel still reads HBM
    assert warm.n_decode_dispatch == 0


# ---------------------------------------------------------------------------
# plan(): single-pass eviction under pinning (the O(capacity) regression)
# ---------------------------------------------------------------------------


def _unit_cache(n_docs, *, doc_len=16, page_tokens=8, min_slots=2):
    streams = {"reps": (np.dtype(np.float16), (4,))}
    pages_per_doc = -(-doc_len // page_tokens)
    page_bytes = page_tokens * (2 * 4 + 1)
    cap = (n_docs * pages_per_doc + 2) * page_bytes
    return DeviceDocCache(cap, doc_len=doc_len, streams=streams,
                          page_tokens=page_tokens, min_slots=min_slots)


def test_plan_full_pin_single_pass():
    """A batch that pins the coldest residents: the evict scan sets each
    pinned victim aside exactly once and keeps walking — the old
    restart-the-scan-per-miss behavior was O(capacity * misses)."""
    cache = _unit_cache(8)
    cache.plan([0, 1, 2, 3])
    cache.plan([4, 5, 6, 7])                   # LRU order now 0..7
    resident = cache.resident_docs
    # the miss comes first, so pinned residents 0..3 sit at the cold end
    pt, miss_ids, _ = cache.plan([100, 0, 1, 2, 3])
    assert miss_ids == [100]
    # walked pinned 0,1,2,3 (set aside) then evicted 4: five pops, one pass
    assert cache.last_plan_scans == 5 <= resident
    assert cache.evictions == 1
    assert 4 not in cache._pages_of
    for d in (0, 1, 2, 3, 100):
        assert d in cache._pages_of
    assert pt.shape == (5, cache.pages_per_doc)


def test_plan_many_misses_bounded_scans():
    """min_slots misses against a full cache: total LRU pops stay bounded
    by the resident count, not misses * capacity."""
    cache = _unit_cache(8)
    cache.plan([0, 1, 2, 3])
    cache.plan([4, 5, 6, 7])
    resident = cache.resident_docs
    _, miss_ids, _ = cache.plan([100, 101, 102, 103])
    assert miss_ids == [100, 101, 102, 103]
    assert cache.last_plan_scans <= resident
    assert cache.evictions == 4


def test_plan_all_pinned_raises():
    """If every resident is pinned by the batch being planned (only
    reachable when the constructor capacity check is bypassed), plan must
    fail loudly and leave the LRU intact."""
    cache = _unit_cache(2, min_slots=2)
    with pytest.raises(RuntimeError, match="pinned"):
        cache.plan([0, 1, 2])
    assert cache.resident_docs == 2            # survivors re-queued


# ---------------------------------------------------------------------------
# Page-pool unit behavior
# ---------------------------------------------------------------------------


def test_cache_multi_page_round_trip():
    """Docs of 1..3 pages scatter/gather through the pools exactly; the
    zero page stays immutable so short docs' table tails read as zeros."""
    cache = _unit_cache(4, doc_len=20, page_tokens=8)   # 3 pages/doc
    assert cache.pages_per_doc == 3 and cache.padded_len == 24
    lens = [20, 5, 9]
    pt, miss_ids, miss_pages = cache.plan([10, 11, 12], lengths=lens)
    assert miss_ids == [10, 11, 12]
    rng = np.random.default_rng(0)
    rows = np.zeros((3, cache.padded_len, 4), np.float16)
    valid = np.zeros((3, cache.padded_len), bool)
    for i, n in enumerate(lens):
        rows[i, :n] = rng.standard_normal((n, 4)).astype(np.float16)
        valid[i, :n] = True
    cache.insert(miss_pages, {"reps": rows}, valid)
    parts, got_valid = cache.take(pt)
    np.testing.assert_array_equal(np.asarray(parts["reps"]), rows)
    np.testing.assert_array_equal(got_valid, valid)
    # table tails beyond each doc's page count point at the zero page
    assert list(pt[1][1:]) == [cache.ZERO_PAGE] * 2
    assert not np.asarray(cache.valid_pool[cache.ZERO_PAGE]).any()
    assert not np.asarray(cache.pools["reps"][cache.ZERO_PAGE]).any()


def test_cache_token_major_pools_insert_take():
    """K/V streams pool as ``[P, page, Hkv * Dh]`` rows (fp16 as its
    uint16 bits), per-token scales as ``[P, page]``: each pool page holds
    the staged rows of its doc's page verbatim, and both ``take()`` and the
    in-jit ``pages_to_dense`` give the staged bytes back, across a
    multi-page doc and an eviction that recycles its pages."""
    from repro.kernels.join_attention import pages_to_dense
    streams = {"layer_k": (np.dtype(np.int8), (8,)),
               "layer_k_scales": (np.dtype(np.float32), ()),
               "layer_v": (np.dtype(np.float16), (8,))}
    row_bytes = 8 + 4 + 16 + 1
    cache = DeviceDocCache(8 * 8 * row_bytes, doc_len=20, streams=streams,
                           page_tokens=8)           # 6 usable pages
    assert cache.pages_per_doc == 3 and cache.capacity_pages == 8
    assert cache.pools["layer_k"].shape == (8, 8, 8)
    assert cache.pools["layer_k_scales"].shape == (8, 8)
    assert cache.pools["layer_v"].dtype == np.uint16
    rng = np.random.default_rng(5)

    def staged(lens):
        n = len(lens)
        valid = np.arange(cache.padded_len)[None] < np.asarray(lens)[:, None]
        rows = {
            "layer_k": rng.integers(-127, 128, (n, cache.padded_len, 8),
                                    dtype=np.int8),
            "layer_k_scales": rng.random((n, cache.padded_len),
                                         dtype=np.float32),
            "layer_v": rng.standard_normal(
                (n, cache.padded_len, 8)).astype(np.float16)}
        return ({k: np.where(valid.reshape(valid.shape + (1,) * (v.ndim - 2)),
                             v, 0).astype(v.dtype) for k, v in rows.items()},
                valid)

    def check(pt, miss_pages, rows, valid):
        parts, got_valid = cache.take(pt)
        np.testing.assert_array_equal(got_valid, valid)
        for name, want in rows.items():
            np.testing.assert_array_equal(np.asarray(parts[name]), want)
            got = pages_to_dense(cache.pools[name], jnp.asarray(pt))
            assert got.dtype == want.dtype
            np.testing.assert_array_equal(np.asarray(got), want)
            # each pool page holds its doc page's rows, token-major
            pool = np.asarray(cache.pools[name])
            for i, pages in enumerate(miss_pages):
                for j, pg in enumerate(pages):
                    if pg == cache.SCRATCH_PAGE:
                        continue
                    page_rows = want[i, 8 * j:8 * (j + 1)]
                    if want.dtype == np.float16:
                        page_rows = page_rows.view(np.uint16)
                    np.testing.assert_array_equal(pool[pg], page_rows)

    pt, miss_ids, miss_pages = cache.plan([10, 11], lengths=[20, 5])
    rows, valid = staged([20, 5])
    cache.insert(miss_pages, rows, valid)
    check(pt, miss_pages, rows, valid)
    old_pages = set(pt[0])
    # a three-page miss evicts doc 10 (LRU) and reuses its pages
    pt, miss_ids, miss_pages = cache.plan([11, 12], lengths=[5, 17])
    assert miss_ids == [12] and cache.evictions == 1
    assert set(miss_pages[0]) & old_pages
    new_rows, new_valid = staged([17])
    cache.insert(miss_pages, new_rows, new_valid)
    want = {k: np.concatenate([rows[k][1:2], new_rows[k]]) for k in rows}
    check(pt, [], want, np.concatenate([valid[1:2], new_valid]))
    check(pt[1:], miss_pages, new_rows, new_valid)


@pytest.mark.parametrize("on_tpu,requested,page", [
    (False, 8, 8), (False, 12, 16), (True, 8, 32), (True, 128, 128),
    (True, 130, 160)])
def test_page_tokens_whole_tiles(monkeypatch, on_tpu, requested, page):
    """On a TPU a page is whole native tiles of every pool (32 rows: the
    int8 K/V and validity pools'), so the paged kernel reads pages where
    they lie; off the chip, where memory is not tiled, it rounds to 8."""
    import repro.serving.doc_cache as dc
    monkeypatch.setattr(dc, "interpret_mode", lambda: not on_tpu)
    streams = {"layer_k": (np.dtype(np.int8), (8,)),
               "reps": (np.dtype(np.float16), (4,))}
    cache = DeviceDocCache(2**20, doc_len=480, streams=streams,
                           page_tokens=requested)
    assert cache.page_tokens == page
    assert cache.pools["layer_k"].shape[1:] == (page, 8)


def test_page_bucket_widths():
    """bucket() pads to the next power of two, capped at pages_per_doc,
    and a bucketed plan shrinks the table to the batch's longest doc."""
    assert DeviceDocCache.bucket(1, 8) == 1
    assert DeviceDocCache.bucket(3, 8) == 4
    assert DeviceDocCache.bucket(5, 8) == 8
    assert DeviceDocCache.bucket(5, 6) == 6
    streams = {"reps": (np.dtype(np.float16), (4,))}
    cache = DeviceDocCache(200 * 72, doc_len=64, streams=streams,
                           page_tokens=8, page_bucket=True)
    pt, _, miss_pages = cache.plan([0, 1], lengths=[9, 17])   # 2, 3 pages
    assert pt.shape == (2, 4) and miss_pages.shape == (2, 4)
    pt, _, _ = cache.plan([2], lengths=[62])                  # 8 pages
    assert pt.shape == (1, 8)


# ---------------------------------------------------------------------------
# Scheduler invariant under paging
# ---------------------------------------------------------------------------


def test_one_dispatch_per_micro_batch_paged(paged_world):
    """Paging + bucketing must not break the one-pool-score-call-per-
    micro-batch property: page-table gather, codec decode and join all
    run in jitted device code with no per-doc or per-page dispatches."""
    cfg, params, _, idx_q = paged_world
    svc = RankingService(params, cfg, idx_q, micro_batch=4, doc_cache_mb=8,
                         page_tokens=8, page_bucket=True)
    calls = [0]
    inner = svc._join_pool

    def counting(*a):
        calls[0] += 1
        return inner(*a)

    svc._join_pool = counting
    rng = np.random.default_rng(17)
    reqs = _requests(rng, 5, 6, len(idx_q))
    _drain(svc, reqs)
    n_rows = sum(len(c) for _, _, c in reqs)
    assert calls[0] == -(-n_rows // 4)
    assert svc.stats.n_join_dispatch == calls[0]
    assert svc.stats.n_decode_dispatch == 0


def test_pallas_paged_pool_score_single_jit(paged_world):
    """Under the pallas backend the pool score stays ONE jit: the paged
    kernel's doc-segment index maps walk the page table, so no dense KV
    copy (and no separate assemble dispatch) exists.  The reference
    backends split assemble/score into two jits instead — and both
    layouts must agree on scores (fp32 flash-accumulation tolerance; the
    dense and paged kernels tile the doc segment differently)."""
    cfg, params, _, idx_q = paged_world
    pcfg = _cfg(backend="pallas")
    rng = np.random.default_rng(11)
    reqs = _requests(rng, 4, 8, len(idx_q))
    blk = RankingService(params, cfg, idx_q, micro_batch=8,
                         doc_cache_mb=4, page_tokens=8, page_bucket=True)
    pal = RankingService(params, pcfg, idx_q, micro_batch=8,
                         doc_cache_mb=4, page_tokens=8, page_bucket=True)
    assert hasattr(pal._join_pool, "lower")       # a jax.jit wrapper
    assert not hasattr(blk._join_pool, "lower")   # split assemble+score
    a = _drain(blk, reqs)
    b = _drain(pal, reqs)
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_allclose(np.asarray(b[k]), np.asarray(a[k]),
                                   rtol=2e-4, atol=2e-4)
    assert pal.stats.n_decode_dispatch == 0
