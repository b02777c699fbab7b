"""RankingService: packed cross-query scheduling must be score-equivalent
to the sequential Reranker, under every compute backend, with the straggler
policy lifted into SchedulerPolicy."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.prettr import (PreTTRConfig, init_prettr, make_backbone,
                               precompute_docs)
from repro.index import TermRepIndex
from repro.serving import (DeadlinePriorityPolicy, RankingService,
                           RankRequest, Reranker, SchedulerPolicy)

N_DOCS = 12
MAX_Q, MAX_D = 8, 16


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    bb = make_backbone(n_layers=3, d_model=32, n_heads=2, d_ff=64,
                       vocab_size=128, l=1, max_len=MAX_Q + MAX_D,
                       compute_dtype=jnp.float32, block_kv=8)
    cfg = PreTTRConfig(backbone=bb, l=1, max_query_len=MAX_Q,
                       max_doc_len=MAX_D, compress_dim=16)
    params, _ = init_prettr(jax.random.PRNGKey(0), cfg)
    docs = jax.random.randint(jax.random.PRNGKey(1), (N_DOCS, MAX_D), 5, 128)
    lengths = np.asarray([16, 12, 9, 16, 5, 16, 7, 16, 10, 16, 11, 13])
    valid = jnp.arange(MAX_D)[None] < jnp.asarray(lengths)[:, None]
    reps = precompute_docs(params, cfg, docs, valid)
    path = str(tmp_path_factory.mktemp("svc") / "idx")
    idx = TermRepIndex(path, rep_dim=16, dtype="float16", l=1,
                       compressed=True, max_doc_len=MAX_D)
    idx.add_docs(np.asarray(reps), lengths)
    idx.finalize()
    queries = [np.asarray(jax.random.randint(jax.random.PRNGKey(i + 2),
                                             (MAX_Q,), 5, 128))
               for i in range(3)]
    qv = np.ones((MAX_Q,), bool)
    # duplicate doc ids within q1 and across q0/q1; q2 is empty
    cands = [list(range(8)), [3, 3, 5, 9, 11, 2], []]
    return cfg, params, path, queries, qv, cands


@pytest.mark.parametrize("backend", ["plain", "blocked", "pallas"])
def test_packed_scores_bit_match_sequential(world, backend):
    """Cross-query packing must not change a single score: rows of
    join_and_score are batch-independent, so the packed service and the
    sequential Reranker produce identical bits per query."""
    cfg, params, path, queries, qv, cands = world
    idx = TermRepIndex.open(path)
    rr = Reranker(params, cfg, idx, micro_batch=4, backend=backend)
    seq = [rr.rerank(q, qv, c) for q, c in zip(queries, cands)]

    svc = RankingService(params, cfg, idx, micro_batch=4, backend=backend)
    for i, (q, c) in enumerate(zip(queries, cands)):
        svc.submit(RankRequest(q, qv, c, request_id=f"q{i}"))
    resp = {r.request_id: r for r in svc.drain()}
    assert len(resp) == 3
    for i, (ranked, scores, _) in enumerate(seq):
        r = resp[f"q{i}"]
        assert r.doc_ids == ranked
        np.testing.assert_array_equal(r.scores, scores)
    # the empty request resolves without scoring
    assert resp["q2"].doc_ids == [] and resp["q2"].scores.shape == (0,)
    # packing actually shared batches: 8 + 6 rows in 4-row batches
    assert svc.stats.n_batches == 4
    assert svc.stats.n_rows == 14 and svc.stats.n_pad_rows == 2


def test_deadline_redispatch_under_policy(world):
    """A 0s deadline must trigger the split-and-redispatch straggler path
    (depth-bounded by SchedulerPolicy) without changing any score."""
    cfg, params, path, queries, qv, cands = world
    idx = TermRepIndex.open(path)
    svc = RankingService(params, cfg, idx, micro_batch=8)
    ref = svc.rank(queries[0], qv, list(range(8)))

    strag = RankingService(params, cfg, idx, micro_batch=8,
                           policy=SchedulerPolicy(max_split_depth=2))
    resp = strag.rank(queries[0], qv, list(range(8)), deadline_s=0.0)
    assert resp.stats.n_redispatch == 3          # depth 0 + two halves
    assert strag.stats.n_redispatch == 3
    assert strag.stats.discarded_s > 0
    assert resp.doc_ids == ref.doc_ids
    np.testing.assert_array_equal(resp.scores, ref.scores)


def test_redispatch_halves_keep_batch_shape(world):
    """Redispatched halves are padded back to the micro-batch shape, so
    every attempt runs the one compiled scoring program."""
    cfg, params, path, queries, qv, cands = world
    idx = TermRepIndex.open(path)
    svc = RankingService(params, cfg, idx, micro_batch=8,
                         policy=SchedulerPolicy(max_split_depth=2))
    eng = svc.engine
    score, rows = eng._score_batch, []

    def spy(qr, qv_, payload):
        rows.append(qr.shape[0])
        return score(qr, qv_, payload)

    eng._score_batch = spy
    resp = svc.rank(queries[0], qv, list(range(5)), deadline_s=0.0)
    assert resp.stats.n_redispatch == 3
    assert rows == [8] * 7            # 1 + 2 halves + 4 quarters
    assert sorted(resp.doc_ids) == list(range(5))


def test_policy_split_depth_zero_disables_redispatch(world):
    cfg, params, path, queries, qv, cands = world
    idx = TermRepIndex.open(path)
    svc = RankingService(params, cfg, idx, micro_batch=8,
                         policy=SchedulerPolicy(max_split_depth=0))
    resp = svc.rank(queries[0], qv, list(range(8)), deadline_s=0.0)
    assert resp.stats.n_redispatch == 0
    assert svc.stats.n_redispatch == 0
    assert len(resp.doc_ids) == 8


def test_priority_orders_completion(world):
    """DeadlinePriorityPolicy schedules urgent requests' rows into the
    earliest batches, so they complete first."""
    cfg, params, path, queries, qv, cands = world
    idx = TermRepIndex.open(path)
    svc = RankingService(params, cfg, idx, micro_batch=4,
                         policy=DeadlinePriorityPolicy())
    svc.submit(RankRequest(queries[0], qv, list(range(4)),
                           request_id="low", priority=5))
    svc.submit(RankRequest(queries[1], qv, [4, 5, 6, 7],
                           request_id="high", priority=0))
    order = [r.request_id for r in svc.drain()]
    assert order == ["high", "low"]


def test_per_request_deadline_applies_to_packed_batch(world):
    """One request's tight deadline governs a batch that packs its rows."""
    cfg, params, path, queries, qv, cands = world
    idx = TermRepIndex.open(path)
    svc = RankingService(params, cfg, idx, micro_batch=8)
    svc.submit(RankRequest(queries[0], qv, list(range(4)),
                           request_id="a", deadline_s=0.0))
    svc.submit(RankRequest(queries[1], qv, [4, 5, 6, 7], request_id="b"))
    resp = {r.request_id: r for r in svc.drain()}
    # the shared 8-row batch overshoots a's 0s deadline and is re-split;
    # both requests see the redispatch but scores stay correct
    assert resp["a"].stats.n_redispatch > 0
    assert sorted(resp["a"].doc_ids) == [0, 1, 2, 3]
    assert sorted(resp["b"].doc_ids) == [4, 5, 6, 7]


def test_query_rep_cache_is_shared(world):
    cfg, params, path, queries, qv, cands = world
    idx = TermRepIndex.open(path)
    svc = RankingService(params, cfg, idx, micro_batch=4)
    r1 = svc.rank(queries[0], qv, list(range(6)), request_id="a")
    r2 = svc.rank(queries[0], qv, list(range(6)), request_id="b")
    assert r2.stats.query_encode_s <= r1.stats.query_encode_s + 1e-3
    np.testing.assert_array_equal(r1.scores, r2.scores)


def test_service_validates_index_compat(world):
    import dataclasses

    cfg, params, path, queries, qv, cands = world
    idx = TermRepIndex.open(path)
    with pytest.raises(ValueError, match="truncate"):
        RankingService(params, dataclasses.replace(cfg, max_doc_len=8), idx)
    with pytest.raises(ValueError, match="rep_dim"):
        RankingService(params, dataclasses.replace(cfg, compress_dim=8), idx)
    bb = dataclasses.replace(cfg.backbone, split_layers=2)
    with pytest.raises(ValueError, match="l="):
        RankingService(params, dataclasses.replace(cfg, l=2, backbone=bb),
                       idx)


def test_rank_preserves_other_requests_responses(world):
    """rank() drains everything queued, but other callers' responses must
    stay claimable from the next drain(), not be silently dropped."""
    cfg, params, path, queries, qv, cands = world
    idx = TermRepIndex.open(path)
    svc = RankingService(params, cfg, idx, micro_batch=4)
    svc.submit(RankRequest(queries[0], qv, list(range(4)), request_id="a"))
    ref = svc.rank(queries[0], qv, list(range(4)))
    later = svc.drain()
    assert [r.request_id for r in later] == ["a"]
    np.testing.assert_array_equal(later[0].scores, ref.scores)


def test_reranker_deadline_stays_mutable(world):
    """Back-compat: setting rr.deadline_s after construction must arm the
    straggler policy on the next rerank, as on the original Reranker."""
    cfg, params, path, queries, qv, cands = world
    idx = TermRepIndex.open(path)
    rr = Reranker(params, cfg, idx, micro_batch=8)
    _, _, st = rr.rerank(queries[0], qv, list(range(8)))
    assert st.n_redispatch == 0
    rr.deadline_s = 0.0
    _, _, st = rr.rerank(queries[0], qv, list(range(8)))
    assert st.n_redispatch > 0


def test_validation_covers_unset_index_max_doc_len(world):
    """An index recorded with max_doc_len=0 must still be rejected when its
    stored docs are longer than the serving config allows."""
    import dataclasses

    cfg, params, path, queries, qv, cands = world
    idx = TermRepIndex.open(path)
    idx.max_doc_len = 0                     # as built by the bare constructor
    with pytest.raises(ValueError, match="truncate"):
        RankingService(params, dataclasses.replace(cfg, max_doc_len=8), idx)


def test_bad_doc_id_rejected_at_admission(world):
    """An out-of-range doc id must fail the submit, not abort a later
    drain() and take co-packed requests' responses down with it."""
    cfg, params, path, queries, qv, cands = world
    idx = TermRepIndex.open(path)
    svc = RankingService(params, cfg, idx, micro_batch=4)
    svc.submit(RankRequest(queries[0], qv, [0, 1, 2], request_id="good"))
    with pytest.raises(ValueError, match="out of range"):
        svc.submit(RankRequest(queries[1], qv, [999], request_id="bad"))
    with pytest.raises(ValueError, match="out of range"):
        svc.submit(RankRequest(queries[1], qv, [-1], request_id="neg"))
    resps = svc.drain()
    assert [r.request_id for r in resps] == ["good"]
    assert len(resps[0].doc_ids) == 3


def test_prefetch_depth_zero_is_synchronous_and_equivalent(world):
    cfg, params, path, queries, qv, cands = world
    idx = TermRepIndex.open(path)
    threaded = RankingService(params, cfg, idx, micro_batch=4)
    sync = RankingService(params, cfg, idx, micro_batch=4, prefetch_depth=0)
    a = threaded.rank(queries[0], qv, list(range(8)))
    b = sync.rank(queries[0], qv, list(range(8)))
    assert a.doc_ids == b.doc_ids
    np.testing.assert_array_equal(a.scores, b.scores)


def test_drain_with_nothing_pending(world):
    cfg, params, path, queries, qv, cands = world
    idx = TermRepIndex.open(path)
    svc = RankingService(params, cfg, idx, micro_batch=4)
    assert svc.drain() == []
