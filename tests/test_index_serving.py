"""Index store + reranking server."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.prettr import (PreTTRConfig, make_backbone, init_prettr,
                               precompute_docs, encode_query, join_and_score)
from repro.index import TermRepIndex
from repro.serving import Reranker


def _setup(tmp_path, compress_dim=16):
    bb = make_backbone(n_layers=3, d_model=32, n_heads=2, d_ff=64,
                       vocab_size=128, l=1, max_len=24,
                       compute_dtype=jnp.float32, block_kv=8)
    cfg = PreTTRConfig(backbone=bb, l=1, max_query_len=8, max_doc_len=16,
                       compress_dim=compress_dim)
    params, _ = init_prettr(jax.random.PRNGKey(0), cfg)
    docs = jax.random.randint(jax.random.PRNGKey(1), (10, 16), 5, 128)
    lengths = np.asarray([16, 12, 9, 16, 5, 16, 7, 16, 10, 16])
    valid = jnp.arange(16)[None] < jnp.asarray(lengths)[:, None]
    reps = precompute_docs(params, cfg, docs, valid)
    e = compress_dim or bb.d_model
    idx = TermRepIndex(str(tmp_path / "idx"), rep_dim=e, dtype="float16",
                       l=1, compressed=bool(compress_dim), max_doc_len=16)
    idx.add_docs(np.asarray(reps), lengths)
    idx.finalize()
    return cfg, params, docs, valid, lengths


def test_index_roundtrip(tmp_path):
    cfg, params, docs, valid, lengths = _setup(tmp_path)
    idx = TermRepIndex.open(str(tmp_path / "idx"))
    assert len(idx) == 10
    reps, dvalid = idx.load_docs([2, 5], pad_to=16)
    assert reps.shape == (2, 16, 16)
    assert dvalid[0].sum() == lengths[2]
    # storage accounting
    assert idx.storage_bytes() == sum(lengths) * 16 * 2


def test_index_scores_match_direct_path(tmp_path):
    """Serving through the on-disk index == scoring straight from memory."""
    cfg, params, docs, valid, lengths = _setup(tmp_path)
    idx = TermRepIndex.open(str(tmp_path / "idx"))
    q = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 5, 128)
    qv = jnp.ones((1, 8), bool)
    q_reps = encode_query(params, cfg, q, qv)

    reps_mem = precompute_docs(params, cfg, docs, valid)
    # zero out padding (the index stores only valid tokens)
    reps_mem = jnp.where(valid[..., None], reps_mem.astype(jnp.float32), 0)
    s_mem = join_and_score(params, cfg,
                           jnp.broadcast_to(q_reps, (10, 8, 32)),
                           jnp.broadcast_to(qv, (10, 8)),
                           reps_mem.astype(jnp.float16), valid)

    reps_idx, dvalid = idx.load_docs(list(range(10)), pad_to=16)
    s_idx = join_and_score(params, cfg,
                           jnp.broadcast_to(q_reps, (10, 8, 32)),
                           jnp.broadcast_to(qv, (10, 8)),
                           jnp.asarray(reps_idx), jnp.asarray(dvalid))
    np.testing.assert_allclose(np.asarray(s_mem), np.asarray(s_idx),
                               rtol=1e-3, atol=1e-3)


def test_reranker_end_to_end(tmp_path):
    cfg, params, docs, valid, lengths = _setup(tmp_path)
    idx = TermRepIndex.open(str(tmp_path / "idx"))
    rr = Reranker(params, cfg, idx, micro_batch=4)
    q = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (8,), 5, 128))
    qv = np.ones((8,), bool)
    ranked, scores, stats = rr.rerank(q, qv, list(range(10)))
    assert len(ranked) == 10 and sorted(ranked) == list(range(10))
    assert np.all(np.diff(scores) <= 1e-6)           # descending
    assert stats.query_encode_s >= 0 and stats.queue_wait_s > 0
    assert rr.stats.combine_s > 0
    # query-rep cache hit on repeat
    _, _, stats2 = rr.rerank(q, qv, list(range(10)))
    assert stats2.query_encode_s <= stats.query_encode_s + 1e-3


def test_reranker_straggler_redispatch(tmp_path):
    cfg, params, docs, valid, lengths = _setup(tmp_path)
    idx = TermRepIndex.open(str(tmp_path / "idx"))
    rr = Reranker(params, cfg, idx, micro_batch=8, deadline_s=0.0)
    q = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (8,), 5, 128))
    ranked, scores, stats = rr.rerank(q, np.ones((8,), bool), list(range(8)))
    assert stats.n_redispatch > 0, "0s deadline must trigger re-dispatch"
    assert len(ranked) == 8


def test_straggler_stats_not_double_counted(tmp_path):
    """Regression: a discarded overshooting batch used to leave its
    combine_s (and re-loaded load_s) in the stats, inflating the Table-5
    split.  With a 0s deadline an 8-doc batch runs 7 join attempts
    (1 + 2 + 4) but only the four depth-2 leaves are returned — only their
    time may be counted."""
    import time

    cfg, params, docs, valid, lengths = _setup(tmp_path)
    idx = TermRepIndex.open(str(tmp_path / "idx"))
    rr = Reranker(params, cfg, idx, micro_batch=8, deadline_s=0.0)
    q = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (8,), 5, 128))
    qv = np.ones((8,), bool)
    rr.rerank(q, qv, list(range(8)))          # warm every jit shape (8,4,2)

    inner = rr._join
    n_calls = [0]
    sleep = 0.1    # dominates the (jit-cached) join cost even on loaded CI

    def slow_join(*a):                         # deterministic per-call cost
        n_calls[0] += 1
        time.sleep(sleep)
        return inner(*a)

    rr._join = slow_join
    rr.reset_stats()
    _, _, stats = rr.rerank(q, qv, list(range(8)))
    assert stats.n_redispatch == 3            # depth 0 + two depth-1 halves
    assert n_calls[0] == 7
    # 4 returned leaves counted; the 3 discarded attempts (0.15s) are not
    assert 4 * sleep <= rr.stats.combine_s < 6 * sleep
    # the request waited only for its first staged attempt, not the joins
    assert 0 < stats.queue_wait_s < sleep


def test_rerank_empty_doc_ids(tmp_path):
    """Regression: rerank([]) used to hit np.concatenate on an empty list."""
    cfg, params, docs, valid, lengths = _setup(tmp_path)
    idx = TermRepIndex.open(str(tmp_path / "idx"))
    rr = Reranker(params, cfg, idx, micro_batch=4)
    q = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (8,), 5, 128))
    ranked, scores, stats = rr.rerank(q, np.ones((8,), bool), [])
    assert ranked == []
    assert scores.shape == (0,)
    assert stats.n_docs == 0


def test_zero_doc_index_roundtrip(tmp_path):
    """Regression: finalize()/open() used to crash on an index with no docs
    (unopened write handle; np.memmap rejects empty files)."""
    idx = TermRepIndex(str(tmp_path / "empty"), rep_dim=16, dtype="float16",
                       l=1, compressed=True, max_doc_len=16)
    idx.finalize()
    idx = TermRepIndex.open(str(tmp_path / "empty"))
    assert len(idx) == 0
    assert idx.storage_bytes() == 0
    reps, dvalid = idx.load_docs([], pad_to=16)
    assert reps.shape == (0, 16, 16) and dvalid.shape == (0, 16)


def test_gather_matches_per_doc_loop(tmp_path):
    """The vectorized gather() must reproduce the original per-doc copy
    loop exactly, including pad_to clamping of over-long docs."""
    cfg, params, docs, valid, lengths = _setup(tmp_path)
    idx = TermRepIndex.open(str(tmp_path / "idx"))
    for ids, pad_to in [(list(range(10)), 16), ([2, 5, 2, 9], 16),
                        ([1, 4], 8), ([], 16), ([3], None)]:
        reps, dvalid = idx.gather(ids, pad_to=pad_to)
        pad = pad_to or idx.max_doc_len
        ref = np.zeros((len(ids), pad, idx.rep_dim), idx.dtype)
        ref_valid = np.zeros((len(ids), pad), bool)
        for i, d in enumerate(ids):
            off, n = idx._offsets[d]
            n = min(n, pad)
            ref[i, :n] = idx._mmap[off: off + n]
            ref_valid[i, :n] = True
        np.testing.assert_array_equal(reps, ref)
        np.testing.assert_array_equal(dvalid, ref_valid)


def test_gather_rejects_bad_ids_and_unopened_index(tmp_path):
    cfg, params, docs, valid, lengths = _setup(tmp_path)
    idx = TermRepIndex.open(str(tmp_path / "idx"))
    with pytest.raises(IndexError):
        idx.gather([0, 99])
    building = TermRepIndex(str(tmp_path / "b"), rep_dim=16)
    with pytest.raises(RuntimeError, match="not open for reading"):
        building.gather([0])


def test_add_docs_after_open_or_finalize_raises(tmp_path):
    """Regression: add_docs() after open()/finalize() used to reopen
    reps.bin with 'wb', silently truncating every stored representation."""
    cfg, params, docs, valid, lengths = _setup(tmp_path)
    reps, _ = TermRepIndex.open(str(tmp_path / "idx")).gather([0])

    opened = TermRepIndex.open(str(tmp_path / "idx"))
    with pytest.raises(RuntimeError, match="read-only"):
        opened.add_docs(reps, [lengths[0]])

    built = TermRepIndex(str(tmp_path / "fin"), rep_dim=16, dtype="float16",
                         l=1, compressed=True, max_doc_len=16)
    built.add_docs(reps, [lengths[0]])
    built.finalize()
    with pytest.raises(RuntimeError, match="read-only"):
        built.add_docs(reps, [lengths[0]])
    with pytest.raises(RuntimeError, match="already-finalized"):
        built.finalize()
    # the data on disk survived every rejected write
    again = TermRepIndex.open(str(tmp_path / "fin"))
    np.testing.assert_array_equal(again.gather([0])[0], reps)


def test_reranker_validates_index_compat(tmp_path):
    """An index built with a larger max_doc_len (or mismatched rep shape)
    must be rejected at construction instead of silently truncating."""
    import dataclasses

    cfg, params, docs, valid, lengths = _setup(tmp_path)
    idx = TermRepIndex.open(str(tmp_path / "idx"))
    with pytest.raises(ValueError, match="truncate"):
        Reranker(params, dataclasses.replace(cfg, max_doc_len=8), idx)
    with pytest.raises(ValueError, match="rep_dim"):
        Reranker(params, dataclasses.replace(cfg, compress_dim=32), idx)
    with pytest.raises(ValueError, match="compress"):
        Reranker(params, dataclasses.replace(cfg, compress_dim=0), idx)
    Reranker(params, cfg, idx)               # compatible: constructs fine


def test_empty_index_and_empty_rerank_together(tmp_path):
    cfg, params, docs, valid, lengths = _setup(tmp_path)
    empty = TermRepIndex(str(tmp_path / "empty2"), rep_dim=16,
                         dtype="float16", l=1, compressed=True,
                         max_doc_len=16)
    empty.finalize()
    empty = TermRepIndex.open(str(tmp_path / "empty2"))
    rr = Reranker(params, cfg, empty, micro_batch=4)
    q = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (8,), 5, 128))
    ranked, scores, _ = rr.rerank(q, np.ones((8,), bool), [])
    assert ranked == [] and scores.shape == (0,)
