"""The traffic generator: a seed fixes every input, seeds change which
documents and tokens a run touches but not how much work it holds."""
import json
from pathlib import Path

import numpy as np
import pytest

import traffic

REPO = Path(__file__).resolve().parents[2]

BIG_SEED = 2**31 + 987_654_321


def _spec(name="cold_moving_l11"):
    return json.loads((REPO / "bench" / "traffic" / f"{name}.json")
                      .read_text())


def _flat(tr):
    return (np.concatenate(tr.docs),
            np.array([r.due_s for r in tr.requests]),
            np.concatenate([r.query for r in tr.requests]),
            np.stack([r.doc_ids for r in tr.requests]))


@pytest.mark.parametrize("name", ["docs_steady_l6", "hot_steady_l11",
                                  "cold_moving_l11"])
def test_same_seed_same_inputs(name):
    a = traffic.generate(_spec(name), 30522, BIG_SEED, 10.0)
    b = traffic.generate(_spec(name), 30522, BIG_SEED, 10.0)
    for x, y in zip(_flat(a), _flat(b)):
        np.testing.assert_array_equal(x, y)


def test_seeds_differ_in_inputs_not_in_sizes():
    spec = _spec()
    a = traffic.generate(spec, 30522, 1, 10.0)
    b = traffic.generate(spec, 30522, BIG_SEED, 10.0)
    la, lb = [len(d) for d in a.docs], [len(d) for d in b.docs]
    assert la != lb and sorted(la) == sorted(lb)
    assert len(a.requests) == len(b.requests)
    assert sorted(len(r.query) for r in a.requests) == \
        sorted(len(r.query) for r in b.requests)
    assert not np.array_equal(_flat(a)[1], _flat(b)[1])
    assert not np.array_equal(_flat(a)[3], _flat(b)[3])
    ga, gb = (np.diff(np.append(_flat(t)[1], 10.0)) for t in (a, b))
    np.testing.assert_allclose(np.sort(ga), np.sort(gb), rtol=1e-9)


def test_arrivals_fill_the_window_at_the_rate():
    t = traffic.arrival_times(7.0, 30.0, BIG_SEED)
    assert len(t) == 210 and t[0] == 0.0 and t[-1] < 30.0
    assert np.all(np.diff(t) > 0)


def test_doc_lengths_follow_the_traffic_file():
    corpus = _spec()["corpus"]
    lens = traffic.doc_lengths(corpus, 3)
    assert lens.min() >= corpus["doc_len_min"]
    assert lens.max() == corpus["doc_len_max"]
    assert abs(np.median(lens) - corpus["doc_len_median"]) <= 2
    # about a third reach the cap (log-normal, sigma 0.6, median 380)
    assert 0.3 < np.mean(lens == corpus["doc_len_max"]) < 0.4


def test_candidates_distinct_and_head_moves():
    spec = _spec()
    tr = traffic.generate(spec, 30522, 5, 20.0)
    for r in tr.requests:
        assert len(set(r.doc_ids.tolist())) == spec["candidates"][
            "per_request"]
    pop = traffic.Popularity(spec["corpus"]["n_docs"], spec["candidates"], 5)
    head0, head1 = pop.docs_by_rank(0.0)[:10], pop.docs_by_rank(5.0)[:10]
    assert not set(head0) & set(head1)
    np.testing.assert_array_equal(pop.docs_by_rank(4.9), pop.docs_by_rank(0))


def test_packing_matches_the_bert_convention():
    tok, valid = traffic.pack_query(np.array([7, 8, 9]), 8)
    assert tok.tolist() == [1, 7, 8, 9, 2, 0, 0, 0]
    assert valid.sum() == 5
    tok, valid = traffic.pack_doc(np.arange(10, 20), 8)
    assert tok.tolist() == [10, 11, 12, 13, 14, 15, 16, 2] and valid.all()
