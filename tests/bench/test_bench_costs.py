"""The yardstick: required operations and bytes at BERT-base widths against
counts written out matmul by matmul, and the peaks table."""
import json
from pathlib import Path

import pytest

import costs

REPO = Path(__file__).resolve().parents[2]
D, F = 768, 3072


def _cfg(name):
    return json.loads((REPO / "bench" / "configs" / f"{name}.json")
                      .read_text())


def _mm(m, k, n):
    return 2 * m * k * n


def test_score_step_flops_l6_dense():
    lq, ld = 20, 380
    t = lq + ld
    layer = (_mm(t, D, D)                       # Q
             + _mm(t, D, D) + _mm(t, D, D)      # K, V (nothing stored)
             + _mm(t, D, t) + _mm(t, t, D)      # scores, values (12 heads)
             + _mm(t, D, D)                     # O
             + _mm(t, D, F) + _mm(t, F, D))     # MLP
    cls = (_mm(1, D, D) + _mm(t, D, D) + _mm(t, D, D) + _mm(1, D, t)
           + _mm(1, t, D) + _mm(1, D, D) + _mm(1, D, F) + _mm(1, F, D)
           + _mm(1, D, 1))
    decompress = _mm(ld, 256, D)
    want = decompress + 5 * layer + cls
    assert costs.score_step_flops(_cfg("bert_base_l6_fp16"), lq, ld) == want
    assert want == 31_875_319_296


def test_score_step_flops_l11_stored_kv():
    lq, ld = 20, 380
    t = lq + ld
    # no join layer is left, the reps are never read, and the document
    # K/V of the CLS layer come from the index: only the query's are made
    want = (_mm(1, D, D) + _mm(lq, D, D) + _mm(lq, D, D) + _mm(1, D, t)
            + _mm(1, t, D) + _mm(1, D, D) + _mm(1, D, F) + _mm(1, F, D)
            + _mm(1, D, 1))
    assert costs.score_step_flops(_cfg("bert_base_l11_int8kv"), lq, ld) \
        == want


def test_join_attention_work_l6():
    lq, ld = 20, 380
    t = lq + ld
    w = costs.join_attention_work(_cfg("bert_base_l6_fp16"), lq, ld)
    assert w["full"][0] == 5 * (_mm(t, D, t) + _mm(t, t, D))
    # bf16 q, query K/V, document K/V (computed in the join), out
    per_layer = 2 * (t * D + 2 * lq * D + 2 * ld * D + t * D)
    assert w["full"][1] == 5 * per_layer
    assert w["cls"] == (_mm(1, D, t) + _mm(1, t, D),
                        2 * (D + 2 * lq * D + 2 * ld * D + D))


def test_join_attention_work_l11_int8():
    lq, ld = 20, 380
    w = costs.join_attention_work(_cfg("bert_base_l11_int8kv"), lq, ld)
    assert w["full"] == (0, 0)
    # int8 document K/V with one fp32 scale per token each
    assert w["cls"][1] == 2 * D + 2 * 2 * lq * D + 2 * ld * D + 2 * ld * 4 \
        + 2 * D


def test_least_time_names_the_bound():
    t, bound = costs.least_time([(197e12, 1.0)], 197e12, 819e9)
    assert t == pytest.approx(1.0) and bound == "compute"
    t, bound = costs.least_time([(1.0, 819e9), (197e12 / 2, 1.0)],
                                197e12, 819e9)
    assert t == pytest.approx(1.5) and bound == "memory"


def test_peaks_table():
    p = costs.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.peaks("cpu")
