"""The per-layer metrics that read the program's named spans: each reader's
arithmetic on a ``ServiceStats`` dict, its silence where the program has
no such field or ran no batch, and a traced run of each small cell that
reports them."""
import time

import pytest

import cells
import harness

SEED = 2**31 + 5151

STATS = {"n_requests": 4, "n_batches": 5, "n_queue_waits": 4,
         "query_encode_s": 0.02, "queue_wait_s": 0.3, "stage_wait_s": 0.01,
         "gather_s": 0.04, "plan_s": 0.015, "dispatch_s": 0.0125,
         "load_s": 0.1, "combine_s": 0.08}

# metric -> its value on STATS, in ms
WANT = {"query_encode_ms": 1e3 * 0.02 / 4,
        "queue_wait_ms": 1e3 * 0.3 / 4,
        "stage_wait_ms_per_batch": 1e3 * 0.01 / 5,
        "gather_ms_per_batch": 1e3 * 0.04 / 5,
        "cache_plan_ms_per_batch": 1e3 * 0.015 / 5,
        "score_dispatch_ms_per_batch": 1e3 * 0.0125 / 5}

# the field each reader takes apart from its denominator
FIELD = {"query_encode_ms": "query_encode_s", "queue_wait_ms": "queue_wait_s",
         "stage_wait_ms_per_batch": "stage_wait_s",
         "gather_ms_per_batch": "gather_s",
         "cache_plan_ms_per_batch": "plan_s",
         "score_dispatch_ms_per_batch": "dispatch_s"}


def _ctx(stats):
    return harness.Context({}, {}, stats, [], None, None)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_arithmetic(checkout, name):
    read = cells.reader(checkout, name)
    assert read(_ctx(dict(STATS))) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_silent_without_batches_or_field(checkout, name):
    read = cells.reader(checkout, name)
    zero = dict(STATS, n_requests=0, n_batches=0, n_queue_waits=0)
    assert read(_ctx(zero)) is None
    # a program without the span (the parent of this change) has no field
    absent = {k: v for k, v in STATS.items() if k != FIELD[name]}
    assert read(_ctx(absent)) is None


# the small cells stand for the accepted ones: ``dense`` for
# l6_docs.steady (gather listed), ``paged`` for l11_hot.steady (plan
# listed); the small paged cell's cache holds part of its corpus, so it
# gathers misses too
LISTED = {"dense": {"query_encode_ms", "queue_wait_ms",
                    "stage_wait_ms_per_batch", "gather_ms_per_batch",
                    "score_dispatch_ms_per_batch"},
          "paged": {"query_encode_ms", "queue_wait_ms",
                    "stage_wait_ms_per_batch", "cache_plan_ms_per_batch",
                    "score_dispatch_ms_per_batch", "gather_ms_per_batch"}}


@pytest.mark.parametrize("workload", sorted(LISTED))
def test_traced_run_reports_the_span_metrics(checkout, program, workload):
    out = harness.run(checkout, workload, SEED, 2.0, True, program,
                      time.time())
    assert out["correct"] is True
    m = {k: v["value"] for k, v in out["metrics"].items()}
    for name in LISTED[workload]:
        assert m.get(name) is not None and m[name] > 0, name
        assert out["metrics"][name]["unit"] == "ms"
    # the parts stay inside the clocks they split
    assert (m["gather_ms_per_batch"] + m["cache_plan_ms_per_batch"]
            <= m["stage_ms_per_batch"])
    assert m["score_dispatch_ms_per_batch"] <= m["score_ms_per_batch"]
