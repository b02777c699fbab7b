"""Named spans of the serving path (``repro.serving.spans``).

What must hold:

* each span adds its host seconds to its ``ServiceStats`` field, the
  parts stay inside the clocks they split (``gather + plan <= load``,
  ``dispatch <= combine``), and ``merge`` sums the new fields;
* under a running profiler the spans land on the host plane, the
  engine's inside ``prettr.drain``;
* the spans add no blocking device call: one ``block_until_ready`` (end of
  staging) and one ``device_get`` (the scores) per micro-batch, as before;
* every request's queue wait (enqueue -> first staged micro-batch) is on
  its ``RerankStats`` and summed into ``ServiceStats``;
* the query encode runs under a stable program name.
"""
import glob
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.prettr import PreTTRConfig, init_prettr, make_backbone
from repro.data.synthetic_ir import pack_query
from repro.index import IndexBuilder, TermRepIndex
from repro.serving import RankingService, RankRequest, ServiceStats, spans
from repro.serving.sharded import RankingRouter

MAX_Q, MAX_D = 8, 24
N_DOCS = 40
MB = 4
SLEEP = 0.02


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    bb = make_backbone(n_layers=3, d_model=32, n_heads=2, d_ff=64,
                       vocab_size=256, l=1, max_len=MAX_Q + MAX_D,
                       compute_dtype=jnp.float32, block_kv=8,
                       attn_impl="blocked")
    cfg = PreTTRConfig(backbone=bb, l=1, max_query_len=MAX_Q,
                       max_doc_len=MAX_D, compress_dim=16,
                       store_dtype=jnp.float16)
    params, _ = init_prettr(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    docs = [rng.integers(5, 250, size=int(n))
            for n in rng.integers(4, MAX_D - 1, size=N_DOCS)]
    path = str(tmp_path_factory.mktemp("spans") / "idx")
    IndexBuilder(path, cfg, params, codec="fp16", n_shards=2,
                 batch_size=16).build(docs)
    reqs = []
    for i in range(3):
        q, qv = pack_query(rng.integers(5, 250, size=MAX_Q - 2), MAX_Q)
        reqs.append(RankRequest(q, qv, [int(d) for d in
                                        rng.integers(0, N_DOCS, size=6)],
                                request_id=f"r{i}"))
    return cfg, params, TermRepIndex.open(path), reqs


def _service(world, cached, **kw):
    cfg, params, idx, _ = world
    return RankingService(params, cfg, idx, micro_batch=MB,
                          doc_cache_mb=0.5 if cached else 0.0,
                          page_tokens=8 if cached else None, **kw)


def _serve(svc, reqs):
    for r in reqs:
        svc.submit(r)
    return svc.drain()


def _warm(svc, world):
    _serve(svc, world[3])
    svc.reset_stats()
    svc._qcache.clear()                # every query encodes again


# what to slow down for each span's field (an attribute the span's body
# calls), and the counter of how many times the body runs
SLOWED = {
    "query_encode_s": (lambda svc: svc, "_encode", "n_requests"),
    "gather_s": (lambda svc: svc.engine.index, "gather_raw", "n_batches"),
    "plan_s": (lambda svc: svc.engine.doc_cache, "plan", "n_batches"),
    "dispatch_s": (lambda svc: svc.engine, "_score_batch", "n_batches"),
    # the scoring thread waits for at least the first staged batch, and
    # the second request's first rows are staged after the first batch
    "stage_wait_s": (lambda svc: svc.engine.index, "gather_raw", None),
    "queue_wait_s": (lambda svc: svc.engine.index, "gather_raw", None),
}


@pytest.mark.parametrize("field", sorted(SLOWED))
def test_each_span_adds_to_its_field(world, monkeypatch, field):
    owner, attr, per = SLOWED[field]
    svc = _service(world, cached=field == "plan_s")
    _warm(svc, world)
    assert getattr(svc.stats, field) == 0
    obj = owner(svc)
    fn = getattr(obj, attr)

    def slow(*a, **k):
        time.sleep(SLEEP)
        return fn(*a, **k)

    monkeypatch.setattr(obj, attr, slow)
    _serve(svc, world[3])
    st = svc.stats
    n = getattr(st, per) if per else 1
    assert n > 0
    assert getattr(st, field) >= n * SLEEP
    # the parts stay inside the clocks they split
    assert st.gather_s + st.plan_s <= st.load_s
    assert st.dispatch_s <= st.combine_s
    assert st.n_queue_waits == st.n_requests == len(world[3])


def test_merge_sums_the_span_fields():
    names = ("plan_s", "gather_s", "dispatch_s", "stage_wait_s",
             "queue_wait_s", "n_queue_waits")
    a = ServiceStats(**{n: i + 1 for i, n in enumerate(names)})
    b = ServiceStats(**{n: 10 * (i + 1) for i, n in enumerate(names)})
    m = a.merge(b)
    for i, n in enumerate(names):
        assert getattr(m, n) == 11 * (i + 1), n


def test_span_names_are_listed_once_under_one_prefix():
    assert len(set(spans.NAMES)) == len(spans.NAMES) == 10
    assert all(n.startswith(spans.PREFIX) for n in spans.NAMES)
    with spans.span(spans.DRAIN) as sp:
        time.sleep(SLEEP)
    assert sp.s >= SLEEP and sp.name == spans.DRAIN


def _host_spans(trace_dir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events
                        if ev.name.startswith(spans.PREFIX)]
    return out


@pytest.mark.parametrize("cached", [False, True])
def test_spans_land_on_the_host_plane_inside_drain(world, tmp_path, cached):
    svc = _service(world, cached)
    if not cached:                     # a cold cache: every row a miss
        _warm(svc, world)
    with jax.profiler.trace(str(tmp_path)):
        _serve(svc, world[3])
    evs = _host_spans(str(tmp_path))
    names = {n for n, _, _ in evs}
    inner = {spans.GATHER, spans.SHIP, spans.STAGE_WAIT, spans.DISPATCH,
             spans.DEVICE_GET} | ({spans.PLAN, spans.INSERT} if cached
                                  else set())
    assert inner | {spans.ENCODE, spans.DRAIN, spans.FINALIZE} <= names
    drains = [(s, e) for n, s, e in evs if n == spans.DRAIN]
    assert len(drains) == 1
    lo, hi = drains[0]
    n_batches = svc.stats.n_batches
    for n, s, e in evs:
        if n in inner:
            assert lo <= s <= e <= hi, n
    assert sum(n == spans.DISPATCH for n, _, _ in evs) == n_batches
    assert all(e <= lo for n, _, e in evs if n == spans.ENCODE)


@pytest.mark.parametrize("cached", [False, True])
def test_blocking_device_calls_per_micro_batch(world, monkeypatch, cached):
    """One ``block_until_ready`` (end of staging) and one ``device_get``
    (the scores) per micro-batch: the spans add none."""
    svc = _service(world, cached)
    _warm(svc, world)
    calls = []
    for name in ("block_until_ready", "device_get"):
        fn = getattr(jax, name)

        def counted(*a, _fn=fn, _name=name, **k):
            calls.append(_name)
            return _fn(*a, **k)

        monkeypatch.setattr(jax, name, counted)
    # only the drain's calls count, not the encodes at submit
    for r in world[3]:
        svc.submit(r)
    calls.clear()
    svc.drain()
    n = svc.stats.n_batches
    assert n == -(-sum(len(r.doc_ids) for r in world[3]) // MB)
    assert calls.count("block_until_ready") == n
    assert calls.count("device_get") == n
    assert len(calls) == 2 * n


def test_queue_wait_per_request(world):
    svc = _service(world, False)
    _warm(svc, world)
    resp = {r.request_id: r for r in _serve(svc, world[3])}
    waits = [resp[f"r{i}"].stats.queue_wait_s for i in range(3)]
    assert all(w > 0 for w in waits)
    # FIFO: a later request's first rows are staged after an earlier's
    assert waits[0] < waits[2]
    st = svc.stats
    assert st.n_queue_waits == 3
    assert st.queue_wait_s == pytest.approx(sum(waits))


def test_router_queue_wait_is_the_slowest_shard_tasks(world):
    cfg, params, idx, reqs = world
    router = RankingRouter(params, cfg, idx, n_shards=2, micro_batch=MB)
    _serve(router, reqs)
    router.reset_stats()
    resp = _serve(router, reqs)
    st = router.stats
    assert st.n_queue_waits >= len(reqs)      # one per shard task
    for r in resp:
        assert 0 < r.stats.queue_wait_s <= st.queue_wait_s


def test_encode_program_has_a_stable_name(world):
    svc = _service(world, False)
    q, qv = world[3][0].q_tokens, world[3][0].q_valid
    text = svc._encode.lower(svc.params, q[None], qv[None]).as_text()
    assert "jit__encode_query" in text
