"""Scale-out serving: router + shard workers must be *bit-exact* against
the single-process RankingService.

What must hold:

* ``TermRepIndex.serving_assignment`` is a deterministic partition of the
  corpus aligned with the physical shard files (shard affinity: each
  serving shard reads exactly one physical shard's memmaps when serving
  shards outnumber physical ones);
* a ``ShardIndexView`` refuses to gather docs it does not own, with a
  message naming both shards — and ``validate_doc_routing`` surfaces the
  same misroute at admission;
* the ``RankingRouter`` returns bitwise-identical scores to a
  single-process ``RankingService`` over the whole index, for 2 and 4
  workers, across backends and codecs, with dup doc ids split across
  shards, empty candidate lists, deadline redispatch, and warm vs cold
  doc caches;
* ``ServiceStats`` merge is field-complete (counters sum, gauges max) and
  the router's aggregate view is consistent with its per-worker stats;
* under 8 forced host devices (subprocess, ``test_distributed.py``-style)
  the pinned workers hold their params/caches on distinct devices and
  still match the single-process scores.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.prettr import PreTTRConfig, init_prettr, make_backbone
from repro.data.synthetic_ir import pack_query
from repro.index import IndexBuilder, TermRepIndex
from repro.index.store import ShardIndexView
from repro.serving import (RankingRouter, RankingService, RankRequest,
                           SchedulerPolicy, ServiceStats,
                           validate_doc_routing)

ROOT = os.path.join(os.path.dirname(__file__), "..")
MAX_Q, MAX_D = 8, 16
N_DOCS = 32


def _cfg(backend="blocked"):
    from repro.models.backend import impls_for
    attn_impl, compress_impl = impls_for(backend)
    bb = make_backbone(n_layers=3, d_model=32, n_heads=2, d_ff=64,
                       vocab_size=256, l=1, max_len=MAX_Q + MAX_D,
                       compute_dtype=jnp.float32, block_kv=8,
                       attn_impl=attn_impl, compress_impl=compress_impl)
    return PreTTRConfig(backbone=bb, l=1, max_query_len=MAX_Q,
                        max_doc_len=MAX_D, compress_dim=16,
                        store_dtype=jnp.float16)


@pytest.fixture(scope="module")
def sharded_world(tmp_path_factory):
    """Variable-length corpus over TWO physical shards, indexed as fp16
    and as int8 (+ int8 layer-K/V) — the codecs whose serving paths
    diverge the most."""
    cfg = _cfg()
    params, _ = init_prettr(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(11)
    lens = rng.integers(4, MAX_D, size=N_DOCS)
    docs = [rng.integers(5, cfg.backbone.vocab_size, size=int(n))
            for n in lens]
    root = tmp_path_factory.mktemp("shardidx")
    IndexBuilder(str(root / "f16"), cfg, params, codec="fp16", n_shards=2,
                 batch_size=16, store_layer_kv=True).build(docs)
    IndexBuilder(str(root / "i8"), cfg, params, codec="int8", n_shards=2,
                 batch_size=16, store_layer_kv=True,
                 kv_codec="int8").build(docs)
    rng = np.random.default_rng(5)
    reqs = []
    for qi in range(6):
        q, qv = pack_query(rng.integers(5, 200, size=MAX_Q - 2), MAX_Q)
        cands = list(rng.integers(0, N_DOCS, size=10))
        reqs.append((q, qv, cands))
    # dup doc ids within one request (and across shards once sharded)
    reqs.append((reqs[0][0], reqs[0][1], [3, 3, 17, 17, 8, 30, 3]))
    # empty candidate list resolves without scoring
    reqs.append((reqs[1][0], reqs[1][1], []))
    return cfg, params, str(root / "f16"), str(root / "i8"), reqs


def _drain(svc, reqs):
    for i, (q, qv, cands) in enumerate(reqs):
        svc.submit(RankRequest(q, qv, cands, request_id=f"q{i}"))
    return {r.request_id: r for r in svc.drain()}


def _assert_same_responses(got, ref, reqs):
    assert set(got) == set(ref) == {f"q{i}" for i in range(len(reqs))}
    for rid in ref:
        assert got[rid].doc_ids == ref[rid].doc_ids
        np.testing.assert_array_equal(got[rid].scores, ref[rid].scores)


# ---------------------------------------------------------------------------
# Assignment + shard views
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_serving", [1, 2, 3, 4, 8])
def test_serving_assignment_is_aligned_partition(sharded_world, n_serving):
    cfg, params, f16, i8, reqs = sharded_world
    idx = TermRepIndex.open(f16)
    a = idx.serving_assignment(n_serving)
    assert a.shape == (len(idx),)
    assert a.min() >= 0 and a.max() < n_serving
    # deterministic: router and workers compute it independently
    np.testing.assert_array_equal(a, idx.serving_assignment(n_serving))
    # every doc owned by exactly one shard; all shards populated
    assert len(np.unique(a)) == min(n_serving, len(idx))
    phys = idx._doc_table[:, 0]
    if n_serving <= idx.n_shards:
        # whole physical shards map to serving shards
        np.testing.assert_array_equal(a, phys % n_serving)
    else:
        # shard affinity: each serving shard reads exactly ONE physical
        # shard's files
        for s in np.unique(a):
            assert len(np.unique(phys[a == s])) == 1


def test_shard_view_ownership_and_delegation(sharded_world):
    cfg, params, f16, i8, reqs = sharded_world
    idx = TermRepIndex.open(f16)
    a = idx.serving_assignment(2)
    view = idx.shard_view(a, 0)
    assert isinstance(view, ShardIndexView)
    # global id space + delegated metadata
    assert len(view) == len(idx)
    assert view.rep_dim == idx.rep_dim and view.l == idx.l
    assert view.streams_spec() == idx.streams_spec()
    assert view.n_owned + idx.shard_view(a, 1).n_owned == len(idx)
    owned = view.owned_ids
    np.testing.assert_array_equal(view.owns(owned), True)
    # owned gathers read the same bytes as the base index
    parts_v, valid_v = view.gather_raw(owned[:5], pad_to=MAX_D)
    parts_b, valid_b = idx.gather_raw(owned[:5], pad_to=MAX_D)
    np.testing.assert_array_equal(valid_v, valid_b)
    for name in parts_b:
        np.testing.assert_array_equal(parts_v[name], parts_b[name])
    assert view.describe_misroute(owned[:5]) is None


def test_shard_view_rejects_misrouted_and_out_of_range(sharded_world):
    cfg, params, f16, i8, reqs = sharded_world
    idx = TermRepIndex.open(f16)
    a = idx.serving_assignment(2)
    view = idx.shard_view(a, 0)
    stranger = int(idx.shard_view(a, 1).owned_ids[0])
    with pytest.raises(IndexError, match="resident elsewhere"):
        view.gather_raw([stranger], pad_to=MAX_D)
    with pytest.raises(IndexError, match=f"shard {a[stranger]}"):
        view.gather([stranger])
    with pytest.raises(IndexError, match="out of range"):
        view.gather_raw([len(idx)], pad_to=MAX_D)
    # validate_doc_routing surfaces the same misroute at admission
    with pytest.raises(ValueError, match="resident elsewhere"):
        validate_doc_routing(view, [stranger])
    with pytest.raises(ValueError, match="out of range"):
        validate_doc_routing(view, [-1])
    validate_doc_routing(view, view.owned_ids[:3])     # owned ids pass
    validate_doc_routing(idx, [0, len(idx) - 1])       # base index: range only


def test_router_rejects_bad_ids_at_admission(sharded_world):
    cfg, params, f16, i8, reqs = sharded_world
    idx = TermRepIndex.open(f16)
    router = RankingRouter(params, cfg, idx, n_shards=2, micro_batch=4)
    q, qv, _ = reqs[0]
    with pytest.raises(ValueError, match="out of range"):
        router.submit(RankRequest(q, qv, [0, N_DOCS]))
    # nothing half-enqueued: a good request still completes
    resp = router.rank(q, qv, [0, 1, 2])
    assert sorted(resp.doc_ids) == [0, 1, 2]


# ---------------------------------------------------------------------------
# Bit-exactness vs the single-process service
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["plain", "blocked", "pallas"])
@pytest.mark.parametrize("n_shards", [2, 4])
def test_router_bit_matches_single_process(sharded_world, backend, n_shards):
    """The core scale-out invariant: same candidates, same bits — the
    shard fan-out (including dup ids split across shards and an empty
    request) must not change a single score."""
    cfg, params, f16, i8, reqs = sharded_world
    idx = TermRepIndex.open(f16)
    ref = _drain(RankingService(params, cfg, idx, micro_batch=4,
                                backend=backend), reqs)
    router = RankingRouter(params, cfg, idx, n_shards=n_shards,
                           micro_batch=4, backend=backend)
    got = _drain(router, reqs)
    _assert_same_responses(got, ref, reqs)
    # shard affinity: every row was scored by the worker owning its doc
    per_worker_rows = sum(w.stats.n_rows for w in router.workers)
    assert per_worker_rows == sum(len(c) for _, _, c in reqs)


def test_router_int8_kv_bit_matches_single_process(sharded_world):
    """The int8 + int8-layer-KV index (in-kernel dequant, raw-stream
    staging) through 2 shards == single process, and no standalone decode
    dispatch appears on any worker."""
    cfg, params, f16, i8, reqs = sharded_world
    idx = TermRepIndex.open(i8)
    ref = _drain(RankingService(params, cfg, idx, micro_batch=4), reqs)
    router = RankingRouter(params, cfg, idx, n_shards=2, micro_batch=4)
    got = _drain(router, reqs)
    _assert_same_responses(got, ref, reqs)
    assert router.stats.n_decode_dispatch == 0


def test_router_doc_cache_warm_and_cold_bit_match(sharded_world):
    """Per-worker paged doc caches: cold pass (all misses) and warm pass
    (hits) must both match the uncached single-process scores."""
    cfg, params, f16, i8, reqs = sharded_world
    idx = TermRepIndex.open(i8)
    ref = _drain(RankingService(params, cfg, idx, micro_batch=4), reqs)
    router = RankingRouter(params, cfg, idx, n_shards=2, micro_batch=4,
                           doc_cache_mb=4, page_tokens=8)
    cold = _drain(router, reqs)
    _assert_same_responses(cold, ref, reqs)
    assert router.stats.n_doc_cache_miss > 0
    router.reset_stats()
    warm = _drain(router, reqs)
    _assert_same_responses(warm, ref, reqs)
    assert router.stats.n_doc_cache_hit > 0
    # warm pass re-ships nothing for resident docs
    assert (router.stats.h2d_bytes <
            sum(w.doc_cache.resident_bytes for w in router.workers))


def test_router_deadline_redispatch_bit_match(sharded_world):
    """A 0s deadline triggers split-and-redispatch inside the workers;
    scores must be unchanged and the redispatch visible in the merged
    stats."""
    cfg, params, f16, i8, reqs = sharded_world
    idx = TermRepIndex.open(f16)
    q, qv, _ = reqs[0]
    cands = list(range(16))
    ref = RankingService(params, cfg, idx, micro_batch=8).rank(q, qv, cands)
    router = RankingRouter(params, cfg, idx, n_shards=2, micro_batch=8,
                           policy=SchedulerPolicy(max_split_depth=2))
    resp = router.rank(q, qv, cands, deadline_s=0.0)
    assert resp.stats.n_redispatch > 0
    assert router.stats.n_redispatch > 0
    assert resp.doc_ids == ref.doc_ids
    np.testing.assert_array_equal(resp.scores, ref.scores)


def test_router_single_shard_degenerates_to_service(sharded_world):
    """n_shards=1 is the identity configuration: same scores, same row
    counters as the single-process service."""
    cfg, params, f16, i8, reqs = sharded_world
    idx = TermRepIndex.open(f16)
    svc = RankingService(params, cfg, idx, micro_batch=4)
    ref = _drain(svc, reqs)
    router = RankingRouter(params, cfg, idx, n_shards=1, micro_batch=4)
    got = _drain(router, reqs)
    _assert_same_responses(got, ref, reqs)
    assert router.stats.n_rows == svc.stats.n_rows
    assert router.stats.n_batches == svc.stats.n_batches
    assert router.stats.n_pad_rows == svc.stats.n_pad_rows


# ---------------------------------------------------------------------------
# Stats merge + aggregation
# ---------------------------------------------------------------------------


def test_service_stats_merge_is_field_complete():
    """merge() must cover every field — a counter added later (the way
    h2d_bytes arrived in PR 7) has to aggregate, not silently vanish.
    Gauges (resident_docs) and overlapped clocks (wall_s) take max."""
    fields = [f.name for f in dataclasses.fields(ServiceStats)]
    a = ServiceStats(**{n: i + 1 for i, n in enumerate(fields)})
    b = ServiceStats(**{n: 10 * (i + 1) for i, n in enumerate(fields)})
    m = a.merge(b)
    for i, n in enumerate(fields):
        if n in ("resident_docs", "wall_s"):
            assert getattr(m, n) == 10 * (i + 1), n
        else:
            assert getattr(m, n) == 11 * (i + 1), n
    # operator forms
    m2 = a + b
    assert m2 == m
    assert sum([a, b]) == m                      # __radd__ for sum()
    with pytest.raises(TypeError):               # non-stats stays rejected
        a + 1


def test_router_stats_aggregate_consistently(sharded_world):
    cfg, params, f16, i8, reqs = sharded_world
    idx = TermRepIndex.open(f16)
    router = RankingRouter(params, cfg, idx, n_shards=2, micro_batch=4)
    _drain(router, reqs)
    agg = router.stats
    per = router.worker_stats
    assert len(per) == 2
    # requests counted once (router-side), never per worker
    assert agg.n_requests == len(reqs)
    assert all(w.n_requests == 0 for w in per)
    # additive counters are the exact sum across workers
    for name in ("n_rows", "n_batches", "n_join_dispatch", "h2d_bytes"):
        assert getattr(agg, name) == sum(getattr(w, name) for w in per), name
    # gauges are the max, with the per-worker list still available
    assert agg.resident_docs == max(w.resident_docs for w in per)
    # the router's wall brackets the concurrent worker drains
    assert agg.wall_s >= max(w.wall_s for w in per)


# ---------------------------------------------------------------------------
# Device-pinned workers under 8 forced host devices (subprocess)
# ---------------------------------------------------------------------------


def _run(snippet: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(snippet)],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


_PINNED_SNIPPET = """
import tempfile
import numpy as np
import jax, jax.numpy as jnp
from repro.core.prettr import PreTTRConfig, init_prettr, make_backbone
from repro.data.synthetic_ir import pack_query
from repro.index import IndexBuilder, TermRepIndex
from repro.serving import RankingRouter, RankingService, RankRequest

N_SHARDS = {n_shards}
assert len(jax.devices()) == 8
bb = make_backbone(n_layers=2, d_model=32, n_heads=2, d_ff=64,
                   vocab_size=256, l=1, max_len=24,
                   compute_dtype=jnp.float32, block_kv=8)
cfg = PreTTRConfig(backbone=bb, l=1, max_query_len=8, max_doc_len=16,
                   compress_dim=16, store_dtype=jnp.float16)
params, _ = init_prettr(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(3)
docs = [rng.integers(5, 256, size=int(n))
        for n in rng.integers(4, 16, size=24)]
with tempfile.TemporaryDirectory() as td:
    IndexBuilder(td + "/idx", cfg, params, codec="int8", n_shards=2,
                 batch_size=8, store_layer_kv=True,
                 kv_codec="int8").build(docs)
    idx = TermRepIndex.open(td + "/idx")
    reqs = []
    for qi in range(4):
        q, qv = pack_query(rng.integers(5, 200, size=6), 8)
        reqs.append((q, qv, list(rng.integers(0, 24, size=7))))
    svc = RankingService(params, cfg, idx, micro_batch=4)
    for i, (q, qv, c) in enumerate(reqs):
        svc.submit(RankRequest(q, qv, c, request_id=str(i)))
    ref = {{r.request_id: r.scores for r in svc.drain()}}

    devices = jax.devices()[:N_SHARDS]
    router = RankingRouter(params, cfg, idx, n_shards=N_SHARDS,
                           devices=devices, micro_batch=4, doc_cache_mb=2,
                           page_tokens=8)
    # params + doc-cache pools actually live on each worker's own device
    for w, d in zip(router.workers, devices):
        leaf = jax.tree_util.tree_leaves(w.engine.params)[0]
        assert leaf.devices() == {{d}}, (leaf.devices(), d)
        pool = next(iter(w.doc_cache.pools.values()))
        assert pool.devices() == {{d}}, (pool.devices(), d)
    for i, (q, qv, c) in enumerate(reqs):
        router.submit(RankRequest(q, qv, c, request_id=str(i)))
    got = {{r.request_id: r.scores for r in router.drain()}}
    assert set(got) == set(ref)
    for rid in ref:
        np.testing.assert_array_equal(got[rid], ref[rid])
    # warm pass: device-resident hits, still bit-exact
    for i, (q, qv, c) in enumerate(reqs):
        router.submit(RankRequest(q, qv, c, request_id=str(i)))
    warm = {{r.request_id: r.scores for r in router.drain()}}
    for rid in ref:
        np.testing.assert_array_equal(warm[rid], ref[rid])
    assert router.stats.n_doc_cache_hit > 0
print("OK pinned", N_SHARDS)
"""


def test_pinned_workers_2_shards_bit_match():
    out = _run(_PINNED_SNIPPET.format(n_shards=2))
    assert "OK pinned 2" in out


def test_pinned_workers_4_shards_bit_match():
    out = _run(_PINNED_SNIPPET.format(n_shards=4))
    assert "OK pinned 4" in out


def test_worker_devices_pins_or_refuses(monkeypatch):
    """One device per worker; too few devices share the default one off
    the chip, and are an error on a TPU."""
    from repro.serving.sharded import worker_devices

    class Dev:
        def __init__(self, platform):
            self.platform, self.device_kind = platform, f"{platform} dev"

    cpus = [Dev("cpu"), Dev("cpu")]
    monkeypatch.setattr(jax, "devices", lambda: cpus)
    assert worker_devices(2) == cpus
    assert worker_devices(1) == cpus[:1]
    assert worker_devices(4) is None
    monkeypatch.setattr(jax, "devices", lambda: [Dev("tpu")])
    with pytest.raises(ValueError, match="need 4 devices"):
        worker_devices(4)
