"""Fault-tolerant serving: the deterministic fault-injection framework,
engine-level plan isolation, the router's retry -> failover -> degrade
ladder, drain timeouts, admission shedding, stats accounting, and the
chaos soak (faults injected under a live client thread: every
non-degraded response bit-exact vs the fault-free run, every degraded
response flagged, the router never deadlocks, the stats account for
every request)."""
import dataclasses
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.prettr import PreTTRConfig, init_prettr, make_backbone
from repro.data.synthetic_ir import pack_query
from repro.index import IndexBuilder, TermRepIndex
from repro.serving import (FaultInjected, FaultPlan, FaultSpec,
                           RankingRouter, RankingService, RankRequest,
                           SchedulerPolicy, ServiceOverloadError,
                           ServiceStats, WorkerHealth, faults)

ROOT = os.path.join(os.path.dirname(__file__), "..")
MAX_Q, MAX_D = 8, 16
N_DOCS = 32


def _cfg():
    bb = make_backbone(n_layers=3, d_model=32, n_heads=2, d_ff=64,
                       vocab_size=256, l=1, max_len=MAX_Q + MAX_D,
                       compute_dtype=jnp.float32, block_kv=8)
    return PreTTRConfig(backbone=bb, l=1, max_query_len=MAX_Q,
                        max_doc_len=MAX_D, compress_dim=16,
                        store_dtype=jnp.float16)


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    """Small fp16 corpus over two physical shards (checksummed manifest —
    the builder default) plus a fixed request set: 6 zipf-ish queries, a
    dup-id request, an empty one."""
    cfg = _cfg()
    params, _ = init_prettr(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(11)
    docs = [rng.integers(5, cfg.backbone.vocab_size, size=int(n))
            for n in rng.integers(4, MAX_D, size=N_DOCS)]
    root = tmp_path_factory.mktemp("faultidx")
    IndexBuilder(str(root / "f16"), cfg, params, codec="fp16", n_shards=2,
                 batch_size=16).build(docs)
    rng = np.random.default_rng(5)
    reqs = []
    for qi in range(6):
        q, qv = pack_query(rng.integers(5, 200, size=MAX_Q - 2), MAX_Q)
        cands = list(rng.choice(N_DOCS, size=10, replace=False))
        reqs.append((q, qv, cands))
    reqs.append((reqs[0][0], reqs[0][1], [3, 3, 17, 17, 8, 30, 3]))
    reqs.append((reqs[1][0], reqs[1][1], []))
    return cfg, params, str(root / "f16"), reqs


def _drain(svc, reqs):
    for i, (q, qv, cands) in enumerate(reqs):
        svc.submit(RankRequest(q, qv, cands, request_id=f"q{i}"))
    return {r.request_id: r for r in svc.drain()}


def _reference(world):
    cfg, params, f16, reqs = world
    idx = TermRepIndex.open(f16)
    svc = RankingService(params, cfg, idx, micro_batch=4)
    return _drain(svc, reqs)


def _assert_bit_exact(got, ref, reqs):
    assert set(got) == set(ref) == {f"q{i}" for i in range(len(reqs))}
    for rid in ref:
        assert not got[rid].degraded, (rid, got[rid].failed_doc_ids)
        assert got[rid].doc_ids == ref[rid].doc_ids
        np.testing.assert_array_equal(got[rid].scores, ref[rid].scores)


def _assert_degraded_contract(resp, ref):
    """Degraded response: flagged, failed ids scored -inf and sorted
    last, every other doc id bit-exact vs the fault-free reference."""
    assert resp.degraded and resp.failed_doc_ids
    ref_by_id = dict(zip(ref.doc_ids, ref.scores))
    failed = set(resp.failed_doc_ids)
    for d, s in zip(resp.doc_ids, resp.scores):
        if d in failed:
            assert s == -np.inf
        else:
            assert s == ref_by_id[d], (d, s, ref_by_id[d])
    n = len(resp.doc_ids)
    assert all(resp.doc_ids[i] in failed for i in
               range(n - len(failed), n))


# ---------------------------------------------------------------------------
# The framework itself
# ---------------------------------------------------------------------------


def test_spec_validation():
    with pytest.raises(ValueError, match="unknown site"):
        FaultSpec("engine.warp", "error")
    with pytest.raises(ValueError, match="unknown kind"):
        FaultSpec("engine.stage", "meteor")


def test_no_plan_installed_is_noop():
    assert not faults.active()
    faults.hit("engine.stage")          # must not raise or record anything


def test_after_count_budget_and_tags():
    spec = FaultSpec("engine.stage", "error", tag=7, after=2, count=2)
    with FaultPlan([spec]) as plan:
        faults.hit("engine.stage", tag=3)        # wrong tag: not a hit
        faults.hit("engine.stage", tag=7)        # hit 1 (skipped: after)
        faults.hit("engine.stage", tag=7)        # hit 2 (skipped: after)
        for _ in range(2):                       # hits 3, 4: fire
            with pytest.raises(FaultInjected):
                faults.hit("engine.stage", tag=7)
        faults.hit("engine.stage", tag=7)        # budget exhausted
    assert plan.n_fired() == 2
    assert [e.hit_no for e in plan.fired] == [3, 4]
    assert not faults.active()


def test_probability_is_seeded_deterministic():
    def firing_pattern(seed):
        spec = FaultSpec("engine.score", "latency", p=0.5, count=None,
                         latency_s=0.0)
        with FaultPlan([spec], seed=seed) as plan:
            pat = []
            for _ in range(64):
                before = plan.n_fired()
                faults.hit("engine.score")
                pat.append(plan.n_fired() > before)
        return pat

    a, b = firing_pattern(3), firing_pattern(3)
    assert a == b and 0 < sum(a) < 64
    assert firing_pattern(4) != a


def test_plans_nest_and_count_independently():
    outer = FaultSpec("worker.drain", "latency", latency_s=0.0, count=None)
    inner = FaultSpec("worker.drain", "latency", latency_s=0.0, count=1)
    with FaultPlan([outer]) as po:
        faults.hit("worker.drain")
        with FaultPlan([inner]) as pi:
            faults.hit("worker.drain")           # both plans see this
        faults.hit("worker.drain")
    assert po.n_fired() == 3 and pi.n_fired() == 1


def test_custom_error_class_and_instance():
    with FaultPlan([FaultSpec("engine.stage", "error", error=OSError)]):
        with pytest.raises(OSError):
            faults.hit("engine.stage")
    boom = KeyError("boom")
    with FaultPlan([FaultSpec("engine.stage", "error", error=boom)]):
        with pytest.raises(KeyError):
            faults.hit("engine.stage")


def test_latency_kind_sleeps():
    with FaultPlan([FaultSpec("engine.stage", "latency", latency_s=0.08)]):
        t0 = time.perf_counter()
        faults.hit("engine.stage")
        assert time.perf_counter() - t0 >= 0.06


def test_corrupt_transient_heals_on_next_hit(world):
    cfg, params, f16, reqs = world
    idx = TermRepIndex.open(f16)
    spec = FaultSpec("index.gather", "corrupt", count=1, restore=True)
    with FaultPlan([spec]) as plan:
        faults.hit("index.gather", index=idx, doc_ids=[0])
        assert plan.n_fired("corrupt") == 1
        assert "flipped" in plan.fired[0].detail
        with pytest.raises(Exception, match="CRC-32C"):
            idx.verify_integrity()
        # the next matching hit (a retry's re-read) restores first
        faults.hit("index.gather", index=idx, doc_ids=[0])
        assert idx.verify_integrity() > 0
    assert idx.verify_integrity() > 0


def test_corrupt_persistent_restored_at_plan_exit(world):
    cfg, params, f16, reqs = world
    idx = TermRepIndex.open(f16)
    spec = FaultSpec("index.gather", "corrupt", count=1, restore=False)
    with FaultPlan([spec]):
        faults.hit("index.gather", index=idx, doc_ids=[0])
        faults.hit("index.gather", index=idx, doc_ids=[0])   # stays rotten
        with pytest.raises(Exception, match="CRC-32C"):
            idx.verify_integrity()
    # plan exit always restores: the shared test index is never left dirty
    assert idx.verify_integrity() > 0


# ---------------------------------------------------------------------------
# Engine-level fault isolation + service degraded responses
# ---------------------------------------------------------------------------


def test_engine_isolates_failed_plan_rows(world):
    """A staging fault fails ONLY the planned micro-batch's rows; the
    engine keeps draining and every other row stays bit-exact."""
    cfg, params, f16, reqs = world
    ref = _reference(world)
    idx = TermRepIndex.open(f16)
    svc = RankingService(params, cfg, idx, micro_batch=4)
    with FaultPlan([FaultSpec("engine.stage", "error", count=1)]) as plan:
        got = _drain(svc, reqs)
    assert plan.n_fired() == 1
    degraded = [r for r in got.values() if r.degraded]
    assert len(degraded) >= 1
    n_failed = sum(len(r.failed_doc_ids) for r in degraded)
    assert 0 < n_failed <= 4                     # at most one plan's rows
    for rid, resp in got.items():
        if resp.degraded:
            _assert_degraded_contract(resp, ref[rid])
        else:
            assert resp.doc_ids == ref[rid].doc_ids
            np.testing.assert_array_equal(resp.scores, ref[rid].scores)
    assert svc.stats.n_degraded == len(degraded)


def test_service_fault_free_after_plan_removal(world):
    cfg, params, f16, reqs = world
    ref = _reference(world)
    idx = TermRepIndex.open(f16)
    svc = RankingService(params, cfg, idx, micro_batch=4)
    with FaultPlan([FaultSpec("engine.score", "error", count=2)]):
        _drain(svc, reqs)
    _assert_bit_exact(_drain(svc, reqs), ref, reqs)   # engine fully healed


def test_service_sheds_beyond_max_queue(world):
    cfg, params, f16, reqs = world
    idx = TermRepIndex.open(f16)
    svc = RankingService(params, cfg, idx, micro_batch=4, max_queue=2)
    q, qv, cands = reqs[0]
    svc.submit(RankRequest(q, qv, cands, request_id="a"))
    svc.submit(RankRequest(q, qv, cands, request_id="b"))
    with pytest.raises(ServiceOverloadError, match="max_queue"):
        svc.submit(RankRequest(q, qv, cands, request_id="c"))
    assert svc.stats.n_shed == 1
    assert {r.request_id for r in svc.drain()} == {"a", "b"}
    svc.submit(RankRequest(q, qv, cands, request_id="c"))   # queue drained
    assert len(svc.drain()) == 1


# ---------------------------------------------------------------------------
# Stats accounting
# ---------------------------------------------------------------------------


def test_stats_merge_is_field_complete_sum_vs_max():
    a, b = ServiceStats(), ServiceStats()
    for i, f in enumerate(dataclasses.fields(ServiceStats)):
        setattr(a, f.name, 2 * i + 1)
        setattr(b, f.name, i + 1)
    m = a.merge(b)
    for i, f in enumerate(dataclasses.fields(ServiceStats)):
        if f.name in ("resident_docs", "wall_s"):    # gauge / overlapped
            assert getattr(m, f.name) == 2 * i + 1, f.name
        else:
            assert getattr(m, f.name) == 3 * i + 2, f.name
    # the fault-ladder counters are plain sums in both directions
    fa = ServiceStats(n_retries=2, n_failovers=1, n_degraded=3, n_shed=4)
    fb = ServiceStats(n_retries=5, n_failovers=6, n_degraded=7, n_shed=8)
    for name, want in [("n_retries", 7), ("n_failovers", 7),
                       ("n_degraded", 10), ("n_shed", 12)]:
        assert getattr(fa.merge(fb), name) == want
        assert getattr(fb.merge(fa), name) == want


def test_policy_drain_timeout():
    pol = SchedulerPolicy()
    assert pol.drain_timeout([]) == pol.drain_timeout_floor
    assert pol.drain_timeout([None, None], 10) == pol.drain_timeout_floor
    big = pol.drain_timeout([200.0, None], n_rows=4)
    assert big == 8.0 * 200.0 * 4


# ---------------------------------------------------------------------------
# The router's recovery ladder
# ---------------------------------------------------------------------------


def test_router_fault_free_matches_service(world):
    cfg, params, f16, reqs = world
    ref = _reference(world)
    router = RankingRouter(params, cfg, TermRepIndex.open(f16), n_shards=2,
                           micro_batch=4)
    _assert_bit_exact(_drain(router, reqs), ref, reqs)
    s = router.stats
    assert (s.n_retries, s.n_failovers, s.n_degraded, s.n_shed) == (0,) * 4
    assert all(h.state == WorkerHealth.HEALTHY for h in router.health)


def test_router_retry_recovers_transient_fault(world):
    cfg, params, f16, reqs = world
    ref = _reference(world)
    router = RankingRouter(params, cfg, TermRepIndex.open(f16), n_shards=2,
                           micro_batch=4, retry_backoff_s=0.0)
    with FaultPlan([FaultSpec("worker.drain", "error", tag=0, count=1)]):
        got = _drain(router, reqs)
    _assert_bit_exact(got, ref, reqs)            # recovered, bit-exact
    s = router.stats
    assert s.n_retries > 0 and s.n_failovers == 0 and s.n_degraded == 0
    assert all(h.state == WorkerHealth.HEALTHY for h in router.health)
    assert router.health[0].n_failures == 1


def test_router_failover_serves_persistent_shard_fault(world):
    cfg, params, f16, reqs = world
    ref = _reference(world)
    router = RankingRouter(params, cfg, TermRepIndex.open(f16), n_shards=2,
                           micro_batch=4, retry_backoff_s=0.0)
    with FaultPlan([FaultSpec("worker.drain", "error", tag=0,
                              count=None)]):
        got = _drain(router, reqs)
        # shard 0 is unhealthy but every response is still bit-exact:
        # its candidates were re-gathered from the full index
        _assert_bit_exact(got, ref, reqs)
        s = router.stats
        assert s.n_retries > 0 and s.n_failovers > 0 and s.n_degraded == 0
        assert router.health[0].state != WorkerHealth.HEALTHY
        assert router.health[1].state == WorkerHealth.HEALTHY
        # keep submitting under the same persistent fault: the worker
        # goes DEAD and traffic routes around it at submit time
        for _ in range(3):
            got = _drain(router, reqs)
            _assert_bit_exact(got, ref, reqs)
    assert router.health[0].state == WorkerHealth.DEAD
    # dead worker: submits route straight to the fallback, still exact
    _assert_bit_exact(_drain(router, reqs), ref, reqs)


def test_router_drain_timeout_kills_stuck_worker(world):
    """A wedged shard (30s stall vs a 5s budget) can no longer hang
    drain(): the worker is declared DEAD (a stuck drain thread still owns
    its engine) and its candidates are served through the fallback."""
    cfg, params, f16, reqs = world
    ref = _reference(world)
    router = RankingRouter(params, cfg, TermRepIndex.open(f16), n_shards=2,
                           micro_batch=4, drain_timeout_s=5.0,
                           max_retries=0)
    with FaultPlan([FaultSpec("worker.drain", "latency", tag=1,
                              latency_s=30.0)]):
        t0 = time.perf_counter()
        got = _drain(router, reqs)
        elapsed = time.perf_counter() - t0
    assert elapsed < 25.0                        # did NOT wait the stall out
    _assert_bit_exact(got, ref, reqs)
    assert router.health[1].state == WorkerHealth.DEAD
    assert router.health[1].n_timeouts == 1
    assert isinstance(router.health[1].last_error, TimeoutError)
    assert router.stats.n_failovers > 0
    # the dead worker stays dead; later traffic still serves bit-exact
    _assert_bit_exact(_drain(router, reqs), ref, reqs)


def test_router_degrades_when_fallback_also_fails(world):
    cfg, params, f16, reqs = world
    ref = _reference(world)
    router = RankingRouter(params, cfg, TermRepIndex.open(f16), n_shards=2,
                           micro_batch=4, retry_backoff_s=0.0)
    with FaultPlan([
            FaultSpec("worker.drain", "error", tag=0, count=None),
            FaultSpec("engine.stage", "error", tag="fallback",
                      count=None)]):
        got = _drain(router, reqs)
    degraded = [r for r in got.values() if r.degraded]
    assert degraded                              # end of the ladder
    for rid, resp in got.items():
        if resp.degraded:
            _assert_degraded_contract(resp, ref[rid])
        else:
            assert resp.doc_ids == ref[rid].doc_ids
            np.testing.assert_array_equal(resp.scores, ref[rid].scores)
    s = router.stats
    assert s.n_degraded == len(degraded) and s.n_failovers > 0
    # every submitted request got exactly one response despite the faults
    assert len(got) == len(reqs)
    # the ladder heals once the plan is gone (fallback engine rebuilt)
    _assert_bit_exact(_drain(router, reqs), ref, reqs)


def test_router_sheds_beyond_max_queue(world):
    cfg, params, f16, reqs = world
    router = RankingRouter(params, cfg, TermRepIndex.open(f16), n_shards=2,
                           micro_batch=4, max_queue=2)
    q, qv, cands = reqs[0]
    router.submit(RankRequest(q, qv, cands, request_id="a"))
    router.submit(RankRequest(q, qv, cands, request_id="b"))
    with pytest.raises(ServiceOverloadError, match="max_queue"):
        router.submit(RankRequest(q, qv, cands, request_id="c"))
    assert router.stats.n_shed == 1
    assert {r.request_id for r in router.drain()} == {"a", "b"}
    router.submit(RankRequest(q, qv, cands, request_id="c"))
    assert len(router.drain()) == 1


def test_router_detects_and_recovers_index_corruption(world):
    """verify_reads=True turns silent bit-rot into a shard fault the
    ladder recovers from: the corrupt gather raises IndexIntegrityError,
    the retry re-reads healed bytes, scores stay bit-exact, and the
    index is verifiably clean afterwards."""
    cfg, params, f16, reqs = world
    ref = _reference(world)
    idx = TermRepIndex.open(f16, verify_reads=True)
    router = RankingRouter(params, cfg, idx, n_shards=2, micro_batch=4,
                           retry_backoff_s=0.0)
    with FaultPlan([FaultSpec("index.gather", "corrupt", tag=0, count=1,
                              restore=True)]) as plan:
        got = _drain(router, reqs)
    assert plan.n_fired("corrupt") == 1
    _assert_bit_exact(got, ref, reqs)
    assert router.stats.n_retries > 0
    assert idx.verify_integrity() > 0            # nothing left flipped


# ---------------------------------------------------------------------------
# Chaos soak (the tier-1 proof)
# ---------------------------------------------------------------------------


def test_chaos_soak(world):
    """A client thread streams zipf-weighted queries while a seeded fault
    schedule (stalls, worker errors, staging errors, transient bit-rot)
    is live.  Invariants: the router never deadlocks, every accepted
    request gets exactly one response, every non-degraded response is
    bit-exact vs the fault-free run, every degraded response honors the
    contract, and the stats account for every request."""
    cfg, params, f16, reqs = world
    rng = np.random.default_rng(17)
    # zipf over a small query pool; candidates zipf-weighted over docs
    pool = [pack_query(rng.integers(5, 200, size=MAX_Q - 2), MAX_Q)
            for _ in range(6)]
    w = 1.0 / np.arange(1, N_DOCS + 1) ** 1.3
    stream = []
    for i in range(30):
        q, qv = pool[min(int(rng.zipf(1.8)) - 1, len(pool) - 1)]
        cands = rng.choice(N_DOCS, size=8, replace=False, p=w / w.sum())
        stream.append((q, qv, [int(c) for c in cands]))

    # fault-free reference
    idx_ref = TermRepIndex.open(f16)
    svc = RankingService(params, cfg, idx_ref, micro_batch=4)
    for i, (q, qv, c) in enumerate(stream):
        svc.submit(RankRequest(q, qv, c, request_id=f"s{i}"))
    ref = {r.request_id: r for r in svc.drain()}

    idx = TermRepIndex.open(f16, verify_reads=True)
    router = RankingRouter(params, cfg, idx, n_shards=2, micro_batch=4,
                           retry_backoff_s=0.0, drain_timeout_s=30.0,
                           max_queue=6)
    # warm the jits fault-free so compile time stays off the soak clock
    q0, qv0, c0 = stream[0]
    router.rank(q0, qv0, c0, request_id="warm")

    plan = FaultPlan([
        FaultSpec("worker.drain", "latency", latency_s=0.05, p=0.3,
                  count=None),
        FaultSpec("worker.drain", "error", tag=0, p=0.25, count=4),
        FaultSpec("engine.stage", "error", tag=1, p=0.2, count=3),
        FaultSpec("engine.stage", "error", tag="fallback", count=1),
        FaultSpec("index.gather", "corrupt", tag=1, after=2, count=2,
                  restore=True),
    ], seed=7)

    lock = threading.Lock()          # router is externally synchronized
    accepted: list[str] = []
    n_shed = 0

    def client():
        nonlocal n_shed
        for i, (q, qv, c) in enumerate(stream):
            rid = f"s{i}"
            while True:
                with lock:
                    try:
                        router.submit(RankRequest(q, qv, c, request_id=rid))
                        accepted.append(rid)
                        break
                    except ServiceOverloadError:
                        n_shed += 1
                time.sleep(0.002)    # back off until the main loop drains

    responses = {}
    t0 = time.perf_counter()
    with plan:
        th = threading.Thread(target=client, daemon=True)
        th.start()
        while th.is_alive() or responses.keys() < set(accepted):
            with lock:
                for r in router.drain():
                    assert r.request_id not in responses   # exactly once
                    responses[r.request_id] = r
            assert time.perf_counter() - t0 < 300.0, "soak deadlocked"
            time.sleep(0.002)
        th.join(timeout=60.0)
        assert not th.is_alive()

    # accounting: every request accounted for — accepted ones answered,
    # shed ones counted, nothing lost, nothing answered twice
    assert len(accepted) == len(stream)
    assert set(responses) == set(accepted)
    s = router.stats
    assert s.n_requests == len(accepted) + 1                # + the warm-up
    assert s.n_shed == n_shed
    degraded = [r for r in responses.values() if r.degraded]
    assert s.n_degraded == len(degraded)
    assert plan.n_fired() > 0                               # chaos happened
    # response correctness under chaos
    for rid, resp in responses.items():
        if resp.degraded:
            _assert_degraded_contract(resp, ref[rid])
        else:
            assert resp.doc_ids == ref[rid].doc_ids
            np.testing.assert_array_equal(resp.scores, ref[rid].scores)
    # the corrupt specs healed: the shared index is verifiably clean
    assert idx.verify_integrity() > 0
    # the fleet survives: post-chaos traffic is fault-free and bit-exact
    router.max_queue = None              # lift the soak's admission bound
    ref2 = _reference(world)
    _assert_bit_exact(_drain(router, reqs), ref2, reqs)


# ---------------------------------------------------------------------------
# 2-worker failover under 8 forced host devices (subprocess)
# ---------------------------------------------------------------------------


def test_pinned_worker_failover_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    snippet = """
    import tempfile
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core.prettr import PreTTRConfig, init_prettr, make_backbone
    from repro.data.synthetic_ir import pack_query
    from repro.index import IndexBuilder, TermRepIndex
    from repro.serving import (FaultPlan, FaultSpec, RankingRouter,
                               RankingService, RankRequest, WorkerHealth)

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
        IndexBuilder(td + "/idx", cfg, params, codec="fp16",
                     n_shards=2, batch_size=8).build(docs)
        idx = TermRepIndex.open(td + "/idx")
        reqs = []
        for qi in range(4):
            q, qv = pack_query(rng.integers(5, 200, size=6), 8)
            reqs.append((q, qv, list(rng.integers(0, 24, size=7))))
        svc = RankingService(params, cfg, idx, micro_batch=4)
        for i, (q, qv, c) in enumerate(reqs):
            svc.submit(RankRequest(q, qv, c, request_id=str(i)))
        ref = {r.request_id: r.scores for r in svc.drain()}

        devices = jax.devices()[:2]
        router = RankingRouter(params, cfg, idx, n_shards=2,
                               devices=devices, micro_batch=4,
                               max_retries=0, dead_after=1,
                               retry_backoff_s=0.0)
        for w, d in zip(router.workers, devices):
            leaf = jax.tree_util.tree_leaves(w.engine.params)[0]
            assert leaf.devices() == {d}, (leaf.devices(), d)
        # kill worker 0 on its pinned device; the fleet keeps serving
        with FaultPlan([FaultSpec("worker.drain", "error", tag=0,
                                  count=None)]):
            for i, (q, qv, c) in enumerate(reqs):
                router.submit(RankRequest(q, qv, c, request_id=str(i)))
            got = {r.request_id: r for r in router.drain()}
            assert router.health[0].state == WorkerHealth.DEAD
            assert router.health[1].state == WorkerHealth.HEALTHY
            for rid in ref:
                assert not got[rid].degraded
                np.testing.assert_array_equal(got[rid].scores, ref[rid])
            # dead-worker traffic routes around at submit time
            for i, (q, qv, c) in enumerate(reqs):
                router.submit(RankRequest(q, qv, c, request_id=str(i)))
            again = {r.request_id: r for r in router.drain()}
            for rid in ref:
                np.testing.assert_array_equal(again[rid].scores, ref[rid])
        assert router.stats.n_failovers > 0
    print("OK pinned failover")
    """
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(snippet)],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, \
        f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "OK pinned failover" in out.stdout


@pytest.mark.parametrize("plan", [False, True])
def test_serve_cli_fails_on_unplanned_degraded(monkeypatch, tmp_path, plan):
    """`repro.launch.serve` exits 1 when a response comes back degraded
    with no fault plan installed (a broken kernel must not look like a
    served run); under an installed plan degraded responses are the
    expected outcome and the run completes."""
    import repro.serving as S
    from repro.launch import compile_cache, serve

    class AlwaysDegraded(S.RankingService):
        def drain(self):
            out = super().drain()
            for r in out:
                r.degraded = True
            return out

    monkeypatch.setattr(S, "RankingService", AlwaysDegraded)
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(sys, "argv", [
        "serve", "--service", "--n-docs", "16", "--n-queries", "2",
        "--candidates", "8", "--micro-batch", "8",
        "--index-dir", str(tmp_path / "idx")])
    if plan:
        with FaultPlan([]):
            serve.main()
        return
    with pytest.raises(SystemExit) as exc:
        serve.main()
    assert exc.value.code == 1
