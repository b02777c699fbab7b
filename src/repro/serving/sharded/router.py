"""RankingRouter: the query-side front of scale-out serving.

The router owns everything a single-process ``RankingService`` owns
*except* the document side: admission (typed ``RankRequest``s, bad-id /
misroute rejection with the full corpus view, bounded-queue shedding),
the shared query-rep LRU (each distinct query is encoded through layers
``0..l`` exactly once, no matter how many shards its candidates fan out
to), shard-affinity candidate routing, the scatter of per-shard candidate
slices, the score all-gather + per-query merge, and aggregate accounting
across workers.

Shard-affinity routing is the core invariant: a candidate's stored bytes
**never leave the shard that stores them**.  The router routes ids by the
deterministic :meth:`TermRepIndex.serving_assignment` map (derived from
the format-v2 doc table's physical-shard column), each
:class:`~repro.serving.sharded.worker.ShardWorker` gathers only from its
own :class:`~repro.index.store.ShardIndexView` (which *raises* on a
misrouted id rather than reading across), and only two things ever cross
shards: query reps going out (``[1, Lq, d]`` per query per shard) and
float32 scores coming back (the all-gather).  There is no cross-shard
re-gather of document state — **except** through the explicit failover
path: when a shard is unhealthy, its candidates are re-gathered from the
full :class:`TermRepIndex` by the router's own fallback engine, which is
a deliberate, counted (``stats.n_failovers``) violation of affinity in
exchange for availability.

Fault tolerance (the robustness tentpole):

* every worker has a :class:`WorkerHealth` state machine —
  ``healthy -> degraded`` on a failed drain, ``-> dead`` after
  ``dead_after`` consecutive failures or immediately on a drain
  *timeout* (a stuck drain thread still owns the worker's engine, so a
  timed-out worker can never be safely reused);
* worker drains are *timed* (``SchedulerPolicy.drain_timeout``, override
  with ``drain_timeout_s``) instead of joined unboundedly — one wedged
  shard can no longer hang ``drain()`` forever;
* a failed shard task is retried on its own worker up to ``max_retries``
  times with linear backoff (``stats.n_retries``), then failed over to
  the full-index fallback engine (``stats.n_failovers``), and only when
  that also fails do the affected rows come back as a **degraded
  response**: ``degraded=True``, the unrecoverable candidates listed in
  ``failed_doc_ids`` with ``-inf`` scores (they sort last), every other
  row bit-exact (``stats.n_degraded``);
* ``submit()`` sheds with :class:`ServiceOverloadError` beyond
  ``max_queue`` in-flight requests (``stats.n_shed``).

Bit-exactness: the merged response for any request equals what a single-
process ``RankingService`` over the whole index returns for the same
candidates — each score row is computed by the same jitted
``join_and_score`` from the same stored bytes, and rows are batch-
independent, so neither packing differences nor shard fan-out nor the
retry/failover re-scoring can change a score (tests assert bitwise
equality across backends, codecs, cache states, shard counts, and
injected-fault recovery).
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving import spans
from repro.serving.service import (BatchEngine, RankRequest, RankResponse,
                                   RerankStats, SchedulerPolicy,
                                   ServiceOverloadError, ServiceStats,
                                   encode_query_jit, validate_doc_routing,
                                   validate_index_compat)
from repro.serving.spans import span
from repro.serving.sharded.worker import ShardTask, ShardWorker


def worker_devices(n_shards: int):
    """One device per shard worker: the first ``n_shards`` devices.

    On a TPU, fewer devices than shards is an error — workers sharing a
    chip would serve (and measure) a different system.  Off the chip,
    without forced host devices, the workers share the default device
    (``None``): same scores, which is what the CPU tests check."""
    devs = jax.devices()
    if len(devs) >= n_shards:
        return devs[:n_shards]
    if devs[0].platform == "tpu":
        raise ValueError(
            f"{n_shards} serving shards need {n_shards} devices; this "
            f"host has {len(devs)} {devs[0].device_kind}")
    return None


class WorkerHealth:
    """Per-worker health state machine.

    ``HEALTHY`` — serving normally.  ``DEGRADED`` — at least one recent
    drain failed; still receives traffic (the next clean drain restores
    ``HEALTHY``).  ``DEAD`` — ``dead_after`` consecutive failures, or one
    drain *timeout* (the stuck drain thread still owns the worker's
    engine, so the worker can never be safely reused): the router stops
    routing to it and serves its documents through the fallback engine.
    """

    HEALTHY = "healthy"
    DEGRADED = "degraded"
    DEAD = "dead"

    def __init__(self, shard_id: int, dead_after: int = 3):
        self.shard_id = int(shard_id)
        self.dead_after = max(1, int(dead_after))
        self.state = self.HEALTHY
        self.consecutive_failures = 0
        self.n_failures = 0
        self.n_timeouts = 0
        self.last_error: BaseException | None = None

    def on_success(self) -> None:
        if self.state != self.DEAD:
            self.state = self.HEALTHY
            self.consecutive_failures = 0

    def on_failure(self, err: BaseException | None = None) -> None:
        self.n_failures += 1
        self.consecutive_failures += 1
        if err is not None:
            self.last_error = err
        if self.state != self.DEAD:
            self.state = (self.DEAD
                          if self.consecutive_failures >= self.dead_after
                          else self.DEGRADED)

    def on_timeout(self, timeout_s: float) -> None:
        self.n_failures += 1
        self.n_timeouts += 1
        self.consecutive_failures += 1
        self.last_error = TimeoutError(
            f"shard {self.shard_id} drain exceeded {timeout_s:.1f}s")
        self.state = self.DEAD

    def __repr__(self):
        return (f"WorkerHealth(shard={self.shard_id}, {self.state}, "
                f"failures={self.n_failures}, timeouts={self.n_timeouts})")


class _RouterReq:
    """Router-side record of one in-flight request: the full candidate
    list, the score buffer the shard tasks scatter back into, row-level
    completion accounting (``pending_rows`` — retry/failover clones
    resolve row subsets independently, so shard-level counting would
    double-resolve), the set of candidate positions no recovery path
    could score (``failed_idx`` -> the degraded response), and the
    *uncommitted* query reps the fallback engine re-scores with."""

    __slots__ = ("rid", "doc_ids", "scores", "stats", "t_submit",
                 "pending_rows", "failed_idx", "q_reps", "q_valid_j")

    def __init__(self, rid: str, doc_ids):
        self.rid = rid
        self.doc_ids = list(doc_ids)
        self.scores = np.zeros(len(self.doc_ids), np.float32)
        self.stats = RerankStats(n_docs=len(self.doc_ids))
        self.t_submit = time.perf_counter()
        self.pending_rows = 0
        self.failed_idx: set[int] = set()
        self.q_reps = None
        self.q_valid_j = None


class RankingRouter:
    """Scale-out re-ranking service: one router, ``n_shards`` workers.

    Drop-in for ``RankingService`` on the request path — ``submit`` /
    ``drain`` / ``rank`` / ``stats`` / ``reset_stats`` have the same
    shapes — so benchmarks and the serve CLI drive either through one
    code path.

    Placement: pass ``mesh`` (a mesh with a ``"shard"`` axis — see
    :func:`repro.dist.sharded_serving_rules`) or an explicit ``devices``
    list to pin worker ``i`` to device ``i``; with neither, workers share
    jax's default device (functionally identical, no scale-out — the
    single-device test configuration).  ``doc_cache_mb`` is **per
    worker**: each shard caches its own hot docs on its own device, so
    the fleet's aggregate cache grows with the shard count exactly like
    the index slices do.

    ``drain`` scatter-gathers: every live worker with queued tasks drains
    concurrently on its own thread under a shared wall timeout (each runs
    its own prefetch pipeline and scoring jits on its own device),
    completed per-shard score slices scatter back into each request's
    buffer by original candidate position, failed rows walk the
    retry -> failover -> degrade ladder (module docstring), and a
    request's response is emitted once its last row resolves.  Aggregate
    :attr:`stats` merge the workers' (and fallback engine's) counters
    through ``ServiceStats.merge``; :attr:`worker_stats` keeps the
    per-shard view and :attr:`health` the per-worker state machines.

    Fault-tolerance knobs: ``max_retries`` same-worker re-attempts per
    failed task (with ``retry_backoff_s * attempt`` linear backoff),
    ``dead_after`` consecutive failures before a worker is declared dead,
    ``drain_timeout_s`` overrides the policy-derived per-drain wall
    budget, ``max_queue`` bounds in-flight requests (``submit`` sheds
    with :class:`ServiceOverloadError` beyond it).
    """

    def __init__(self, params, cfg, index, *, n_shards: int | None = None,
                 mesh=None, devices=None, backend: str | None = None,
                 micro_batch: int = 32,
                 policy: SchedulerPolicy | None = None,
                 cache_size: int = 64, prefetch_depth: int = 2,
                 deadline_s: float | None = None,
                 encode_fn=None, validate_index: bool = True,
                 fused: bool = True, use_layer_kv: bool | None = None,
                 doc_cache_mb: float = 0.0,
                 page_tokens: int | None = None,
                 page_bucket: bool = False,
                 max_retries: int = 1, retry_backoff_s: float = 0.05,
                 dead_after: int = 3, drain_timeout_s: float | None = None,
                 max_queue: int | None = None):
        if backend is not None:
            from repro.models.backend import apply_backend
            cfg = apply_backend(cfg, backend)
        if mesh is not None:
            from repro.dist import serving_shard_devices
            mesh_devs = serving_shard_devices(mesh)
            if devices is None:
                devices = mesh_devs
            if n_shards is None:
                n_shards = len(devices)
            if n_shards != len(devices):
                raise ValueError(
                    f"n_shards={n_shards} but the mesh's shard axis has "
                    f"{len(mesh_devs)} positions")
        if n_shards is None:
            n_shards = len(devices) if devices else 1
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        if devices is not None and len(devices) != n_shards:
            raise ValueError(
                f"{len(devices)} devices for {n_shards} shards")
        if validate_index:
            validate_index_compat(cfg, index)
        self.cfg = cfg
        self.index = index
        self.n_shards = int(n_shards)
        self.default_deadline_s = deadline_s
        self.max_retries = max(0, int(max_retries))
        self.retry_backoff_s = float(retry_backoff_s)
        self.drain_timeout_s = drain_timeout_s
        self.max_queue = max_queue
        self.assignment = index.serving_assignment(self.n_shards)
        self._policy = policy or SchedulerPolicy()
        devs = list(devices) if devices is not None else [None] * n_shards
        self.workers = [
            ShardWorker(params, cfg, index.shard_view(self.assignment, s),
                        shard_id=s, device=devs[s], micro_batch=micro_batch,
                        policy=self._policy, prefetch_depth=prefetch_depth,
                        fused=fused, use_layer_kv=use_layer_kv,
                        doc_cache_mb=doc_cache_mb, page_tokens=page_tokens,
                        page_bucket=page_bucket)
            for s in range(self.n_shards)]
        self.health = [WorkerHealth(s, dead_after=dead_after)
                       for s in range(self.n_shards)]
        self.params = params
        self._encode = encode_fn or encode_query_jit(cfg)
        self._qcache: OrderedDict = OrderedDict()
        self._cache_size = cache_size
        self._seq = 0
        self._inflight: dict[str, _RouterReq] = {}
        self._done_early: list[RankResponse] = []
        #: tasks each worker currently owes (cloned away on its failure)
        self._routed: list[list[ShardTask]] = [[] for _ in range(n_shards)]
        #: tasks routed around dead workers at submit time
        self._fallback_queue: list[ShardTask] = []
        # the fallback engine re-gathers an unhealthy shard's candidates
        # from the FULL index (affinity deliberately broken for
        # availability); built lazily on first failover, rebuilt if it
        # itself fails, never doc-cached (cold + correct beats stale)
        self._fallback: BatchEngine | None = None
        self._fallback_stats = ServiceStats()
        self._engine_kwargs = dict(
            micro_batch=micro_batch, prefetch_depth=prefetch_depth,
            fused=fused, use_layer_kv=use_layer_kv)
        #: admission-side counters (n_requests, query_encode_s, router
        #: drain wall, fault-ladder counters); worker counters merge in
        #: via :attr:`stats`
        self._admission_stats = ServiceStats()

    # -- accounting ----------------------------------------------------------
    @property
    def stats(self) -> ServiceStats:
        """Aggregate across the router, every worker, and the fallback
        engine (see ``ServiceStats.merge`` for per-field semantics).
        ``wall_s`` is the router's own drain wall — it brackets the
        concurrent worker drains, so merging by max keeps it the fleet's
        true elapsed time."""
        out = self._admission_stats
        for w in self.workers:
            out = out.merge(w.stats)
        out = out.merge(self._fallback_stats)
        if self._fallback is not None:
            out = out.merge(self._fallback.stats)
        return out

    @property
    def doc_cache(self):
        """Worker 0's device doc cache (None when caching is disabled) —
        the presence probe CLIs use; each worker's own cache is at
        ``router.workers[i].doc_cache``."""
        return self.workers[0].doc_cache

    @property
    def worker_stats(self) -> list[ServiceStats]:
        """Per-shard counters, shard order (the issue's 'aggregate as a
        list' view for gauges like ``resident_docs``)."""
        return [w.stats for w in self.workers]

    def reset_stats(self) -> None:
        self._admission_stats = ServiceStats()
        self._fallback_stats = ServiceStats()
        if self._fallback is not None:
            self._fallback.stats = ServiceStats()
        for w in self.workers:
            w.reset_stats()

    # -- admission -----------------------------------------------------------
    def submit(self, req: RankRequest) -> str:
        """Queue a request: validate ids against the *full* corpus view,
        encode the query once (shared LRU), split the candidate list by
        shard assignment, and enqueue one :class:`ShardTask` per live
        shard that owns any of its candidates (a dead shard's slice is
        queued for the fallback engine instead).  Sheds with
        :class:`ServiceOverloadError` beyond ``max_queue`` in-flight
        requests."""
        rid = req.request_id or f"req-{self._seq}"
        if self.max_queue is not None \
                and len(self._inflight) >= self.max_queue:
            self._admission_stats.n_shed += 1
            raise ServiceOverloadError(
                f"request {rid} shed: {len(self._inflight)} requests "
                f"in flight (max_queue={self.max_queue}); drain() or "
                f"back off")
        if len(req.doc_ids):
            try:
                validate_doc_routing(self.index, req.doc_ids)
            except ValueError as e:
                raise ValueError(f"request {rid}: {e}") from None
        rec = _RouterReq(rid, req.doc_ids)
        seq = self._seq
        self._seq += 1
        self._admission_stats.n_requests += 1
        if not rec.doc_ids:                # nothing to rank; respond now
            self._done_early.append(RankResponse(
                request_id=rid, doc_ids=[],
                scores=np.zeros((0,), np.float32), stats=rec.stats,
                latency_s=0.0))
            return rid
        with span(spans.ENCODE) as sp:
            q_reps = self._query_reps(np.asarray(req.q_tokens),
                                      np.asarray(req.q_valid))
        rec.stats.query_encode_s = sp.s
        self._admission_stats.query_encode_s += sp.s
        q_valid = jnp.asarray(req.q_valid)
        # the fallback engine re-scores with the router's own uncommitted
        # copies (a dead worker's device may be gone with it)
        rec.q_reps = q_reps
        rec.q_valid_j = q_valid
        deadline = (req.deadline_s if req.deadline_s is not None
                    else self.default_deadline_s)

        ids = np.asarray(rec.doc_ids, np.int64)
        homes = self.assignment[ids]
        for s in np.unique(homes):
            sel = np.flatnonzero(homes == s)
            s = int(s)
            task = ShardTask(
                rid, seq, ids[sel].tolist(), sel,
                priority=req.priority, deadline_s=deadline,
                q_reps=q_reps, q_valid_j=q_valid, shard_id=s)
            if self.health[s].state == WorkerHealth.DEAD:
                self._fallback_queue.append(task)
            else:
                w = self.workers[s]
                # query reps cross the shard boundary here — the only
                # doc-ward traffic; each worker gets its own committed copy
                task.q_reps = w.put(q_reps)
                task.q_valid_j = w.put(q_valid)
                w.enqueue(task)
                self._routed[s].append(task)
            rec.pending_rows += len(sel)
        self._inflight[rid] = rec
        return rid

    def rank(self, q_tokens, q_valid, doc_ids, *, priority: int = 0,
             deadline_s: float | None = None,
             request_id: str | None = None) -> RankResponse:
        """Synchronous single-query convenience: submit + drain (drains
        everything queued; other requests' responses are buffered for the
        next ``drain()``)."""
        rid = self.submit(RankRequest(q_tokens, q_valid, list(doc_ids),
                                      request_id=request_id,
                                      priority=priority,
                                      deadline_s=deadline_s))
        out = None
        for resp in self.drain():
            if resp.request_id == rid:
                out = resp
            else:
                self._done_early.append(resp)
        assert out is not None
        return out

    def _query_reps(self, q_tokens: np.ndarray, q_valid: np.ndarray):
        key = (q_tokens.tobytes(), q_valid.tobytes())
        if key in self._qcache:
            self._qcache.move_to_end(key)
            return self._qcache[key]
        reps = self._encode(self.params, q_tokens[None], q_valid[None])
        reps.block_until_ready()
        self._qcache[key] = reps
        if len(self._qcache) > self._cache_size:
            self._qcache.popitem(last=False)
        return reps

    # -- scatter / gather ----------------------------------------------------
    def drain(self) -> list[RankResponse]:
        """Drain every live worker concurrently under a shared wall
        timeout, walk failed tasks down the retry -> failover -> degrade
        ladder, merge per-shard score slices, and return completed
        responses in completion order.  Never raises for a worker fault
        and never blocks past the timeout budget — every submitted
        request gets a response (possibly degraded)."""
        t_wall = time.perf_counter()
        done: list[RankResponse] = list(self._done_early)
        self._done_early.clear()
        fallback_tasks = list(self._fallback_queue)
        self._fallback_queue.clear()
        busy = [(s, w) for s, w in enumerate(self.workers)
                if w.pending and self.health[s].state != WorkerHealth.DEAD]
        if busy:
            timeout = self._drain_timeout()
            outcomes = self._timed_drains([w for _, w in busy], timeout)
            for (s, w), (status, payload) in zip(busy, outcomes):
                if status == "timeout":
                    # the stuck thread still owns the engine: clone the
                    # outstanding tasks away (its late writes land in the
                    # abandoned originals) and never reuse the worker
                    self.health[s].on_timeout(timeout)
                    fallback_tasks += [t.clone() for t in self._routed[s]]
                    self._routed[s] = []
                elif status == "error":
                    self.health[s].on_failure(payload)
                    w.abandon()
                    clones = [t.clone() for t in self._routed[s]]
                    self._routed[s] = []
                    fallback_tasks += self._retry(s, clones, done)
                else:
                    retry_clones: list[ShardTask] = []
                    err = None
                    for task in payload:
                        retry_clones += self._merge_task(task, done)
                        err = task.error or err
                    self._routed[s] = []
                    if retry_clones:
                        # engine-isolated plan faults: worker trouble too
                        self.health[s].on_failure(err)
                        fallback_tasks += self._retry(s, retry_clones, done)
                    else:
                        self.health[s].on_success()
        self._failover(fallback_tasks, done)
        self._admission_stats.wall_s += time.perf_counter() - t_wall
        return done

    def _timed_drains(self, targets, timeout_s: float):
        """Run each target's ``drain()`` on its own thread under one
        shared wall deadline (drains are concurrent, so the per-worker
        budget IS the wall budget).  -> list of ``("ok", tasks)`` /
        ``("error", exc)`` / ``("timeout", None)``, target order.
        Completion is detected by per-thread events, never an unbounded
        ``join()``."""
        results: list = [None] * len(targets)
        errors: list = [None] * len(targets)
        events = [threading.Event() for _ in targets]

        def _run(i, t):
            try:
                results[i] = t.drain()
            except BaseException as e:                # noqa: BLE001
                errors[i] = e
            finally:
                events[i].set()

        for i, t in enumerate(targets):
            threading.Thread(target=_run, args=(i, t), daemon=True).start()
        deadline = time.monotonic() + timeout_s
        out = []
        for i, ev in enumerate(events):
            if not ev.wait(max(0.0, deadline - time.monotonic())):
                out.append(("timeout", None))
            elif errors[i] is not None:
                out.append(("error", errors[i]))
            else:
                out.append(("ok", results[i]))
        return out

    def _drain_timeout(self) -> float:
        if self.drain_timeout_s is not None:
            return self.drain_timeout_s
        deadlines, n_rows = [], 0
        for tasks in self._routed:
            for t in tasks:
                deadlines.append(t.deadline_s)
                n_rows += t.n
        return self._policy.drain_timeout(deadlines, n_rows)

    # -- the recovery ladder -------------------------------------------------
    def _retry(self, s: int, tasks: list[ShardTask], done: list) \
            -> list[ShardTask]:
        """Re-enqueue failed-task clones on their own worker, up to
        ``max_retries`` attempts with linear backoff.  Returns the tasks
        no attempt recovered (they continue to failover)."""
        remaining = tasks
        attempt = 0
        while (remaining and attempt < self.max_retries
               and self.health[s].state != WorkerHealth.DEAD):
            attempt += 1
            self._admission_stats.n_retries += len(remaining)
            time.sleep(self.retry_backoff_s * attempt)
            w = self.workers[s]
            for t in remaining:
                w.enqueue(t)
            self._routed[s] = list(remaining)
            (status, payload), = self._timed_drains(
                [w], self._drain_timeout())
            if status == "timeout":
                self.health[s].on_timeout(self._drain_timeout())
                remaining = [t.clone() for t in self._routed[s]]
                self._routed[s] = []
                break
            if status == "error":
                self.health[s].on_failure(payload)
                w.abandon()
                remaining = [t.clone() for t in self._routed[s]]
                self._routed[s] = []
                continue
            next_round: list[ShardTask] = []
            err = None
            for task in payload:
                next_round += self._merge_task(task, done)
                err = task.error or err
            self._routed[s] = []
            if next_round:
                self.health[s].on_failure(err)
            else:
                self.health[s].on_success()
            remaining = next_round
        return remaining

    def _failover(self, tasks: list[ShardTask], done: list) -> None:
        """Re-score tasks through the full-index fallback engine (shard
        affinity deliberately broken — the shard that owns the bytes is
        unhealthy).  Rows the fallback also fails degrade."""
        if not tasks:
            return
        self._admission_stats.n_failovers += len(tasks)
        if self._fallback is None:
            self._fallback = BatchEngine(
                self.params, self.cfg, self.index,
                policy=self._policy, fault_tag="fallback",
                **self._engine_kwargs)
        eng = self._fallback
        clones = []
        for t in tasks:
            rec = self._inflight.get(t.rid)
            if rec is None:
                continue
            c = t.clone(q_reps=rec.q_reps, q_valid_j=rec.q_valid_j)
            clones.append(c)
            eng.enqueue(c)
        (status, payload), = self._timed_drains([eng], self._drain_timeout())
        if status == "ok":
            for task in payload:
                for c in self._merge_task(task, done):
                    self._degrade_rows(c, done)
        else:
            if status == "error":
                eng.abandon_pending()
            # a timed-out fallback's drain thread still owns this engine;
            # a failed one may be wedged — rebuild lazily either way
            self._fallback_stats = self._fallback_stats.merge(eng.stats)
            self._fallback = None
            for c in clones:
                self._degrade_rows(c, done)

    # -- merge ---------------------------------------------------------------
    def _merge_task(self, task: ShardTask, done: list) -> list[ShardTask]:
        """Scatter one completed task's *good* rows back into its
        request's buffer; return a subset clone of any failed rows (the
        next rung of the recovery ladder re-scores exactly those)."""
        rec = self._inflight.get(task.rid)
        if rec is None:
            return []
        failed = sorted(set(task.failed_idx))
        good = [i for i in range(task.n) if i not in set(failed)]
        if good:
            rec.scores[task.cand_idx[good]] = task.scores[good]
            rec.pending_rows -= len(good)
        # the request is answered when its slowest shard task is
        rec.stats.queue_wait_s = max(rec.stats.queue_wait_s,
                                     task.stats.queue_wait_s)
        rec.stats.n_redispatch += task.stats.n_redispatch
        self._maybe_finish(rec, done)
        if failed:
            return [task.clone(failed)]
        return []

    def _degrade_rows(self, task: ShardTask, done: list) -> None:
        """End of the ladder: every row of ``task`` is unrecoverable —
        record the candidate positions on the request (-> ``degraded``
        response with ``failed_doc_ids``), score them ``-inf`` so they
        sort last, and resolve them."""
        rec = self._inflight.get(task.rid)
        if rec is None:
            return
        for i in range(task.n):
            ci = int(task.cand_idx[i])
            rec.failed_idx.add(ci)
            rec.scores[ci] = -np.inf
        rec.pending_rows -= task.n
        self._maybe_finish(rec, done)

    def _maybe_finish(self, rec: _RouterReq, done: list) -> None:
        if rec.pending_rows <= 0 and rec.rid in self._inflight:
            del self._inflight[rec.rid]
            done.append(self._finalize(rec))

    def _finalize(self, rec: _RouterReq) -> RankResponse:
        order = np.argsort(-rec.scores)
        failed = sorted(rec.failed_idx)
        if failed:
            self._admission_stats.n_degraded += 1
        return RankResponse(
            request_id=rec.rid,
            doc_ids=[rec.doc_ids[i] for i in order],
            scores=rec.scores[order],
            stats=rec.stats,
            latency_s=time.perf_counter() - rec.t_submit,
            degraded=bool(failed),
            failed_doc_ids=[rec.doc_ids[i] for i in failed])
