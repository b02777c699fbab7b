"""ShardWorker: one serving shard's half of the scale-out split.

A worker owns everything *document-sided* for one slice of the corpus:
the :class:`~repro.index.store.ShardIndexView` over its slice of the
format-v2 doc table (ownership-checked — gathering a doc routed to the
wrong shard raises instead of silently reading another shard's bytes),
its own paged :class:`~repro.serving.doc_cache.DeviceDocCache`, its own
prefetch thread, and its own scoring jits — all composed through the same
:class:`~repro.serving.service.BatchEngine` that powers the single-
process ``RankingService``, pinned to one device of the serving mesh.

The worker has **no query side**: the router encodes queries once
(shared query-rep LRU) and hands each worker device-resident ``q_reps``
inside :class:`ShardTask` objects.  Scoring a task's rows is therefore
bit-identical to the single-process service scoring the same candidates
— same stored bytes, same jitted ``join_and_score`` rows, and row scores
are batch-independent — which is the invariant that makes the scale-out
path safe to adopt.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from repro.serving import faults
from repro.serving.service import (BatchEngine, RerankStats, SchedulerPolicy,
                                   ServiceStats)


@dataclasses.dataclass
class _TaskDocs:
    """Duck-typed ``req`` for the engine's row admission (it only reads
    ``.doc_ids``)."""
    doc_ids: list


class ShardTask:
    """One request's candidate slice routed to one shard: the engine-state
    contract (see ``BatchEngine``) plus the bookkeeping the router needs
    to merge scores back — ``rid`` and ``cand_idx`` (each routed doc's
    position in the *original* request candidate list, so duplicates and
    interleavings scatter back exactly)."""

    __slots__ = ("req", "rid", "seq", "n", "priority", "deadline_s",
                 "q_reps", "q_valid_j", "scores", "n_done", "t_submit",
                 "t_enqueue", "stats", "cand_idx", "shard_id", "failed_idx",
                 "error")

    def __init__(self, rid: str, seq: int, doc_ids, cand_idx, *,
                 priority: int = 0, deadline_s: float | None = None,
                 q_reps=None, q_valid_j=None, shard_id: int = 0):
        self.req = _TaskDocs(doc_ids=list(doc_ids))
        self.rid = rid
        self.seq = seq
        self.n = len(self.req.doc_ids)
        self.priority = priority
        self.deadline_s = deadline_s
        self.q_reps = q_reps              # [1, Lq, d] on the worker's device
        self.q_valid_j = q_valid_j        # [Lq] on the worker's device
        self.scores = np.zeros(self.n, np.float32)
        self.n_done = 0
        self.t_submit = time.perf_counter()
        self.t_enqueue = None             # set by BatchEngine.enqueue
        self.stats = RerankStats(n_docs=self.n)
        self.cand_idx = np.asarray(cand_idx, np.int64)
        self.shard_id = shard_id
        self.failed_idx: list[int] = []   # task-local rows a fault hit
        self.error: BaseException | None = None

    def clone(self, sel=None, *, q_reps=None, q_valid_j=None,
              shard_id: int | None = None) -> "ShardTask":
        """A fresh, unscored task over a row subset (``sel`` indexes into
        *this task's* rows; None = all) — what retry and failover enqueue,
        so a stale drain thread's late writes land in the abandoned
        original, never in the in-flight copy."""
        if sel is None:
            sel = range(self.n)
        return ShardTask(
            self.rid, self.seq,
            [self.req.doc_ids[i] for i in sel],
            self.cand_idx[list(sel)],
            priority=self.priority, deadline_s=self.deadline_s,
            q_reps=self.q_reps if q_reps is None else q_reps,
            q_valid_j=self.q_valid_j if q_valid_j is None else q_valid_j,
            shard_id=self.shard_id if shard_id is None else shard_id)


class ShardWorker:
    """One index shard's scoring node.

    ``index_view`` is the shard's :class:`ShardIndexView`; ``device``
    (optional) pins the worker's params, staged batches, and doc-cache
    pools to one device via explicit ``jax.device_put`` — thread-safe
    where the thread-local ``jax.default_device`` is not, which matters
    because each worker drains on its own thread and runs its own
    prefetch thread.  Unpinned (``device=None``) workers share jax's
    default device: same scores, no scale-out — the single-device test
    and CI-smoke configuration.
    """

    def __init__(self, params, cfg, index_view, *, shard_id: int,
                 device=None, micro_batch: int = 32,
                 policy: SchedulerPolicy | None = None,
                 prefetch_depth: int = 2, fused: bool = True,
                 use_layer_kv: bool | None = None,
                 doc_cache_mb: float = 0.0,
                 page_tokens: int | None = None,
                 page_bucket: bool = False):
        self.shard_id = int(shard_id)
        self.device = device
        self.index = index_view
        self.engine = BatchEngine(
            params, cfg, index_view, micro_batch=micro_batch, policy=policy,
            prefetch_depth=prefetch_depth, fused=fused,
            use_layer_kv=use_layer_kv, doc_cache_mb=doc_cache_mb,
            page_tokens=page_tokens, page_bucket=page_bucket, device=device,
            fault_tag=self.shard_id)

    def put(self, x):
        """Commit an array to this worker's device (identity when the
        worker is unpinned)."""
        return jax.device_put(x, self.device) if self.device is not None \
            else x

    @property
    def n_owned(self) -> int:
        return self.index.n_owned

    @property
    def stats(self) -> ServiceStats:
        return self.engine.stats

    def reset_stats(self) -> None:
        self.engine.stats = ServiceStats()

    @property
    def doc_cache(self):
        return self.engine.doc_cache

    @property
    def pending(self) -> bool:
        return self.engine.pending

    def enqueue(self, task: ShardTask) -> None:
        self.engine.enqueue(task)

    def drain(self) -> list[ShardTask]:
        """Score every enqueued task to completion -> completed tasks.
        Runs this worker's whole pipeline (planning, prefetch+H2D onto its
        device, scoring jits); safe to call concurrently with other
        workers' drains."""
        faults.hit("worker.drain", tag=self.shard_id)
        return self.engine.drain()

    def abandon(self) -> list[ShardTask]:
        """Drop every enqueued-but-unfinished task (router failover path);
        returns the distinct abandoned tasks."""
        return self.engine.abandon_pending()
