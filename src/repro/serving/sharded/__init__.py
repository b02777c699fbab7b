"""Scale-out serving: sharded index + router/shard-worker subsystem.

The single-process ``RankingService`` caps PreTTR's throughput at one
device no matter how fast PRs 5/7 made the join — this package splits it
into the two halves that scale independently:

* :class:`~repro.serving.sharded.worker.ShardWorker` — one per index
  shard: owns that shard's :class:`~repro.index.store.ShardIndexView`,
  paged device doc cache, prefetch pipeline, and scoring jits, pinned to
  one device of the serving mesh (``repro.dist.sharded_serving_rules`` /
  ``serving_shard_devices``).
* :class:`~repro.serving.sharded.router.RankingRouter` — the query-side
  front: admission, the shared query-rep LRU, shard-affinity candidate
  routing over :meth:`TermRepIndex.serving_assignment`, concurrent
  scatter/drain of the workers, score all-gather + per-query merge, and
  merged ``ServiceStats`` accounting.

Invariants: a doc's bytes never leave the shard that stores them (only
query reps go out, only scores come back), and the merged scores are
bit-exact against a single-process ``RankingService`` over the whole
index for the same candidates.  Under faults, the router degrades
instead of dying: per-worker :class:`WorkerHealth` state machines,
timed drains, bounded retry, full-index failover, and degraded
responses (see the router module docstring).
"""
from repro.serving.sharded.router import (RankingRouter, WorkerHealth,
                                          worker_devices)
from repro.serving.sharded.worker import ShardTask, ShardWorker

__all__ = ["RankingRouter", "ShardTask", "ShardWorker", "WorkerHealth",
           "worker_devices"]
