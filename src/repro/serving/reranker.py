"""PreTTR re-ranking client (paper Fig. 1, step 3) — back-compat shim.

.. deprecated::
    ``Reranker`` is now a thin *single-query client* of
    :class:`repro.serving.service.RankingService`; new code should use the
    service directly — it exposes the same per-query behaviour plus
    admission queueing, cross-query micro-batch packing, overlapped index
    prefetch, and pluggable scheduling (``SchedulerPolicy``).

The public surface is unchanged: ``Reranker(params, cfg, index, ...)`` and
``rerank(q_tokens, q_valid, doc_ids) -> (ranked_ids, scores, RerankStats)``.
The index may be any :class:`~repro.index.store.TermRepIndex` — legacy v1
single-file or a sharded, codec-encoded v2 index from
:class:`repro.index.IndexBuilder` (int8 streams decode on device inside
the service's scoring step).
Each ``rerank`` call submits one :class:`RankRequest` to a private service
and drains it, so per-query numerics, the query-rep LRU cache, the fixed
micro-batch shapes, and the deadline/split straggler policy (now the
default ``SchedulerPolicy``) all behave exactly as before.
"""
from __future__ import annotations

from typing import Sequence

import jax
import numpy as np

from repro.core import prettr as P
from repro.index.store import TermRepIndex
from repro.serving.service import RankingService, RerankStats  # noqa: F401

__all__ = ["Reranker", "RerankStats"]


class Reranker:
    def __init__(self, params, cfg: P.PreTTRConfig, index: TermRepIndex,
                 micro_batch: int = 32, deadline_s: float | None = None,
                 cache_size: int = 64, backend: str | None = None):
        # encode/join are late-bound through the instance attributes so
        # tests (and callers) can still monkeypatch `rr._join`/`rr._encode`
        self._service = RankingService(
            params, cfg, index, micro_batch=micro_batch,
            cache_size=cache_size, backend=backend,
            encode_fn=lambda *a: self._encode(*a),
            join_fn=lambda *a: self._join(*a))
        cfg = self._service.cfg            # backend override already applied
        self.cfg = cfg
        self.index = index
        self.deadline_s = deadline_s       # read per rerank(): stays mutable

        self._encode = jax.jit(
            lambda p, t, v: P.encode_query(p, cfg, t, v))
        self._join = jax.jit(
            lambda p, qr, qv, st, dv: P.join_and_score(p, cfg, qr, qv, st, dv))

    # params/micro_batch proxy the service so post-construction mutation
    # keeps affecting subsequent rerank() calls (as on the original class)
    @property
    def params(self):
        return self._service.params

    @params.setter
    def params(self, value):
        self._service.params = value

    @property
    def micro_batch(self):
        return self._service.micro_batch

    @micro_batch.setter
    def micro_batch(self, value):
        self._service.micro_batch = value

    @property
    def stats(self):
        """The private service's ``ServiceStats`` (per-batch phase clocks,
        summed over every ``rerank`` since the last :meth:`reset_stats`)."""
        return self._service.stats

    def reset_stats(self) -> None:
        self._service.reset_stats()

    def rerank(self, q_tokens: np.ndarray, q_valid: np.ndarray,
               doc_ids: Sequence[int]):
        """-> (doc_ids sorted by descending score, scores, stats)."""
        resp = self._service.rank(q_tokens, q_valid, doc_ids,
                                  deadline_s=self.deadline_s)
        return resp.doc_ids, resp.scores, resp.stats
