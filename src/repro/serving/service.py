"""RankingService: the request/response serving surface for PreTTR.

The paper's 42x win (Table 5) is a *per-query* cost split — Query encode /
Decompress / Combine — but a production server amortizes it across many
concurrent queries.  This module turns the one-query-at-a-time
``Reranker.rerank`` loop into a service:

* **Admission** — typed :class:`RankRequest` objects enter a queue
  (``submit``); each query is encoded through layers ``0..l`` once, via an
  LRU query-rep cache (Table 5's "Query" phase, shared across repeats).
* **Packing** — the scheduler packs candidate rows from *multiple in-flight
  queries* into shared fixed-shape micro-batches.  ``join_and_score``
  already takes per-row ``q_reps``, so a packed batch just gathers each
  row's query reps from the cache — one jit cache entry regardless of how
  traffic interleaves, and no model change.
* **Overlapped I/O** — a prefetch thread pulls the next batches' term reps
  from the :class:`~repro.index.store.TermRepIndex` (``gather`` — Table 5's
  "Decompress"-adjacent host load) and ``jax.device_put``\\ s them while the
  device runs the previous batch's Combine phase (layers ``l..n`` + the
  CLS-only final layer).  Double-buffered: the output queue holds at most
  ``prefetch_depth`` staged batches.
* **Straggler policy** — the per-batch deadline / split-and-redispatch
  behaviour that used to live inline in ``Reranker`` is a pluggable
  :class:`SchedulerPolicy` (ordering, batch deadline, split).

The scheduler/packer/scorer core lives in :class:`BatchEngine` so it can
be composed twice: ``RankingService`` pairs one engine with the admission
/ query-encode side for the classic single-process service, and
``repro.serving.sharded.ShardWorker`` pairs one engine *per index shard*
(pinned to its own device, with its own doc cache and prefetch thread)
behind a :class:`~repro.serving.sharded.RankingRouter`.

The Table-5 split is kept per micro-batch in :class:`ServiceStats`:
``query_encode_s`` (Query), ``load_s`` (index gather + H2D + packed q-rep
assembly — overlapped with device compute, so phase sums can exceed wall
clock), ``combine_s`` (Decompress + Combine on device).  Each clock is a
named span (``repro.serving.spans``) that also lands on the profiler's
trace.  Per request, :class:`RerankStats` keeps its query encode and its
wait in the row pool before its first micro-batch is staged.

Equivalence invariant (tests/test_service.py): for any workload, the packed
service returns per query exactly what a sequential ``Reranker.rerank``
returns — rows are batch-independent in ``join_and_score``, so packing
changes throughput, never scores.
"""
from __future__ import annotations

import dataclasses
import logging
import queue
import threading
import time
from collections import OrderedDict, deque
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import prettr as P
from repro.kernels.join_attention import pages_to_dense
from repro.index.store import TermRepIndex
from repro.serving import faults, spans
from repro.serving.spans import span

log = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# Typed API surface
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RankRequest:
    """One re-ranking query: tokens + candidate doc ids, with scheduling
    hints.  ``priority``: lower = scheduled earlier.  ``deadline_s``: per-
    micro-batch combine deadline driving the straggler policy (falls back
    to the service default)."""
    q_tokens: np.ndarray                  # [Lq] int tokens, padded
    q_valid: np.ndarray                   # [Lq] bool
    doc_ids: Sequence[int]
    request_id: str | None = None         # auto-assigned if None
    priority: int = 0
    deadline_s: float | None = None


@dataclasses.dataclass
class RerankStats:
    """Per-request clocks: ``query_encode_s``, the Table-5 Query phase
    (0 on a query-rep cache hit), and ``queue_wait_s``, from the
    request's enqueue to the start of staging of the first micro-batch
    that holds one of its rows (under a router, the longest of its shard
    tasks' waits).  Micro-batch phase times are per batch, shared by
    every request packed into it: they live on :class:`ServiceStats`."""
    query_encode_s: float = 0.0
    queue_wait_s: float = 0.0
    n_docs: int = 0
    n_redispatch: int = 0


@dataclasses.dataclass
class RankResponse:
    request_id: str
    doc_ids: list[int]                    # sorted by descending score
    scores: np.ndarray                    # [n] float32, same order
    stats: RerankStats
    latency_s: float = 0.0                # submit -> completion wall time
    #: degraded-response contract: when a fault could not be retried or
    #: failed over, the response still arrives — ``degraded=True``,
    #: ``failed_doc_ids`` lists the candidates whose scores are invalid
    #: (they carry ``-inf`` and sort to the bottom); every doc id NOT
    #: listed scored bit-exactly as in a fault-free run
    degraded: bool = False
    failed_doc_ids: list[int] = dataclasses.field(default_factory=list)


class ServiceOverloadError(RuntimeError):
    """``submit()`` shed this request: the admission queue is at the
    configured ``max_queue`` depth (counted in ``ServiceStats.n_shed``).
    Callers back off and resubmit; nothing was enqueued."""


#: ServiceStats fields that are per-engine *gauges* (a snapshot of one
#: worker's state, e.g. its doc-cache residency) — a router aggregating
#: workers takes their max, never their sum; the per-worker values stay
#: readable on ``RankingRouter.worker_stats``.
_STATS_GAUGE_FIELDS = frozenset({"resident_docs"})

#: ServiceStats fields that are *overlapped clocks*: shard workers drain
#: concurrently, so the aggregate wall is the slowest worker's, not the
#: sum of all of them.
_STATS_CONCURRENT_FIELDS = frozenset({"wall_s"})


@dataclasses.dataclass
class ServiceStats:
    """Aggregate scheduler counters across all drained batches.

    Instances are **mergeable** (:meth:`merge` / ``+``) so a router can
    aggregate its shard workers' counters without dropping any field:
    merge iterates ``dataclasses.fields``, so a counter added later (the
    way ``h2d_bytes``/``doc_hbm_bytes`` arrived) is summed automatically
    instead of silently vanishing from the aggregate.  Two exceptions are
    declared by name: gauges (``resident_docs``) merge as ``max`` and
    overlapped clocks (``wall_s``) merge as ``max`` because concurrent
    workers' walls overlap."""
    n_requests: int = 0
    n_batches: int = 0                    # accepted (non-redispatched) batches
    n_rows: int = 0                       # real candidate rows scored
    n_pad_rows: int = 0                   # shape-padding rows
    n_redispatch: int = 0
    n_join_dispatch: int = 0              # scoring jit entries issued
    n_decode_dispatch: int = 0            # standalone codec-decode dispatches
    n_doc_cache_hit: int = 0              # candidate rows served from device
    n_doc_cache_miss: int = 0             # candidate rows staged from disk
    h2d_bytes: int = 0                    # doc-side bytes shipped host->device
    doc_hbm_bytes: int = 0                # doc-side bytes the join reads from
                                          # device memory (analytic, per batch)
    resident_docs: int = 0                # doc-cache residency gauge (last)
    # fault-tolerance counters (all plain sums under merge): tasks
    # re-enqueued on their own worker after a failure; tasks re-gathered
    # through the router's full-index fallback engine; responses returned
    # with degraded=True; requests shed at admission (max_queue)
    n_retries: int = 0
    n_failovers: int = 0
    n_degraded: int = 0
    n_shed: int = 0
    query_encode_s: float = 0.0
    load_s: float = 0.0                   # staging, per accepted batch
    combine_s: float = 0.0                # scoring, per accepted batch
    # parts of load_s (prefetch thread): doc-cache planning, index gather
    plan_s: float = 0.0
    gather_s: float = 0.0
    # part of combine_s: scoring-step dispatch up to the scores' device_get
    dispatch_s: float = 0.0
    stage_wait_s: float = 0.0             # scoring thread waiting on staging
    # request waits in the row pool before their first staged batch
    queue_wait_s: float = 0.0
    n_queue_waits: int = 0
    discarded_s: float = 0.0              # time spent on overshooting attempts
    wall_s: float = 0.0                   # total time inside drain()

    @property
    def pack_fill(self) -> float:
        """Fraction of scored batch rows that were real candidates."""
        return self.n_rows / max(1, self.n_rows + self.n_pad_rows)

    @property
    def doc_cache_hit_rate(self) -> float:
        seen = self.n_doc_cache_hit + self.n_doc_cache_miss
        return self.n_doc_cache_hit / max(1, seen)

    def merge(self, other: "ServiceStats") -> "ServiceStats":
        """Field-complete aggregate of two stat blocks (e.g. two shard
        workers'): counters and phase clocks sum; gauges and overlapped
        walls take the max (see the class docstring)."""
        out = ServiceStats()
        for f in dataclasses.fields(ServiceStats):
            a, b = getattr(self, f.name), getattr(other, f.name)
            if f.name in _STATS_GAUGE_FIELDS | _STATS_CONCURRENT_FIELDS:
                setattr(out, f.name, max(a, b))
            else:
                setattr(out, f.name, a + b)
        return out

    def __add__(self, other):
        if not isinstance(other, ServiceStats):
            return NotImplemented
        return self.merge(other)

    def __radd__(self, other):
        if other == 0:                    # sum([...]) support
            return self.merge(ServiceStats())
        return NotImplemented


# ---------------------------------------------------------------------------
# Scheduler policy (pluggable)
# ---------------------------------------------------------------------------


class SchedulerPolicy:
    """Packing order + straggler policy.

    The default is the policy that used to live inline in ``Reranker``:
    FIFO admission (priority-, then arrival-ordered), and a per-batch
    deadline under which an overshooting micro-batch is split in half and
    re-dispatched (bounded depth) — on a real pod the halves re-route
    around a slow host; on CPU the mechanism is what's demonstrated.
    Subclass to change ordering (:meth:`admission_key`), the effective
    batch deadline (:meth:`batch_deadline`), or the split shape
    (:meth:`split`)."""

    #: lower bound (seconds) on a router's per-worker drain timeout —
    #: generous because a cold worker's first drain includes jit compiles;
    #: a deadline-carrying workload tightens the bound via
    #: :meth:`drain_timeout`, a stuck worker still gets caught
    drain_timeout_floor: float = 300.0

    def __init__(self, max_split_depth: int = 2):
        self.max_split_depth = max_split_depth

    def admission_key(self, state: "_ReqState"):
        return (state.priority, state.seq)

    def drain_timeout(self, deadlines: Sequence[float | None],
                      n_rows: int = 0) -> float:
        """Wall budget the router gives one worker's ``drain()`` before
        declaring it dead: generous (every row at its slowest deadline,
        8x slack for redispatch halves + staging), floored so a workload
        with no deadlines still cannot wedge the router forever."""
        ds = [d for d in deadlines if d is not None]
        if not ds:
            return self.drain_timeout_floor
        return max(self.drain_timeout_floor,
                   8.0 * max(ds) * max(1, n_rows))

    def batch_deadline(self, deadlines: Sequence[float | None]) -> float | None:
        """Effective deadline for a packed batch: the tightest row deadline."""
        ds = [d for d in deadlines if d is not None]
        return min(ds) if ds else None

    def should_redispatch(self, elapsed_s: float, deadline_s: float | None,
                          n_rows: int, depth: int) -> bool:
        return (deadline_s is not None and elapsed_s > deadline_s
                and n_rows > 1 and depth < self.max_split_depth)

    def split(self, rows: list) -> list[list]:
        mid = len(rows) // 2
        return [rows[:mid], rows[mid:]]


class DeadlinePriorityPolicy(SchedulerPolicy):
    """Order admission by (priority, tightest deadline, arrival) so urgent
    requests' rows land in the earliest packed batches."""

    def admission_key(self, state: "_ReqState"):
        d = state.deadline_s if state.deadline_s is not None else float("inf")
        return (state.priority, d, state.seq)


# ---------------------------------------------------------------------------
# Internal per-request / per-batch state
# ---------------------------------------------------------------------------


class _ReqState:
    __slots__ = ("req", "rid", "seq", "n", "priority", "deadline_s",
                 "q_reps", "q_valid_j", "scores", "n_done", "t_submit",
                 "t_enqueue", "stats", "failed_idx", "error")

    def __init__(self, req: RankRequest, rid: str, seq: int,
                 deadline_s: float | None):
        self.req = req
        self.rid = rid
        self.seq = seq
        self.n = len(req.doc_ids)
        self.priority = req.priority
        self.deadline_s = deadline_s
        self.q_reps = None                # [1, Lq, d] device array
        self.q_valid_j = None             # [Lq] device array
        self.scores = np.zeros(self.n, np.float32)
        self.n_done = 0
        self.t_submit = time.perf_counter()
        self.t_enqueue = None             # set by BatchEngine.enqueue
        self.stats = RerankStats(n_docs=self.n)
        self.failed_idx: list[int] = []   # candidate rows a fault invalidated
        self.error: BaseException | None = None


@dataclasses.dataclass
class _Plan:
    """One planned micro-batch: rows are (state | None, cand_idx, doc_id);
    ``state is None`` marks a shape-padding row (its score is discarded)."""
    rows: list
    depth: int = 0


@dataclasses.dataclass
class _StageClocks:
    """Host clocks of one staged micro-batch, taken on the prefetch thread
    and added to :class:`ServiceStats` on the scoring thread (no stats
    field is written from two threads)."""
    t_start: float
    plan_s: float = 0.0
    gather_s: float = 0.0
    load_s: float = 0.0


_STOP = object()


def encode_query_jit(cfg: P.PreTTRConfig):
    """The query-side encode (layers ``0..l``), jitted under a stable name:
    its program shows on the device trace as ``jit__encode_query``."""
    def _encode_query(p, t, v):
        return P.encode_query(p, cfg, t, v)

    return jax.jit(_encode_query)


# ---------------------------------------------------------------------------
# Index-vs-config compatibility (satellite: no silent truncation)
# ---------------------------------------------------------------------------


def validate_doc_routing(index, doc_ids) -> None:
    """Raise ValueError when any of ``doc_ids`` cannot be gathered from
    ``index``: out of the global id range, or — when ``index`` is a
    :class:`~repro.index.store.ShardIndexView` — routed to a serving shard
    that does not store the document.  Catching a misroute *here*, at
    admission, gives a clear shard-affinity message instead of the raw
    gather fault it would otherwise surface as deep in the prefetcher."""
    ids = np.asarray(list(doc_ids), np.int64).reshape(-1)
    if ids.size == 0:
        return
    if ids.min() < 0 or ids.max() >= len(index):
        raise ValueError(f"doc id out of range [0, {len(index)})")
    describe = getattr(index, "describe_misroute", None)
    if describe is not None:
        msg = describe(ids)
        if msg:
            raise ValueError(msg)


def validate_index_compat(cfg: P.PreTTRConfig, index: TermRepIndex,
                          doc_ids=None) -> None:
    """Raise ValueError when an opened index cannot be served under ``cfg``.

    ``load_docs(pad_to=cfg.max_doc_len)`` would otherwise silently truncate
    documents indexed under a larger ``max_doc_len``, and mismatched
    ``rep_dim`` / ``l`` / compression would produce garbage scores instead
    of an error.

    With ``doc_ids``, additionally validates that every id can actually be
    gathered from ``index`` — in range, and (for a serving-shard view)
    resident in that shard's slice of the doc table — via
    :func:`validate_doc_routing`."""
    if bool(index.compressed) != bool(cfg.compress_dim):
        raise ValueError(
            f"index compressed={bool(index.compressed)} but config "
            f"compress_dim={cfg.compress_dim} — reps would be "
            f"(de)compressed with the wrong path")
    e = cfg.compress_dim or cfg.backbone.d_model
    if index.rep_dim != e:
        raise ValueError(
            f"index rep_dim={index.rep_dim} does not match the config's "
            f"stored-rep width {e} (compress_dim or d_model)")
    if index.l != cfg.l:
        raise ValueError(
            f"index was precomputed through l={index.l} layers but the "
            f"config joins at l={cfg.l}; re-index or change the config")
    if getattr(index, "has_layer_kv", False):
        want = cfg.backbone.n_kv_heads * cfg.backbone.dh
        if index.kv_dim != want:
            raise ValueError(
                f"index stores layer-l K/V streams of width "
                f"{index.kv_dim} but the config's K/V width is {want} "
                f"(n_kv_heads * head_dim); re-index or change the config")
    # indexes built without an explicit max_doc_len record 0 — fall back to
    # the longest stored document so truncation still cannot slip through
    lengths = index.doc_lengths
    idx_max = index.max_doc_len or (int(lengths.max()) if len(lengths) else 0)
    if idx_max > cfg.max_doc_len:
        raise ValueError(
            f"index max_doc_len={idx_max} exceeds config "
            f"max_doc_len={cfg.max_doc_len}: serving would silently "
            f"truncate stored documents")
    if doc_ids is not None:
        validate_doc_routing(index, doc_ids)


# ---------------------------------------------------------------------------
# The scheduler/packer/scorer core
# ---------------------------------------------------------------------------


def _pool_reps(codec, rep_streams, pools, pt):
    """A batch's document reps, gathered from the doc cache's pools
    through the page table and decoded (inside the caller's jit)."""
    if codec.decode_is_identity:
        return pages_to_dense(pools["reps"], pt)
    return codec.decode_group(
        "reps", {s: pages_to_dense(pools[s], pt) for s in rep_streams})


def pool_score_fn(cfg: P.PreTTRConfig, codec, rep_streams, kv_quant=None,
                  *, fused: bool = True) -> Callable:
    """The pallas backend's pool-fused scoring step, ``_pool_score(p, qr,
    qv, pools, vpool, pt)``: the doc cache's layer-``l`` K/V (and, for an
    int8 ``kv_quant`` codec, scale) pools go to the paged join kernel as
    they lie, in a :class:`PagedDocKV` whose page table the kernel walks;
    only the reps and the validity are gathered.  Built from the config
    and the codecs alone, so it can be lowered from shapes."""
    def _pool_score(p, qr, qv, pools, vpool, pt):
        dval = pages_to_dense(vpool, pt).astype(bool)
        x_d = _pool_reps(codec, rep_streams, pools, pt)
        scales = ((None, None) if kv_quant is None else
                  tuple(pools[kv_quant.scale_stream(g)]
                        for g in ("layer_k", "layer_v")))
        dkv = P.PagedDocKV(k=pools["layer_k"], v=pools["layer_v"],
                           valid=vpool, page_table=pt,
                           k_scale=scales[0], v_scale=scales[1])
        return P.join_and_score(p, cfg, qr, qv, x_d, dval, doc_kv=dkv,
                                fused=fused)
    return _pool_score


class BatchEngine:
    """The reusable micro-batch scheduler/packer/scorer.

    One engine owns: the packing queue and straggler re-dispatch, the
    prefetch pipeline (index ``gather_raw`` + H2D overlap), the doc-side
    scoring jits (raw-stream / pool-fused), the optional paged device doc
    cache, and one :class:`ServiceStats` block.  It knows nothing about
    requests or query encoding — callers enqueue *states* and drain
    completed ones back:

    * :class:`RankingService` composes one engine with its admission /
      query-rep-LRU side (the classic single-process service);
    * :class:`repro.serving.sharded.ShardWorker` composes one engine per
      index-shard view, pinned to its own device, with the query reps
      handed over (already device-resident) by the router.

    A *state* is any object with the ``_ReqState`` row contract:
    ``q_reps`` ([1, Lq, d] on this engine's device), ``q_valid_j``
    ([Lq]), ``priority`` / ``seq`` / ``deadline_s`` (scheduling),
    ``scores`` (np [n] float32), ``n`` / ``n_done`` (completion),
    ``t_enqueue`` (set here at :meth:`enqueue`; cleared once the state's
    queue wait is counted) and ``stats`` (:class:`RerankStats`).

    ``device`` pins the engine to one device of the serving mesh: params
    are copied there once, every staged array is ``device_put`` there, and
    the jits follow their (committed) inputs — so N engines on N devices
    score concurrently without any cross-device traffic.  ``None`` keeps
    jax's default placement (single-process behaviour, bit-identical to
    the pre-engine ``RankingService``).
    """

    def __init__(self, params, cfg: P.PreTTRConfig, index, *,
                 micro_batch: int = 32, policy: SchedulerPolicy | None = None,
                 prefetch_depth: int = 2, fused: bool = True,
                 use_layer_kv: bool | None = None,
                 join_fn: Callable | None = None,
                 doc_cache_mb: float = 0.0,
                 page_tokens: int | None = None,
                 page_bucket: bool = False,
                 device=None,
                 fault_tag=None):
        self.cfg = cfg
        self.index = index
        self.micro_batch = micro_batch
        # identifies this engine at the fault-injection sites (a shard id
        # for ShardWorker engines, "fallback" for the router's fallback
        # engine, None for the single-process service)
        self.fault_tag = fault_tag
        self.policy = policy or SchedulerPolicy()
        self.prefetch_depth = max(0, prefetch_depth)
        self.device = device
        self.params = (jax.device_put(params, device)
                       if device is not None else params)
        self.stats = ServiceStats()

        self.fused = bool(fused)
        has_kv = bool(getattr(index, "has_layer_kv", False))
        if use_layer_kv is None:
            # stored K/V only plug into the fused path, and an injected
            # join_fn (the Reranker shim) has the 5-arg signature
            use_layer_kv = has_kv and self.fused and join_fn is None
        if use_layer_kv and not has_kv:
            raise ValueError(
                "use_layer_kv=True but the index has no layer_k/layer_v "
                "streams; rebuild it with IndexBuilder(store_layer_kv=True)")
        if use_layer_kv and not self.fused:
            raise ValueError(
                "stored layer-l K/V streams require the fused join path "
                "(fused=True)")
        self.use_layer_kv = bool(use_layer_kv)

        self._join = join_fn or jax.jit(
            lambda p, qr, qv, st, dv: P.join_and_score(p, cfg, qr, qv, st,
                                                       dv, fused=fused))
        # codec-aware staging: quantizing codecs (int8) ship their narrow
        # raw streams over H2D and decode *inside* the scoring jit (for
        # int8 layer-K/V, in-register inside the join kernel) — the
        # standalone decode dispatch only survives for injected join_fn
        # test doubles; identity codecs (fp16/fp32) feed stored bytes
        # straight through either way
        codec = getattr(index, "codec", None)
        kv_codec = getattr(index, "kv_codec", None)
        self._kv_quant = (self.use_layer_kv and kv_codec is not None
                          and not kv_codec.decode_is_identity)
        self._decode = None
        if (codec is not None and not codec.decode_is_identity
                and join_fn is not None):
            self._decode = jax.jit(codec.decode)
        self._join_raw = None
        if (join_fn is None and codec is not None
                and getattr(index, "gather_raw", None) is not None):
            use_kv, kvq = self.use_layer_kv, self._kv_quant

            def _raw_score(p, qr, qv, parts, dv):
                x_d = (parts["reps"] if codec.decode_is_identity
                       else codec.decode_group("reps", parts))
                dkv = None
                if use_kv:
                    dkv = ((parts["layer_k"], parts["layer_v"],
                            parts[kv_codec.scale_stream("layer_k")],
                            parts[kv_codec.scale_stream("layer_v")])
                           if kvq else
                           (parts["layer_k"], parts["layer_v"]))
                return P.join_and_score(p, cfg, qr, qv, x_d, dv,
                                        doc_kv=dkv, fused=fused)

            self._join_raw = jax.jit(_raw_score)
        # stream subset to stage: skip the (large) K/V streams of an index
        # that has them when this service doesn't consume them
        self._gather_streams = None
        if has_kv and not self.use_layer_kv and codec is not None:
            self._gather_streams = list(codec.streams(index.rep_dim))
        lens = getattr(index, "doc_lengths", None)
        self._doc_lens = np.asarray(lens) if lens is not None else None

        self._doc_cache = None
        if doc_cache_mb and doc_cache_mb > 0:
            if join_fn is not None:
                raise ValueError(
                    "doc_cache_mb scores through a pool-fused jit of the "
                    "model's join_and_score; an injected join_fn would be "
                    "silently bypassed — disable the doc cache or drop "
                    "join_fn")
            if getattr(index, "gather_raw", None) is None or codec is None:
                raise ValueError(
                    "doc_cache_mb needs a codec-aware TermRepIndex "
                    "(gather_raw); this index stand-in has none")
            from repro.serving.doc_cache import DeviceDocCache
            # the cache pools hold the index's *raw stored bytes* (int8
            # payload + scales for quantizing codecs) — decode happens
            # inside the pool-fused scoring jit, so an int8 index keeps
            # ~4x more docs resident per MiB than decoded-float pools
            spec = dict(codec.streams(index.rep_dim))
            if self.use_layer_kv:
                kvs = getattr(index, "kv_streams_spec", None)
                spec.update(kvs() if kvs else {
                    "layer_k": (np.dtype(index.layer_kv["dtype"]),
                                (index.kv_dim,)),
                    "layer_v": (np.dtype(index.layer_kv["dtype"]),
                                (index.kv_dim,))})
            self._cache_streams = list(spec)
            cache = self._doc_cache = DeviceDocCache(
                int(doc_cache_mb * 2**20), doc_len=cfg.max_doc_len,
                streams=spec, page_tokens=page_tokens,
                page_bucket=page_bucket, min_slots=2 * self.micro_batch,
                device=device)
            # pool-fused scoring, one `_join_pool` call per micro-batch and
            # zero per-document work.  On the pallas backend that call is a
            # single jit: the layer-l K/V pools go in as a PagedDocKV and
            # the kernel's index maps walk the page table, so no dense KV
            # copy is ever materialized.  On the reference backends
            # (plain/blocked) the call is two fused device dispatches —
            # a page-table *assemble* jit (gather + reps decode) feeding a
            # dense *score* jit.  Keeping them in one jit looks tidier but
            # is ~2.3x slower: XLA refuses to materialize the page gathers
            # and instead fuses a re-gather into every attention consumer.
            # The raw int8 K/V bytes + scales pass through the seam
            # undecoded, so dequantization still happens inside the scoring
            # jit and `stats.n_decode_dispatch` stays 0.
            use_kv, kvq = self.use_layer_kv, self._kv_quant
            rep_streams = list(codec.streams(index.rep_dim))

            def _pool_assemble(pools, vpool, pt):
                dval = pages_to_dense(vpool, pt).astype(bool)
                x_d = _pool_reps(codec, rep_streams, pools, pt)
                dkv = None
                if use_kv:
                    names = ["layer_k", "layer_v"]
                    if kvq:
                        names += [kv_codec.scale_stream("layer_k"),
                                  kv_codec.scale_stream("layer_v")]
                    dkv = tuple(pages_to_dense(pools[n], pt) for n in names)
                return x_d, dval, dkv

            def _dense_score(p, qr, qv, x_d, dval, dkv):
                return P.join_and_score(p, cfg, qr, qv, x_d, dval,
                                        doc_kv=dkv, fused=fused)

            attn_impl = getattr(getattr(cfg, "backbone", cfg), "attn_impl",
                                "plain")
            if use_kv and attn_impl == "pallas":
                self._join_pool = jax.jit(pool_score_fn(
                    cfg, codec, rep_streams,
                    kv_codec if kvq else None, fused=fused))
            else:
                assemble = jax.jit(_pool_assemble)
                score = jax.jit(_dense_score)

                def _pool_call(p, qr, qv, pools, vpool, pt):
                    x_d, dval, dkv = assemble(pools, vpool, pt)
                    return score(p, qr, qv, x_d, dval, dkv)

                self._join_pool = _pool_call

        self._waiting: list[_ReqState] = []     # enqueued, not yet planned
        self._rows: deque = deque()             # planned row pool
        self._replans: deque = deque()          # straggler re-dispatch plans

    @property
    def doc_cache(self):
        """The device-resident hot-doc cache (None when disabled)."""
        return self._doc_cache

    @property
    def pending(self) -> bool:
        return bool(self._waiting or self._rows or self._replans)

    def enqueue(self, state) -> None:
        """Admit a state's candidate rows into the next drain's packing
        pool (ordering applied at drain time via the policy)."""
        state.t_enqueue = time.perf_counter()
        self._waiting.append(state)

    # -- scheduling ----------------------------------------------------------
    def _admit_waiting(self):
        for state in sorted(self._waiting, key=self.policy.admission_key):
            for ci, d in enumerate(state.req.doc_ids):
                self._rows.append((state, ci, int(d)))
        self._waiting.clear()

    def _next_plan(self) -> _Plan | None:
        if self._replans:
            return self._replans.popleft()
        if not self._rows:
            return None
        rows = [self._rows.popleft()
                for _ in range(min(self.micro_batch, len(self._rows)))]
        return self._padded_plan(rows)

    def _padded_plan(self, rows: list, depth: int = 0) -> _Plan:
        """Pad real rows to the fixed micro-batch shape: every batch,
        redispatched halves included, runs the one compiled program, and
        XLA's output differs at the ulp across batch shapes (not row
        positions), so a row scores the same bits in any batch.  Padding
        replicates the last real row; its scores are discarded."""
        pad = (None, -1, rows[-1][2])
        return _Plan(rows=rows + [pad] * (self.micro_batch - len(rows)),
                     depth=depth)

    def _stage(self, plan: _Plan):
        """Host-side staging of one planned batch: index gather (the
        codec's raw streams — for int8 the narrow encoded payload, decoded
        on device), H2D copy, and per-row query-rep batch assembly (padding
        rows replicate the last real row; their scores are discarded).

        With the hot-doc cache enabled, only the *misses* are gathered and
        shipped (bucket-padded so the decode/insert jits see O(log B)
        shapes); hit rows are just slot numbers into the device pool.
        -> (qr, qv, payload, clocks).  ``clocks.load_s`` stops only after
        ``block_until_ready`` on everything staged — ``device_put`` is
        async, and an unblocked timestamp silently books the H2D copy
        under the next combine phase."""
        clocks = _StageClocks(t_start=time.perf_counter())
        faults.hit("engine.stage", tag=self.fault_tag)
        faults.hit("index.gather", tag=self.fault_tag, index=self.index,
                   doc_ids=[r[2] for r in plan.rows])
        if self._doc_cache is not None:
            payload = self._stage_cached(plan, clocks)
        else:
            gather_raw = getattr(self.index, "gather_raw", None)
            with span(spans.GATHER) as sp:
                if gather_raw is not None:
                    parts, dvalid = gather_raw(
                        [r[2] for r in plan.rows],
                        pad_to=self.cfg.max_doc_len,
                        streams=self._gather_streams)
                else:                      # index stand-ins without codecs
                    reps, dvalid = self.index.gather(
                        [r[2] for r in plan.rows],
                        pad_to=self.cfg.max_doc_len)
                    parts = {"reps": reps}
            clocks.gather_s = sp.s
            h2d = sum(np.asarray(a).nbytes for a in parts.values())
            payload = {"parts": parts, "valid": dvalid,
                       "h2d_bytes": h2d + np.asarray(dvalid).nbytes}
        with span(spans.SHIP):
            for k in ("parts", "valid", "miss_parts"):
                if payload.get(k) is not None:
                    payload[k] = jax.device_put(payload[k], self.device)
            last = next(s for s, _, _ in reversed(plan.rows)
                        if s is not None)
            qr = jnp.concatenate(
                [(s or last).q_reps for s, _, _ in plan.rows], axis=0)
            qv = jnp.stack([(s or last).q_valid_j for s, _, _ in plan.rows])
            jax.block_until_ready((qr, qv, payload))
        clocks.load_s = time.perf_counter() - clocks.t_start
        return qr, qv, payload, clocks

    def _stage_cached(self, plan: _Plan, clocks: _StageClocks):
        """Cache-aware staging: plan pages (LRU bump + miss admission) and
        gather only the miss rows, staged at the planned page-table width
        so they scatter straight into the page pools (``_stage`` ships
        them)."""
        cache = self._doc_cache
        with span(spans.PLAN) as sp:
            ids = [r[2] for r in plan.rows]
            # hit/miss accounting over *real* candidate rows only — the
            # micro-batch shape pads (state None, always trailing) would
            # otherwise skew the hit rates (pack_fill already excludes them)
            real_ids = [d for s, _, d in plan.rows if s is not None]
            lens = self._doc_lens[ids] if self._doc_lens is not None else None
            page_table, miss_ids, miss_pages = cache.plan(
                ids, lengths=lens, n_real=len(real_ids))
            fresh = set(miss_ids)
            n_miss_rows = sum(1 for d in real_ids if d in fresh)
            payload = {"page_table": page_table, "miss_pages": None,
                       "miss_parts": None, "miss_valid": None,
                       "h2d_bytes": 0, "n_miss_rows": n_miss_rows,
                       "n_rows": len(real_ids)}
            if miss_ids:
                bucket = cache.bucket(len(miss_ids), self.micro_batch)
                pad = bucket - len(miss_ids)
                padded_ids = miss_ids + [miss_ids[-1]] * pad
                pages = (np.concatenate([miss_pages,
                                         np.repeat(miss_pages[-1:], pad, 0)])
                         if pad else miss_pages)
        clocks.plan_s = sp.s
        if miss_ids:
            with span(spans.GATHER) as sp:
                parts, valid = self.index.gather_raw(
                    padded_ids, pad_to=pages.shape[1] * cache.page_tokens,
                    streams=self._cache_streams)
            clocks.gather_s = sp.s
            payload["miss_pages"] = pages
            payload["h2d_bytes"] = (
                sum(np.asarray(a).nbytes for a in parts.values())
                + np.asarray(valid).nbytes)
            payload["miss_parts"] = parts
            payload["miss_valid"] = valid
        return payload

    def _prefetch_loop(self, in_q: queue.Queue, out_q: queue.Queue):
        """Prefetch thread: stage the next planned batches while the device
        scores the current one."""
        while True:
            plan = in_q.get()
            if plan is _STOP:
                return
            try:
                out_q.put((plan, *self._stage(plan), None))
            except Exception as e:                    # noqa: BLE001
                out_q.put((plan, None, None, None, None, e))

    def drain(self) -> list:
        """Run the scheduler until every enqueued state is fully scored.
        Returns the *completed states* in completion order (the composer
        turns them into responses)."""
        with span(spans.DRAIN) as sp:
            done = self._drain()
        self.stats.wall_s += sp.s
        return done

    def _drain(self) -> list:
        done: list = []
        self._admit_waiting()
        if not self._rows and not self._replans:
            return done
        if self.prefetch_depth == 0:
            # synchronous debug path: no prefetch thread, stage + score
            # each batch inline
            while True:
                plan = self._next_plan()
                if plan is None:
                    break
                try:
                    staged = self._stage(plan)
                    self._score_plan(plan, *staged, done)
                except Exception as e:                # noqa: BLE001
                    self._fail_plan(plan, e, done)
            return done

        in_q: queue.Queue = queue.Queue()
        out_q: queue.Queue = queue.Queue(maxsize=self.prefetch_depth)
        worker = threading.Thread(
            target=self._prefetch_loop, args=(in_q, out_q), daemon=True)
        worker.start()
        inflight = 0
        try:
            while True:
                while inflight < self.prefetch_depth:
                    plan = self._next_plan()
                    if plan is None:
                        break
                    in_q.put(plan)
                    inflight += 1
                if inflight == 0:
                    break
                with span(spans.STAGE_WAIT) as sp:
                    plan, qr, qv, payload, clocks, err = out_q.get()
                self.stats.stage_wait_s += sp.s
                inflight -= 1
                if err is not None:
                    # fault isolation: a staging error (bad gather, H2D
                    # fault, injected) used to raise out of drain() and
                    # abandon every co-packed in-flight state — fail only
                    # this plan's rows and keep draining the rest
                    self._fail_plan(plan, err, done)
                    continue
                try:
                    self._score_plan(plan, qr, qv, payload, clocks, done)
                except Exception as e:                # noqa: BLE001
                    self._fail_plan(plan, e, done)
        finally:
            in_q.put(_STOP)
            # unblock a worker stuck on a full out_q before joining
            while worker.is_alive():
                try:
                    out_q.get_nowait()
                except queue.Empty:
                    pass
                worker.join(timeout=0.05)
        return done

    def _fail_plan(self, plan: _Plan, err: BaseException, done: list) -> None:
        """Resolve an errored plan's real rows as *failed*: the row index
        lands on its state's ``failed_idx`` (the composer flags the
        response degraded), the score is ``-inf`` (sorts to the bottom),
        and the state still completes — no co-packed state is lost.  The
        error is logged with its traceback: a failed batch is never
        silent, even where the degraded response is the right answer."""
        log.warning("micro-batch of %d rows failed (engine %s)",
                    sum(s is not None for s, _, _ in plan.rows),
                    self.fault_tag, exc_info=err)
        for s, ci, _ in plan.rows:
            if s is None:
                continue
            s.failed_idx.append(ci)
            s.error = err
            s.scores[ci] = -np.inf
            s.n_done += 1
            if s.n_done == s.n:
                done.append(s)

    def abandon_pending(self) -> list:
        """Drop every enqueued-but-unfinished state (a router failing this
        engine over re-runs them elsewhere).  Returns the distinct states
        whose rows were dropped; their scores/counters are untouched."""
        states: dict[int, object] = {}
        for s in self._waiting:
            states[id(s)] = s
        for rows in (self._rows,
                     [r for p in self._replans for r in p.rows]):
            for s, _, _ in rows:
                if s is not None:
                    states[id(s)] = s
        self._waiting.clear()
        self._rows.clear()
        self._replans.clear()
        return list(states.values())

    # -- device step ---------------------------------------------------------
    def _score_batch(self, qr, qv, payload):
        """Assemble the doc-side operands and issue exactly one pool-score
        call (a fixed number of fused device dispatches, never per-doc).
        Cache mode: insert staged misses into the device pool, then
        gather every row from it (hit and miss rows take the identical
        compute path, so scores are bit-equal either way)."""
        faults.hit("engine.score", tag=self.fault_tag)
        self.stats.h2d_bytes += payload.get("h2d_bytes", 0)
        if self._doc_cache is not None:
            cache = self._doc_cache
            mp = payload["miss_parts"]
            if mp is not None:
                with span(spans.INSERT):
                    cache.insert(payload["miss_pages"], mp,
                                 payload["miss_valid"])
            self.stats.n_doc_cache_miss += payload["n_miss_rows"]
            self.stats.n_doc_cache_hit += (payload["n_rows"]
                                           - payload["n_miss_rows"])
            self.stats.resident_docs = cache.resident_docs
            pt = cache.put(payload["page_table"])
            # doc-side bytes the join pulls from device memory: one page
            # gather per page-table entry (validity byte included)
            self.stats.doc_hbm_bytes += (payload["page_table"].size
                                         * cache.page_bytes)
            self.stats.n_join_dispatch += 1
            return self._join_pool(self.params, qr, qv, cache.pools,
                                   cache.valid_pool, pt)
        dparts, dval = payload["parts"], payload["valid"]
        self.stats.doc_hbm_bytes += payload.get("h2d_bytes", 0)
        if self._join_raw is not None:
            # raw-stream scoring jit: codec decode (reps and, for an int8
            # KV index, the in-kernel K/V dequant) happens inside the one
            # dispatch — n_decode_dispatch stays 0 on this path
            self.stats.n_join_dispatch += 1
            return self._join_raw(self.params, qr, qv, dparts, dval)
        if self._decode:                   # injected join_fn test doubles
            st = self._decode(dparts)
            self.stats.n_decode_dispatch += 1
        else:
            st = dparts["reps"]
        self.stats.n_join_dispatch += 1
        return self._join(self.params, qr, qv, st, dval)

    def _score_plan(self, plan: _Plan, qr, qv, payload,
                    clocks: _StageClocks, done: list):
        rows = plan.rows
        states = [s for s, _, _ in rows if s is not None]
        uniq = {id(s): s for s in states}
        for s in uniq.values():
            if s.t_enqueue is not None:   # the state's first staged batch
                wait = clocks.t_start - s.t_enqueue
                s.t_enqueue = None
                s.stats.queue_wait_s = wait
                self.stats.queue_wait_s += wait
                self.stats.n_queue_waits += 1
        with span(spans.DISPATCH) as sp_dispatch:
            out = self._score_batch(qr, qv, payload)
        with span(spans.DEVICE_GET) as sp_get:
            scores = np.asarray(jax.device_get(out))
        dt = sp_dispatch.s + sp_get.s
        load_dt = clocks.load_s

        deadline = self.policy.batch_deadline(
            [s.deadline_s for s in uniq.values()])
        if self.policy.should_redispatch(dt, deadline, len(states),
                                         plan.depth):
            # the overshooting attempt's scores are discarded — only the
            # re-dispatched halves (whose results are returned) may count
            # toward the Table-5 split
            self.stats.n_redispatch += 1
            self.stats.discarded_s += dt + load_dt
            for s in uniq.values():
                s.stats.n_redispatch += 1
            real = [r for r in rows if r[0] is not None]
            halves = [self._padded_plan(h, plan.depth + 1)
                      for h in self.policy.split(real) if h]
            self._replans.extendleft(reversed(halves))
            return

        n_real = len(states)
        self.stats.n_batches += 1
        self.stats.n_rows += n_real
        self.stats.n_pad_rows += len(rows) - n_real
        self.stats.load_s += load_dt
        self.stats.plan_s += clocks.plan_s
        self.stats.gather_s += clocks.gather_s
        self.stats.combine_s += dt
        self.stats.dispatch_s += sp_dispatch.s
        for i, (s, ci, _) in enumerate(rows):
            if s is None:
                continue
            s.scores[ci] = scores[i]
            s.n_done += 1
            if s.n_done == s.n:
                done.append(s)


# ---------------------------------------------------------------------------
# The service
# ---------------------------------------------------------------------------


class RankingService:
    """Request/response re-ranking service over a :class:`TermRepIndex`.

    Usage::

        svc = RankingService(params, cfg, index, micro_batch=32)
        rid = svc.submit(RankRequest(q_tokens, q_valid, doc_ids))
        for resp in svc.drain():          # processes everything queued
            ...
        # or, single query: svc.rank(q_tokens, q_valid, doc_ids)

    ``drain`` runs the scheduler: candidate rows from every queued request
    are packed into fixed ``micro_batch``-row batches (cross-query), the
    prefetch thread stages each planned batch's index blocks + H2D copy
    while the device scores the previous one, and the ``policy`` handles
    ordering and deadline-triggered re-dispatch.  The packing / staging /
    scoring core is a :class:`BatchEngine`; this class adds admission, the
    query-rep LRU, and response assembly.

    ``prefetch_depth`` bounds the staged-batch pipeline (``0`` disables the
    prefetch thread entirely: synchronous inline staging, for debugging).
    ``backend`` routes all compute through ``repro.models.backend`` (e.g.
    ``"pallas"`` for the flash/fused kernels) exactly as on ``Reranker``.
    ``encode_fn`` / ``join_fn`` override the jitted model entry points
    (used by the ``Reranker`` shim so patched-in test doubles stay live).

    ``fused`` selects the join execution path (default: the fused
    split-KV path; ``False`` = legacy concat).  ``use_layer_kv`` consumes
    the index's stored layer-``l`` doc K/V streams in the join (default:
    automatically on when the index has them and the fused path is
    active); streams stored with ``kv_codec="int8"`` stay raw int8 all
    the way into the join kernel, which dequantizes them in-register —
    no standalone decode dispatch exists on any path
    (``stats.n_decode_dispatch`` stays 0).  ``doc_cache_mb`` > 0 enables
    the **paged device-resident hot-doc cache**
    (``repro.serving.doc_cache``): the raw codec streams live in token-
    page pools on device, cache-hit candidates skip index ``gather()``
    and the H2D copy entirely, the prefetcher stages only misses, and
    batch assembly is a page-table gather *inside* the scoring jit —
    scores are bit-identical hit-vs-miss because every row is assembled
    from the same stored bytes.  ``page_tokens`` sets the page size
    (default: whole-doc slots); ``page_bucket=True`` additionally shrinks
    each batch's page-table width to its longest doc (bucketed powers of
    two — fewer gathered bytes, a few extra jit shapes).
    """

    def __init__(self, params, cfg: P.PreTTRConfig, index: TermRepIndex, *,
                 micro_batch: int = 32, policy: SchedulerPolicy | None = None,
                 cache_size: int = 64, backend: str | None = None,
                 prefetch_depth: int = 2, deadline_s: float | None = None,
                 encode_fn: Callable | None = None,
                 join_fn: Callable | None = None,
                 validate_index: bool = True, fused: bool = True,
                 use_layer_kv: bool | None = None,
                 doc_cache_mb: float = 0.0,
                 page_tokens: int | None = None,
                 page_bucket: bool = False,
                 device=None,
                 max_queue: int | None = None):
        if backend is not None:
            from repro.models.backend import apply_backend
            cfg = apply_backend(cfg, backend)
        if validate_index:
            validate_index_compat(cfg, index)
        self.cfg = cfg
        self.index = index
        self.default_deadline_s = deadline_s
        # bounded admission: submit() sheds (ServiceOverloadError) once
        # this many requests are queued for the next drain; None = unbounded
        self.max_queue = max_queue
        self._queued = 0
        self.engine = BatchEngine(
            params, cfg, index, micro_batch=micro_batch, policy=policy,
            prefetch_depth=prefetch_depth, fused=fused,
            use_layer_kv=use_layer_kv, join_fn=join_fn,
            doc_cache_mb=doc_cache_mb, page_tokens=page_tokens,
            page_bucket=page_bucket, device=device)
        self._encode = encode_fn or encode_query_jit(cfg)
        self._qcache: OrderedDict = OrderedDict()
        self._cache_size = cache_size
        self._seq = 0
        self._done_early: list[RankResponse] = []   # empty-candidate requests

    # -- engine proxies (back-compat attribute surface) -----------------------
    @property
    def params(self):
        return self.engine.params

    @params.setter
    def params(self, value):
        self.engine.params = value

    @property
    def micro_batch(self):
        return self.engine.micro_batch

    @micro_batch.setter
    def micro_batch(self, value):
        self.engine.micro_batch = value

    @property
    def policy(self):
        return self.engine.policy

    @policy.setter
    def policy(self, value):
        self.engine.policy = value

    @property
    def prefetch_depth(self):
        return self.engine.prefetch_depth

    @property
    def fused(self):
        return self.engine.fused

    @property
    def use_layer_kv(self):
        return self.engine.use_layer_kv

    @property
    def stats(self) -> ServiceStats:
        return self.engine.stats

    def reset_stats(self) -> None:
        """Zero the aggregate counters (e.g. after a jit-warmup request)."""
        self.engine.stats = ServiceStats()

    @property
    def doc_cache(self):
        """The device-resident hot-doc cache (None when disabled)."""
        return self.engine.doc_cache

    @property
    def _join(self):
        return self.engine._join

    @_join.setter
    def _join(self, fn):
        self.engine._join = fn

    @property
    def _join_raw(self):
        return self.engine._join_raw

    @_join_raw.setter
    def _join_raw(self, fn):
        self.engine._join_raw = fn

    @property
    def _join_pool(self):
        return self.engine._join_pool

    @_join_pool.setter
    def _join_pool(self, fn):
        self.engine._join_pool = fn

    @property
    def _decode(self):
        return self.engine._decode

    @_decode.setter
    def _decode(self, fn):
        self.engine._decode = fn

    # -- admission -----------------------------------------------------------
    def submit(self, req: RankRequest) -> str:
        """Queue a request; returns its request id.  The query is encoded
        (or fetched from the query-rep LRU cache) at admission time."""
        rid = req.request_id or f"req-{self._seq}"
        if self.max_queue is not None and self._queued >= self.max_queue:
            self.stats.n_shed += 1
            raise ServiceOverloadError(
                f"request {rid} shed: {self._queued} requests already "
                f"queued (max_queue={self.max_queue}); drain() or back off")
        if len(req.doc_ids):
            try:
                # reject at admission: a bad id surfacing later, inside the
                # prefetcher, would abort drain() and lose every co-packed
                # request's response
                validate_doc_routing(self.index, req.doc_ids)
            except ValueError as e:
                raise ValueError(f"request {rid}: {e}") from None
        state = _ReqState(req, rid, self._seq,
                          req.deadline_s if req.deadline_s is not None
                          else self.default_deadline_s)
        self._seq += 1
        self.stats.n_requests += 1
        if state.n == 0:                   # nothing to rank; respond now
            self._done_early.append(RankResponse(
                request_id=rid, doc_ids=[],
                scores=np.zeros((0,), np.float32), stats=state.stats,
                latency_s=0.0))
            return rid
        with span(spans.ENCODE) as sp:
            state.q_reps = self._query_reps(np.asarray(req.q_tokens),
                                            np.asarray(req.q_valid))
        state.stats.query_encode_s = sp.s
        self.stats.query_encode_s += sp.s
        state.q_valid_j = jax.device_put(np.asarray(req.q_valid),
                                         self.engine.device)
        self.engine.enqueue(state)
        self._queued += 1
        return rid

    def rank(self, q_tokens, q_valid, doc_ids, *, priority: int = 0,
             deadline_s: float | None = None,
             request_id: str | None = None) -> RankResponse:
        """Synchronous single-query convenience: submit + drain.  Note this
        drains *every* queued request (other requests' responses are
        buffered and returned by the next ``drain()``); concurrent traffic
        should use ``submit``/``drain`` directly."""
        rid = self.submit(RankRequest(q_tokens, q_valid, list(doc_ids),
                                      request_id=request_id,
                                      priority=priority,
                                      deadline_s=deadline_s))
        out = None
        for resp in self.drain():
            if resp.request_id == rid:
                out = resp
            else:                 # other callers' responses stay claimable
                self._done_early.append(resp)
        assert out is not None
        return out

    # -- query side ----------------------------------------------------------
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

    def drain(self) -> list[RankResponse]:
        """Run the scheduler until every queued request has a response.
        Returns responses in completion order."""
        done: list[RankResponse] = list(self._done_early)
        self._done_early.clear()
        done += [self._finalize(s) for s in self.engine.drain()]
        self._queued = 0
        return done

    def _finalize(self, state: _ReqState) -> RankResponse:
        with span(spans.FINALIZE):
            order = np.argsort(-state.scores)
            ids = list(state.req.doc_ids)
            failed = sorted(set(state.failed_idx))
            if failed:
                self.stats.n_degraded += 1
            return RankResponse(
                request_id=state.rid,
                doc_ids=[ids[i] for i in order],
                scores=state.scores[order],
                stats=state.stats,
                latency_s=time.perf_counter() - state.t_submit,
                degraded=bool(failed),
                failed_doc_ids=[ids[i] for i in failed])
