"""Named host spans of the serving path.

``span(name)`` is a context manager that does two things at once: it
opens a ``jax.profiler.TraceAnnotation`` of that name, so that under a
running profiler the span lands on the trace's host plane on the same
clock as the device's ops, and it times its body on the host, leaving
the seconds on ``.s`` for the caller to add to a
:class:`~repro.serving.service.ServiceStats` field.  There is no switch:
with no profiler running an annotation costs well under a microsecond.

A span never synchronises with the device itself: each one sits around
work the serving path already does (a ``block_until_ready``, a
``device_get``), so wrapping code in spans leaves the number of blocking
device calls unchanged.

Every span name is listed here, under the one ``prettr.`` prefix.
"""
from __future__ import annotations

import time

import jax

PREFIX = "prettr."

#: query encode through layers ``0..l`` at admission (``submit``),
#: blocked until the reps are ready -> ``query_encode_s``
ENCODE = PREFIX + "encode"
#: doc-cache planning: LRU walk, page allocation, page-table build
#: (prefetch thread) -> ``plan_s``
PLAN = PREFIX + "plan"
#: index ``gather_raw`` of the rows (or misses) to ship (prefetch
#: thread) -> ``gather_s``
GATHER = PREFIX + "gather"
#: the rest of staging: ``device_put``, per-row q-rep assembly and the
#: final ``block_until_ready`` (prefetch thread) -> part of ``load_s``
SHIP = PREFIX + "ship"
#: the scoring thread blocked on the next staged batch -> ``stage_wait_s``
STAGE_WAIT = PREFIX + "stage_wait"
#: host side of the scoring step: cache insert issue, page-table ``put``,
#: the scoring jit's dispatch -> ``dispatch_s``
DISPATCH = PREFIX + "dispatch"
#: the doc cache's miss insertion (inside ``dispatch``; trace only)
INSERT = PREFIX + "insert"
#: the scores' ``device_get``: waiting for the chip (trace only; the rest
#: of ``combine_s``)
DEVICE_GET = PREFIX + "device_get"
#: one ``BatchEngine.drain`` -> ``wall_s``
DRAIN = PREFIX + "drain"
#: ``RankingService._finalize``: sorting and response assembly (trace
#: only)
FINALIZE = PREFIX + "finalize"

NAMES = (ENCODE, PLAN, GATHER, SHIP, STAGE_WAIT, DISPATCH, INSERT,
         DEVICE_GET, DRAIN, FINALIZE)


class span:
    """``with span(GATHER) as sp: ...`` then ``sp.s``: the body's host
    seconds, annotated on the profiler's trace as ``GATHER``."""

    __slots__ = ("name", "s", "_ann", "_t0")

    def __init__(self, name: str):
        self.name = name
        self.s = 0.0

    def __enter__(self) -> "span":
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.s = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        return False
