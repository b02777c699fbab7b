"""One run of one cell: set-up, the open-loop window, the metrics, and the
comparison with the plain reference that decides ``correct``.

Set-up (``setup_s``: process start to the first due request): weights from
the seed on the device, the traffic generator's corpus and requests, the
program's index build, its service (or router), and the warm-up the
traffic file asks for: each miss-bucket shape of the doc cache, the whole
corpus into the cache, and the warm requests.  Every program the window
runs is compiled (or read from the persistent cache) by then; the run
counts lowerings inside the window and prints the count.

The window drives the program's ``submit`` / ``drain``.  Arrivals are
open loop: the harness submits every request that is due, then drains,
then repeats; a request that falls due during a drain waits for the next
one, and that wait counts.  Each request is timed from its due time to
the return of the drain that answered it.  The window stays open until
every request due in ``[0, seconds)`` has its answer.

After the window the peak device memory is read, the program's state is
dropped, and a seeded sample of the answered requests (the one with the
most candidate tokens among them) is scored again by ``model.score_pairs``
in float32, and once more at the precisions the configuration states;
the program's score gaps, in units of the stated-precision reference's,
are held to the limits the configuration's ``check`` names (``gaps``).
"""
from __future__ import annotations

import dataclasses
import gc
import math
import shutil
import sys
import time
from pathlib import Path

import numpy as np

import cells
import costs
import model
import system
import trace_reduce
import traffic as traffic_lib

LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Record:
    """One request of the window, as the harness saw it (seconds after the
    window opened)."""
    index: int
    due_s: float
    n_docs: int
    q_len: int                    # packed query tokens ([CLS] q [SEP])
    d_lens: list                  # packed tokens of each candidate
    submit_s: float | None = None
    done_s: float | None = None
    scores: dict | None = None    # doc id -> score
    failed: bool = False


@dataclasses.dataclass
class Context:
    """What a per-layer metric's ``read(ctx)`` gets."""
    config: dict
    traffic: dict
    stats: dict                   # ServiceStats over the window
    requests: list                # Record
    trace: trace_reduce.Trace | None
    peaks: dict | None


class _Lowerings:
    """Counts program lowerings (a compile or a persistent-cache read)
    while ``on``."""

    def __init__(self):
        import jax

        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, *_a, **_k):
        if self.on and name == LOWERING_EVENT:
            self.n += 1


def _peak_bytes(devices):
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def _serve_closed(svc, reqs, cfg, tag):
    """Set-up traffic: submit ``reqs``, drain, fail on any degraded row."""
    mq = cfg["max_query_len"]
    for i, r in enumerate(reqs):
        tok, val = traffic_lib.pack_query(r.query, mq)
        svc.submit(system.request(tok, val, r.doc_ids, f"{tag}-{i}"))
    for resp in svc.drain():
        if resp.degraded:
            raise RuntimeError(f"set-up request {resp.request_id} degraded")


def warm_up(svc, cfg, spec, tr) -> dict:
    """The set-up traffic the traffic file's ``warmup`` asks for."""
    wu = spec.get("warmup", {})
    mb = cfg["serving"]["micro_batch"]
    timings = {}
    queries = [r.query for r in tr.warm] or [tr.requests[0].query]
    if wu.get("miss_buckets"):
        # one micro-batch per request: b never-seen docs and mb - b docs of
        # the request before, which the cache still holds -> every bucket
        # shape of the miss path (powers of two up to mb, and mb)
        t0 = time.perf_counter()
        touched = {int(d) for r in tr.warm for d in r.doc_ids}
        fresh = iter([d for d in range(len(tr.docs)) if d not in touched])
        prev = [next(fresh) for _ in range(mb)]
        _serve_closed(svc, [traffic_lib.Request(-1, 0.0, queries[0],
                                                np.array(prev))], cfg, "b")
        buckets = sorted({min(1 << max(0, (m - 1).bit_length()), mb)
                          for m in range(1, mb + 1)})
        for b in buckets:
            ids = [next(fresh) for _ in range(b)] + prev[:mb - b]
            _serve_closed(svc, [traffic_lib.Request(-1, 0.0, queries[0],
                                                    np.array(ids))],
                          cfg, f"b{b}")
            prev = ids
        timings["miss_buckets_s"] = time.perf_counter() - t0
    if wu.get("fill_corpus"):
        t0 = time.perf_counter()
        k = spec["candidates"]["per_request"]
        ids = np.arange(len(tr.docs))
        chunk = [traffic_lib.Request(-1, 0.0, queries[i % len(queries)],
                                     ids[lo:lo + k])
                 for i, lo in enumerate(range(0, len(ids), k))]
        for lo in range(0, len(chunk), 4):
            _serve_closed(svc, chunk[lo:lo + 4], cfg, "fill")
        timings["fill_corpus_s"] = time.perf_counter() - t0
    if tr.warm:
        t0 = time.perf_counter()
        group = int(wu.get("group", 4))
        for lo in range(0, len(tr.warm), group):
            _serve_closed(svc, tr.warm[lo:lo + group], cfg, "warm")
        timings["warm_requests_s"] = time.perf_counter() - t0
    return timings


def open_loop(svc, tr, cfg, lowerings) -> tuple:
    """The measured window -> (records, seconds from open to last answer)."""
    import jax

    mq, md = cfg["max_query_len"], cfg["max_doc_len"]
    recs = [Record(r.index, r.due_s, len(r.doc_ids),
                   int(traffic_lib.pack_query(r.query, mq)[1].sum()),
                   [min(len(tr.docs[d]), md - 1) + 1 for d in r.doc_ids])
            for r in tr.requests]
    packed = [traffic_lib.pack_query(r.query, mq) for r in tr.requests]
    n, i = len(recs), 0
    queued: dict = {}
    ann = jax.profiler.TraceAnnotation
    lowerings.on = True
    t0 = time.perf_counter()
    with ann("bench.window"):
        while i < n or queued:
            now = time.perf_counter() - t0
            if not queued and recs[i].due_s > now:
                with ann("bench.idle"):
                    time.sleep(recs[i].due_s - now)
            with ann("bench.submit"):
                while i < n and recs[i].due_s <= time.perf_counter() - t0:
                    r = tr.requests[i]
                    recs[i].submit_s = time.perf_counter() - t0
                    svc.submit(system.request(*packed[i], r.doc_ids, str(i)))
                    queued[str(i)] = recs[i]
                    i += 1
            with ann("bench.drain"):
                responses = svc.drain()
            t_done = time.perf_counter() - t0
            for resp in responses:
                rec = queued.pop(resp.request_id)
                rec.done_s = t_done
                rec.failed = bool(resp.degraded) or not np.all(
                    np.isfinite(resp.scores))
                rec.scores = dict(zip((int(d) for d in resp.doc_ids),
                                      (float(s) for s in resp.scores)))
            if queued and not responses:
                raise RuntimeError(f"drain answered none of {len(queued)} "
                                   f"queued requests")
    lowerings.on = False
    span = max(r.done_s for r in recs if r.done_s is not None)
    return recs, span


def end_to_end(recs, span, seconds, peak_bytes, setup_s) -> dict:
    """The cell's end-to-end metrics; the window is ``seconds`` long, or
    as long as its last answer took."""
    lat = np.array([(r.done_s - r.due_s) * 1e3 for r in recs
                    if r.done_s is not None and not r.failed])
    docs = sum(r.n_docs for r in recs if r.done_s is not None and not r.failed)
    out = {"setup_s": setup_s}
    if len(lat):
        out["p50_latency_ms"] = float(np.percentile(lat, 50))
        out["p95_latency_ms"] = float(np.percentile(lat, 95))
    out["docs_per_s"] = docs / max(span, seconds)
    if peak_bytes is not None:
        out["peak_hbm_gib"] = peak_bytes / 2**30
    return out


def stated(cfg: dict) -> dict:
    """The reference at the precisions the configuration states: matmul
    operands in its compute dtype, and stored layer-``l`` K/V in int8 where
    its index keeps them so."""
    kv = 8 if cfg["index"].get("kv_codec") == "int8" else 0
    return {"mm_dtype": cfg["compute_dtype"], "kv_bits": kv}


def controls_for(cfg: dict) -> dict:
    """The controls: the stated reference one precision lower, put in the
    program's place (``bench/control.py`` and the tests read them; the
    benchmark's own runs never do).  ``fp8``: matmul operands in fp8 where
    the configuration computes in bfloat16; ``int4_kv``: stored K/V in
    int4 where the index keeps int8."""
    out = {"fp8": dict(stated(cfg), mm_dtype="float8_e4m3fn")}
    if cfg["index"].get("kv_codec") == "int8":
        out["int4_kv"] = dict(stated(cfg), kv_bits=4)
    return out


def sample(recs, seed: int, n: int) -> list:
    """The answered requests the comparison reads: the one with the most
    candidate tokens and ``n - 1`` more drawn from the seed."""
    done = [r for r in recs if r.scores is not None and not r.failed]
    if not done:
        return []
    longest = max(done, key=lambda r: r.q_len * r.n_docs + sum(r.d_lens))
    rest = [r for r in done if r is not longest]
    rng = traffic_lib._rng(seed, "check")
    pick = rng.choice(len(rest), min(len(rest), n - 1), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def _rms(x: np.ndarray) -> float:
    return float(np.sqrt(np.mean(x * x)))


def _centred(x, ref, sizes) -> np.ndarray:
    """Per request, the gap to the reference less its mean over that
    request's candidates."""
    cuts = np.cumsum(sizes)[:-1]
    return np.concatenate([d - d.mean() for d in np.split(x - ref, cuts)])


def gaps(got, ref, stated, sizes) -> dict:
    """Score gaps to the float32 reference ``ref`` over requests of
    ``sizes`` candidates, in units of the stated-precision reference's:
    ``rms_gap_ratio``, the RMS of the centred gaps (each request's mean gap
    taken out) over the same RMS of ``stated``, and ``max_gap_ratio``, the
    largest centred gap over that RMS.

    Centring takes out the one draw of query-side rounding that all of a
    request's candidates share: left in, it made the gap swing fourfold
    from seed to seed (PERF.md).  The unit follows each seed's weights, so
    the ratios hold still where the raw gaps do not."""
    unit = _rms(_centred(stated, ref, sizes))
    c = _centred(got, ref, sizes)
    if unit == 0.0:
        return {"rms_gap_ratio": math.inf, "max_gap_ratio": math.inf}
    return {"rms_gap_ratio": _rms(c) / unit,
            "max_gap_ratio": float(np.max(np.abs(c))) / unit}


def check(w, cfg, tr, recs, seed, n_sample, controls=None,
          keep=None) -> tuple:
    """Score a seeded sample of the answered requests with the float32
    reference and the stated-precision one -> (``{name: (value, limit)}``,
    ``{control: gaps}``).  ``keep`` (a dict) receives the score arrays."""
    import jax.numpy as jnp

    limits = cfg["check"]
    failed = sum(1 for r in recs if r.done_s is None or r.failed)
    out = {"failed_requests": (float(failed), 0.0)}
    pick = sample(recs, seed, n_sample)
    if not pick:
        out.update({k: (math.inf, v) for k, v in limits.items()})
        return out, {}
    ways = dict(stated=stated(cfg), **(controls or {}))
    got, ref, sizes = [], [], []
    alt = {c: [] for c in ways}
    for r in pick:
        req = tr.requests[r.index]
        docs = [tr.docs[d] for d in req.doc_ids]
        sizes.append(len(docs))
        ref.append(model.score_pairs(w, cfg, req.query, docs))
        got.append(np.array([r.scores[int(d)] for d in req.doc_ids]))
        for c, kw in ways.items():
            alt[c].append(model.score_pairs(
                w, cfg, req.query, docs, kv_bits=kw["kv_bits"],
                mm_dtype=jnp.dtype(kw["mm_dtype"])))
    got, ref = np.concatenate(got), np.concatenate(ref)
    alt = {c: np.concatenate(v) for c, v in alt.items()}
    if keep is not None:
        keep.update(got=got, ref=ref, **alt)
    g = gaps(got, ref, alt["stated"], sizes)
    out.update({k: (g[k], v) for k, v in limits.items()})
    log(f"reference: {len(pick)} requests, {ref.size} scores, score std "
        f"{float(np.std(ref))!r}, raw RMS gap over it "
        f"{_rms(got - ref) / float(np.std(ref))!r}; program: " + ", ".join(
            f"{k} {v!r}" for k, v in g.items()))
    return out, {c: gaps(alt[c], ref, alt["stated"], sizes)
                 for c in controls or {}}


@dataclasses.dataclass
class SetUp:
    """A cell built and warmed up, ready for its window."""
    cell: cells.Cell
    weights: dict
    traffic: traffic_lib.Traffic
    index: object
    service: object
    phases: dict


def set_up(root: Path, workload: str, seed: int, seconds: float, devices,
           work: Path, *, wrap_service=None) -> SetUp:
    """Weights, traffic, the program's index (written under ``work``) and
    service, and the warm-up the traffic file asks for.  ``run``,
    ``sweep.py`` and ``control.py`` all build a cell here."""
    import jax

    cell = cells.load(root, workload)
    cfg, spec = cell.config, cell.traffic
    phases = {}
    t = time.perf_counter()
    w = model.make_weights(seed, cfg, devices[0])
    jax.block_until_ready(w)
    phases["weights_s"] = time.perf_counter() - t
    t = time.perf_counter()
    tr = traffic_lib.generate(spec, cfg["vocab_size"], seed, seconds)
    phases["traffic_s"] = time.perf_counter() - t
    pcfg = system.program_config(cfg)
    t = time.perf_counter()
    index = system.build_index(work / "index", pcfg, w, tr.docs, cfg,
                               n_shards=len(devices))
    phases["index_build_s"] = time.perf_counter() - t
    log(f"peak device memory after the index build: "
        f"{_peak_bytes(devices)}")
    svc = system.build_service(pcfg, w, index, cfg["serving"], devices)
    if wrap_service is not None:
        wrap_service(svc)
    phases.update(warm_up(svc, cfg, spec, tr))
    log(f"peak device memory after warm-up: {_peak_bytes(devices)}")
    svc.reset_stats()
    return SetUp(cell, w, tr, index, svc, phases)


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        devices, t_start: float, *, wrap_service=None,
        controls=None, keep=None) -> dict:
    """One run of ``workload`` on ``devices`` -> the result line's dict.
    ``wrap_service(svc)`` (tests) may replace parts of the built service
    before the window; ``controls`` (``bench/control.py``) adds each
    control's reading on the same sample under ``"controls"``, and
    ``keep`` (a dict) receives the compared score arrays."""
    import jax

    work = Path(root) / "bench" / ".work" / workload
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    lowerings = _Lowerings()
    try:
        su = set_up(root, workload, seed, seconds, devices, work,
                    wrap_service=wrap_service)
        cell, w, tr, svc = su.cell, su.weights, su.traffic, su.service
        cfg, spec = cell.config, cell.traffic
        trace_dir = work / "trace"
        if trace:
            trace_reduce.start(str(trace_dir))
        setup_s = time.time() - t_start
        log(f"set-up {setup_s!r} s: " + ", ".join(
            f"{k} {v:.3f}" for k, v in su.phases.items()))
        recs, span = open_loop(svc, tr, cfg, lowerings)
        if trace:
            jax.profiler.stop_trace()
        stats = system.stats_dict(svc)
        peak = _peak_bytes(devices)
        log(f"window: {len(recs)} requests due in {seconds} s, last answer "
            f"at {span!r} s; lowerings in the window: {lowerings.n}; peak "
            f"device memory {peak}")
        metrics = end_to_end(recs, span, seconds, peak, setup_s)
        log("end-to-end: " + ", ".join(f"{k} {v!r}"
                                       for k, v in metrics.items()))
        tr_red = None
        if trace:
            tr_red = trace_reduce.reduce(
                trace_reduce.xspace_dict(trace_reduce.find_xplane(
                    str(trace_dir))), {d.id for d in devices})
        kind = devices[0].device_kind
        peaks = (costs.peaks(kind) if devices[0].platform == "tpu"
                 else None)
        ctx = Context(cfg, spec, stats, recs, tr_red, peaks)
        per_layer = {}
        for m in cell.per_layer:
            v = cells.reader(root, m["name"])(ctx)
            if v is not None:
                per_layer[m["name"]] = v
        del svc, su
        gc.collect()
        t = time.perf_counter()
        checks, readings = check(
            w, cfg, tr, recs, seed,
            int(spec.get("check", {}).get("requests", 8)), controls, keep)
        log(f"reference check took {time.perf_counter() - t!r} s")
    finally:
        shutil.rmtree(work, ignore_errors=True)

    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    chosen = per_layer if trace else {
        m["name"]: metrics[m["name"]] for m in cell.end_to_end
        if m["name"] in metrics}
    correct = all(v <= lim for v, lim in checks.values())
    out = {
        "correct": bool(correct),
        "attempted": len(recs),
        "failed": int(checks["failed_requests"][0]),
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in chosen.items()},
        "device": {"platform": devices[0].platform, "kind": kind,
                   "count": len(devices), "memory_peak_bytes": peak},
    }
    if trace:
        out["device"]["busy_s"] = trace_reduce.busy_s(tr_red)
        out["device"]["window_s"] = tr_red.window_s
        out["breakdown"] = {"device_ops": trace_reduce.top_ops(tr_red),
                            "idle_gaps": trace_reduce.idle_gaps(tr_red)}
    if controls:
        out["controls"] = readings
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out
