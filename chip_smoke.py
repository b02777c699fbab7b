#!/usr/bin/env python3
"""Chip smoke test: the paper's BERT-base PreTTR ranker, end to end on a TPU.

    python chip_smoke.py [--seed N]          # one chip: phases (a) and (b)
    python chip_smoke.py --chips 4 [--seed N]  # four chips: phase (c) only

One process, run on a machine whose JAX sees a TPU; anything else exits
non-zero before any phase runs.  Widths are ``configs/prettr_bert.
full_config`` (12 layers, d=768, 12 heads, d_ff 3072, vocab 30522, query 32
+ doc 480 tokens, l=6, e=256); parameters are a seeded random init.

Corpus (from ``--seed``): 512 documents with heavy-tailed (log-normal)
lengths capped at 480 tokens, zipf-distributed token ids, 4 queries of
8..30 tokens, each with 100 candidates — the paper's Table 5 unit.

(a) fp16 index, no doc cache: ``IndexBuilder(backend="pallas")`` writes the
    compressed fp16 reps; ``RankingService`` scores on the miss path (index
    gather -> fused decompress -> dense split-KV join -> CLS row).  Scores
    are checked against ``rank_forward`` — the joint forward, plain backend,
    fp32 compute, ``default_matmul_precision("highest")`` — on the chip.
(b) The same corpus with int8 stored layer-l K/V and the paged doc cache:
    the paged int8 join, first over a cold cache (misses inserted), then
    warm (all hits).  Checked against phase (a): max |score difference|
    and the rank correlation of each query's candidates.
(c) ``--chips 4``: ``RankingRouter`` with 4 shard workers pinned to
    ``jax.devices()[:4]`` against a one-chip ``RankingService`` on the same
    requests, with implicit device-to-device transfers disallowed
    process-wide (a stray device-0 array fails the phase); scores must be
    bit-identical.

Every phase fails on a degraded or failed row, a shed request, or a scoring
program without a compiled kernel (``tpu_custom_call``; in phase (c) the
one-chip service's and every router worker's).  The last line of
standard output is ``{"ok": true, "device": {...}}``; a failure prints no
such line and exits non-zero.

Limits.  Scores are the ranker's unnormalized logits; with this seed's
random init they spread with a standard deviation of 0.197 on the chip.
Each limit sits between the sound readings and planted faults, measured
on a TPU v5e at this corpus and seed (sound: (a) vs fp32, (b) vs (a);
faults: K/V pages of the neighbouring row, V from the neighbouring pool
page, half of every page's validity dropped, the dense join skipping doc
tiles past the first):

==========================  =======  =======  ==================
reading                     sound a  sound b  faults (nearest)
==========================  =======  =======  ==================
max |dscore|                0.0286   0.0170   0.108 .. 0.307
Spearman, worst query       0.9917   0.9917   0.925 .. 0.198
==========================  =======  =======  ==================

* ``TOL_REF`` = 0.05, (a) vs the fp32 reference: bf16 compute through 12
  layers against fp32, both reading the same fp16 stored reps; 1.7x the
  sound reading, a third of the nearest (a)-fault (0.159).
* ``TOL_INT8`` = 0.04, (b) vs (a): int8 K/V with per-token absmax scales
  at layer l; 2.4x the sound reading, 2.7x under the nearest fault.
* ``MIN_SPEARMAN`` = 0.97, both phases: the rank correlation of the 100
  candidates' scores, worst query.  bf16 or int8 noise reorders only near
  ties (0.9917); every planted fault above falls to 0.925 or lower.
  Top-10 overlap is printed, not checked: sound runs keep 7 of 10 (near
  ties swap), and so does a run with half the validity dropped.
A K-scale one token out of place (0.023, Spearman 0.993) stays inside the
noise: no bound at this spread can see it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

N_DOCS, MAX_QUERY_LEN, MAX_DOC_LEN = 512, 32, 480
N_QUERIES, N_CANDIDATES, MICRO_BATCH = 4, 100, 32
SPLIT_L, COMPRESS_DIM = 6, 256
DOC_CACHE_MB, PAGE_TOKENS = 512, 128
TOL_REF = 0.05           # max |score(a) - score(fp32 reference)|
TOL_INT8 = 0.04          # max |score(b) - score(a)|
MIN_SPEARMAN = 0.97      # rank correlation per query, worst query


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require_tpu(n_chips: int):
    """The TPU devices, or exit non-zero: this check never runs anywhere
    else (a CPU run would only test the Pallas interpreter)."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX sees {len(devs)} "
              f"{devs[0].platform} device(s); run this on the chip",
              file=sys.stderr)
        sys.exit(2)
    if len(devs) < n_chips:
        print(f"[chip_smoke] --chips {n_chips} needs {n_chips} TPU chips; "
              f"JAX sees {len(devs)}", file=sys.stderr)
        sys.exit(2)
    return devs


# ---------------------------------------------------------------------------
# corpus and reference
# ---------------------------------------------------------------------------


def make_corpus(seed: int, vocab: int, *, n_docs=N_DOCS,
                max_doc_len=MAX_DOC_LEN, n_queries=N_QUERIES,
                n_candidates=N_CANDIDATES):
    """Seeded synthetic corpus -> (docs, queries, candidates [Q, C])."""
    import numpy as np

    from repro.data.tokenizer import N_SPECIAL

    rng = np.random.default_rng(seed)

    def ids(n):                              # zipf over the real vocab
        return (N_SPECIAL + (rng.zipf(1.3, n) - 1) % (vocab - N_SPECIAL)) \
            .astype(np.int32)

    # heavy tail: median ~120 tokens, a few percent hit the cap
    lens = np.clip(rng.lognormal(np.log(120), 0.8, n_docs).astype(int), 8,
                   max_doc_len - 1)
    docs = [ids(n) for n in lens]
    queries = [ids(int(n)) for n in rng.integers(8, MAX_QUERY_LEN - 1,
                                                 n_queries)]
    cands = np.stack([rng.choice(n_docs, n_candidates, replace=False)
                      for _ in range(n_queries)])
    return docs, queries, cands


def reference_scores(params, cfg, docs, queries, cands):
    """fp32 joint forward (``rank_forward``, plain backend, highest matmul
    precision) for every (query, candidate) -> [Q, C] float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.prettr import rank_forward
    from repro.data.synthetic_ir import pack_doc_batch, pack_query

    bb = dataclasses.replace(cfg.backbone, compute_dtype=jnp.float32,
                             attn_impl="plain", compress_impl="plain")
    ref_cfg = dataclasses.replace(cfg, backbone=bb)
    fwd = jax.jit(lambda p, t, s, v: rank_forward(p, ref_cfg, t, s, v))
    out = []
    for q, row in zip(queries, cands):
        qt, qv = pack_query(q, cfg.max_query_len)
        dt, _, dv = pack_doc_batch([docs[i] for i in row], cfg.max_doc_len)
        n = len(row)
        tokens = np.concatenate([np.repeat(qt[None], n, 0), dt], 1)
        valid = np.concatenate([np.repeat(qv[None], n, 0), dv], 1)
        segs = np.concatenate(
            [np.zeros((n, cfg.max_query_len), np.int32),
             np.ones((n, cfg.max_doc_len), np.int32)], 1)
        with jax.default_matmul_precision("highest"):
            out.append(np.asarray(fwd(params, tokens, segs, valid)))
    return np.stack(out)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


class ScoringProbe:
    """Wraps a service engine's scoring jit: records the abstract
    arguments of its first call, so the program it ran can be lowered
    again and searched for the compiled kernel."""

    def __init__(self, engine, attr: str):
        self.engine, self.attr = engine, attr
        self.fn = getattr(engine, attr)
        self.args = None
        setattr(engine, attr, self)

    def __call__(self, *args):
        import jax

        if self.args is None:
            self.args = jax.tree.map(
                lambda a: (jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                sharding=a.sharding)
                           if isinstance(a, jax.Array) else a), args)
        return self.fn(*args)

    def has_kernel(self) -> bool:
        if self.args is None:
            raise SmokeFailure(f"scoring entry {self.attr} never ran")
        return "tpu_custom_call" in self.fn.lower(*self.args).as_text()


def build_index(out: Path, cfg, params, docs, **kw):
    from repro.index import IndexBuilder, TermRepIndex

    shutil.rmtree(out, ignore_errors=True)
    report = IndexBuilder(str(out), cfg, params, backend="pallas",
                          **kw).build(docs)
    return TermRepIndex.open(str(out)), report


def serve(svc, queries, cands, max_query_len):
    """Submit every query, drain -> ([Q, C] scores aligned with ``cands``,
    wall seconds).  Any degraded row or shed request fails the phase."""
    import numpy as np

    from repro.data.synthetic_ir import pack_query
    from repro.serving import RankRequest

    shed0 = svc.stats.n_shed
    t0 = time.perf_counter()
    for qi, (q, row) in enumerate(zip(queries, cands)):
        qt, qv = pack_query(q, max_query_len)
        svc.submit(RankRequest(qt, qv, [int(d) for d in row],
                               request_id=str(qi)))
    responses = svc.drain()
    wall = time.perf_counter() - t0
    if svc.stats.n_shed != shed0:
        raise SmokeFailure(f"{svc.stats.n_shed - shed0} requests shed")
    if len(responses) != len(queries):
        raise SmokeFailure(f"{len(responses)} responses for "
                           f"{len(queries)} requests")
    scores = np.full(cands.shape, np.nan, np.float32)
    for r in responses:
        if r.degraded or r.failed_doc_ids:
            raise SmokeFailure(f"request {r.request_id} degraded: "
                               f"{len(r.failed_doc_ids)} failed rows")
        by_doc = dict(zip(r.doc_ids, r.scores))
        qi = int(r.request_id)
        scores[qi] = [by_doc[int(d)] for d in cands[qi]]
    if not np.isfinite(scores).all():
        raise SmokeFailure("non-finite scores")
    return scores, wall


def top10_overlap(a, b) -> int:
    """Smallest per-query overlap of the top-10 candidate sets."""
    import numpy as np

    return min(len(set(np.argsort(-x)[:10]) & set(np.argsort(-y)[:10]))
               for x, y in zip(a, b))


def spearman(a, b) -> float:
    """Smallest per-query Spearman rank correlation of two [Q, C] score
    arrays."""
    import numpy as np

    def ranks(x):
        return np.argsort(np.argsort(x))

    return min(float(np.corrcoef(ranks(x), ranks(y))[0, 1])
               for x, y in zip(a, b))


def check(name: str, got, want, tol: float) -> float:
    import numpy as np

    err = float(np.max(np.abs(got - want)))
    log(f"{name}: max |dscore| = {err!r} (tolerance {tol})")
    if not err <= tol:
        raise SmokeFailure(f"{name}: max |dscore| {err} > {tol}")
    return err


def check_ranks(name: str, got, want):
    """Rank agreement of every query's candidates: Spearman checked
    against ``MIN_SPEARMAN``, top-10 overlap printed."""
    rho = spearman(got, want)
    log(f"{name}: min Spearman {rho!r} (need {MIN_SPEARMAN}); min top-10 "
        f"overlap {top10_overlap(got, want)}/10 (not checked)")
    if not rho >= MIN_SPEARMAN:
        raise SmokeFailure(f"{name}: Spearman {rho} < {MIN_SPEARMAN}")


def run_service_phase(name, svc, probe_attr, queries, cands, cfg,
                      passes=("cold", "warm")):
    """Serve the requests ``len(passes)`` times through ``svc`` -> list of
    [Q, C] score arrays.  The first pass compiles (set-up time)."""
    probe = ScoringProbe(svc.engine, probe_attr)
    out, walls = [], []
    for label in passes:
        scores, wall = serve(svc, queries, cands, cfg.max_query_len)
        log(f"{name} {label} pass: {len(queries)} queries x "
            f"{cands.shape[1]} candidates in {wall!r} s"
            + (" (includes compiles)" if label == passes[0] else ""))
        out.append(scores)
        walls.append(wall)
    log(f"{name}: set-up (compiles) ~ first pass - last pass = "
        f"{walls[0] - walls[-1]!r} s")
    if not probe.has_kernel():
        raise SmokeFailure(f"{name}: scoring program has no tpu_custom_call "
                           f"(kernels did not run compiled)")
    log(f"{name}: scoring program holds compiled Pallas kernels")
    return out


def one_chip(cfg, params, docs, queries, cands, work: Path):
    from repro.serving import RankingService

    # (a) fp16 index, no doc cache: dense join on the miss path
    t0 = time.perf_counter()
    idx_a, rep = build_index(work / "fp16", cfg, params, docs,
                             codec="fp16", batch_size=MICRO_BATCH)
    log(f"phase a: built fp16 index of {rep.n_docs} docs "
        f"({rep.n_tokens} tokens) in {time.perf_counter() - t0!r} s "
        f"(encode {rep.encode_s!r} s, includes compiles)")
    svc = RankingService(params, cfg, idx_a, micro_batch=MICRO_BATCH)
    cold_a, warm_a = run_service_phase("phase a", svc, "_join_raw", queries,
                                       cands, cfg)
    t0 = time.perf_counter()
    ref = reference_scores(params, cfg, docs, queries, cands)
    log(f"phase a: fp32 reference in {time.perf_counter() - t0!r} s "
        f"(score std {float(ref.std())!r})")
    check("phase a vs fp32 reference", cold_a, ref, TOL_REF)
    check_ranks("phase a vs fp32 reference", cold_a, ref)
    check("phase a warm vs cold", warm_a, cold_a, 0.0)

    # (b) int8 stored layer-l K/V through the paged doc cache
    t0 = time.perf_counter()
    idx_b, rep = build_index(work / "int8kv", cfg, params, docs,
                             codec="fp16", batch_size=MICRO_BATCH,
                             store_layer_kv=True, kv_codec="int8")
    log(f"phase b: built fp16 + int8 layer-K/V index in "
        f"{time.perf_counter() - t0!r} s")
    svc = RankingService(params, cfg, idx_b, micro_batch=MICRO_BATCH,
                         doc_cache_mb=DOC_CACHE_MB, page_tokens=PAGE_TOKENS)
    cold_b, warm_b = run_service_phase("phase b", svc, "_join_pool", queries,
                                       cands, cfg)
    s = svc.stats
    log(f"phase b: doc cache hit rate {s.doc_cache_hit_rate!r}, "
        f"{s.resident_docs} resident docs, decode dispatches "
        f"{s.n_decode_dispatch}")
    check("phase b cold vs phase a", cold_b, cold_a, TOL_INT8)
    check("phase b warm vs cold", warm_b, cold_b, 0.0)
    check_ranks("phase b cold vs phase a", cold_b, cold_a)


def four_chips(cfg, params, docs, queries, cands, work: Path, devices):
    """(c) RankingRouter over 4 pinned workers vs one-chip RankingService."""
    import jax
    import numpy as np

    from repro.serving import RankingRouter, RankingService

    t0 = time.perf_counter()
    idx, _ = build_index(work / "int8kv_4", cfg, params, docs, codec="fp16",
                         n_shards=4, batch_size=MICRO_BATCH,
                         store_layer_kv=True, kv_codec="int8")
    log(f"phase c: built 4-shard index in {time.perf_counter() - t0!r} s")
    knobs = dict(micro_batch=MICRO_BATCH, doc_cache_mb=DOC_CACHE_MB,
                 page_tokens=PAGE_TOKENS)
    single = RankingService(params, cfg, idx, device=devices[0], **knobs)
    probes = [ScoringProbe(single.engine, "_join_pool")]
    want, wall = serve(single, queries, cands, cfg.max_query_len)
    log(f"phase c: one-chip service in {wall!r} s (includes compiles)")
    # process-wide, not the thread-local context manager: the workers
    # stage and score on threads of their own
    jax.config.update("jax_transfer_guard_device_to_device", "disallow")
    try:
        router = RankingRouter(params, cfg, idx, n_shards=4,
                               devices=list(devices[:4]), **knobs)
        log("phase c: router workers on " + ", ".join(
            f"s{w.shard_id}={w.device}" for w in router.workers))
        probes += [ScoringProbe(w.engine, "_join_pool")
                   for w in router.workers]
        got, wall = serve(router, queries, cands, cfg.max_query_len)
    finally:
        jax.config.update("jax_transfer_guard_device_to_device", "allow")
    log(f"phase c: 4-worker router in {wall!r} s (includes compiles)")
    bad = int(np.sum(got != want))
    log(f"phase c: router vs one chip: {bad} of {got.size} scores differ, "
        f"max |dscore| = {float(np.max(np.abs(got - want)))!r} "
        f"(bit-exact required)")
    if bad:
        raise SmokeFailure(f"router scores differ from one chip in {bad}")
    if not all(p.has_kernel() for p in probes):
        raise SmokeFailure("phase c: a scoring program has no "
                           "tpu_custom_call (kernels did not run compiled)")
    log(f"phase c: all {len(probes)} scoring programs hold compiled "
        f"Pallas kernels")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the 4-chip router phase")
    args = ap.parse_args()

    devs = require_tpu(args.chips)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import jax

        from repro.configs.prettr_bert import full_config
        from repro.core.prettr import init_prettr
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"[chip_smoke] the repository's sources are missing: {e}",
              file=sys.stderr)
        return 3
    log(f"cache: {enable_compile_cache()}")
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs) if args.chips == 4 else 1}
    log(f"device: {dev}")

    cfg = full_config(l=SPLIT_L, compress_dim=COMPRESS_DIM,
                      max_query_len=MAX_QUERY_LEN, max_doc_len=MAX_DOC_LEN,
                      attn_impl="pallas", compress_impl="pallas")
    params, _ = init_prettr(jax.random.PRNGKey(args.seed), cfg)
    docs, queries, cands = make_corpus(args.seed, cfg.backbone.vocab_size)
    log(f"corpus: {len(docs)} docs (lengths median "
        f"{int(sorted(map(len, docs))[len(docs) // 2])}, max "
        f"{max(map(len, docs))}), {len(queries)} queries x "
        f"{cands.shape[1]} candidates")
    work = ROOT / "results" / "chip_smoke"
    try:
        if args.chips == 4:
            four_chips(cfg, params, docs, queries, cands, work, devs)
        else:
            one_chip(cfg, params, docs, queries, cands, work)
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
