"""Paper Table 5: query-time latency of re-ranking 100 candidates vs ``l``.

Measures, per l: query-encode time (layers 0..l once per query), decompress
time, and combine time (layers l..n over query+doc with the CLS-only final
layer) — the exact phase split of Table 5 — plus the speedup over the base
(l=0, full joint forward) model.  Wall-clock is CPU here; the *ratios*
reproduce the paper's structure (cost ~ (n-l)/n with an extra kick at
l=n-1 from the CLS-only last layer; paper: 42x at l=11/12 layers).

``--backend {plain,blocked,pallas}`` routes every phase through the chosen
compute backend (``repro.models.backend``), so the Query/Decompress/Combine
split can be compared per backend; off-TPU "pallas" runs the kernels in
interpret mode (slow in absolute terms — use the size flags for smokes).

``--service`` measures *throughput* instead of the single-query split: it
builds a small on-disk index and drives the ``RankingService`` with
``--concurrency`` queries in flight per wave, reporting QPS and p50/p99
request latency.  Packing candidates from concurrent queries into shared
micro-batches means fewer (and fuller) device dispatches, so QPS at
``--concurrency 8`` should beat ``--concurrency 1`` even on CPU.

A bigger backbone than the quality benchmarks is used so compute dominates
dispatch overhead.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp

if __package__ in (None, ""):            # `python benchmarks/table5_latency.py`
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, _root)                      # benchmarks.*
    sys.path.insert(0, os.path.join(_root, "src"))  # repro.* sans install

from benchmarks.common import timer
from repro.core.compression import decompress
from repro.models.backend import impls_for
from repro.core.prettr import (PreTTRConfig, encode_query, init_prettr,
                               join_and_score, make_backbone, precompute_docs,
                               rank_forward)

N_LAYERS = 8
D_MODEL = 128
MAX_Q, MAX_D = 16, 112
N_DOCS = 100


def run(backend: str = "blocked", n_layers: int = N_LAYERS,
        d_model: int = D_MODEL, n_docs: int = N_DOCS,
        max_q: int = MAX_Q, max_d: int = MAX_D,
        max_l: int | None = None) -> list[dict]:
    attn_impl, compress_impl = impls_for(backend)
    rows = []
    key = jax.random.PRNGKey(0)
    q = jax.random.randint(key, (1, max_q), 5, 1000)
    qv = jnp.ones((1, max_q), bool)
    docs = jax.random.randint(key, (n_docs, max_d), 5, 1000)
    dv = jnp.ones((n_docs, max_d), bool)
    tokens = jnp.concatenate([jnp.broadcast_to(q, (n_docs, max_q)), docs], 1)
    segs = jnp.concatenate([jnp.zeros((n_docs, max_q), jnp.int32),
                            jnp.ones((n_docs, max_d), jnp.int32)], 1)
    valid = jnp.concatenate([jnp.broadcast_to(qv, (n_docs, max_q)), dv], 1)

    base_s = None
    stop = n_layers if max_l is None else min(n_layers, max_l + 1)
    for l in range(stop):
        e = d_model // 4
        bb = make_backbone(n_layers=n_layers, d_model=d_model, n_heads=8,
                           d_ff=4 * d_model, vocab_size=1024, l=l,
                           max_len=max_q + max_d,
                           compute_dtype=jnp.float32, block_kv=64,
                           attn_impl=attn_impl, compress_impl=compress_impl)
        cfg = PreTTRConfig(backbone=bb, l=l, max_query_len=max_q,
                           max_doc_len=max_d, compress_dim=e)
        params, _ = init_prettr(jax.random.PRNGKey(1), cfg)

        if l == 0:
            # base model: full joint forward over the candidates
            f = jax.jit(lambda p: rank_forward(p, cfg, tokens, segs, valid))
            total = timer(f, params)
            base_s = total
            rows.append({"l": 0, "backend": backend, "total_s": total,
                         "speedup": 1.0, "query_ms": None,
                         "decompress_ms": None, "combine_ms": None})
            print(f"[table5] {backend} base (l=0): {total*1e3:.1f} ms / "
                  f"{n_docs} docs")
            continue

        store = precompute_docs(params, cfg, docs, dv)   # index time (free)
        enc = jax.jit(lambda p: encode_query(p, cfg, q, qv))
        t_query = timer(enc, params)
        q_reps = enc(params)
        dec = jax.jit(lambda c, s: decompress(c, s, compute_dtype=jnp.float32,
                                              impl=compress_impl))
        t_dec = timer(dec, params["compressor"], store)
        d_reps = dec(params["compressor"], store)

        def _join(p, qr, dr):
            # measure the combine phase on already-decompressed reps by
            # using an uncompressed-config view of the same weights
            cfg_nc = PreTTRConfig(backbone=bb, l=l, max_query_len=max_q,
                                  max_doc_len=max_d, compress_dim=0,
                                  store_dtype=jnp.float32)
            return join_and_score({k: v for k, v in p.items()
                                   if k != "compressor"},
                                  cfg_nc,
                                  jnp.broadcast_to(qr, (n_docs, max_q,
                                                        d_model)),
                                  jnp.broadcast_to(qv, (n_docs, max_q)),
                                  dr, dv)

        joinf = jax.jit(_join)
        t_comb = timer(joinf, params, q_reps, d_reps)
        total = t_query + t_dec + t_comb
        rows.append({"l": l, "backend": backend, "total_s": total,
                     "speedup": base_s / total,
                     "query_ms": t_query * 1e3, "decompress_ms": t_dec * 1e3,
                     "combine_ms": t_comb * 1e3})
        print(f"[table5] {backend} l={l}: total={total*1e3:.1f}ms "
              f"(query={t_query*1e3:.1f} decomp={t_dec*1e3:.1f} "
              f"combine={t_comb*1e3:.1f}) speedup={base_s/total:.1f}x")
    return rows


def _drive_service(svc, queries, cand_lists, concurrency):
    """Push the whole workload through the service twice — a cold pass off
    the clock (compiles every jit entry the steady state touches and warms
    the doc cache to its stationary zipf population), then the measured
    warm pass.  Steady-state serving is the regime the trajectory tracks;
    cold-start compilation is a one-time cost per deployment."""
    import numpy as np

    from repro.serving import RankRequest

    n_queries = len(queries)

    def one_pass():
        lat = []
        t0 = time.perf_counter()
        for lo in range(0, n_queries, concurrency):
            for qi in range(lo, min(lo + concurrency, n_queries)):
                q, qv = queries[qi]
                svc.submit(RankRequest(q, qv, cand_lists[qi],
                                       request_id=str(qi)))
            lat += [r.latency_s for r in svc.drain()]
        return lat, time.perf_counter() - t0

    one_pass()                                   # cold: compile + cache warm
    # median-of-3 warm passes: single-pass wall clock on a shared CPU is
    # too noisy to commit as a perf trajectory
    passes = []
    for _ in range(3):
        svc.reset_stats()
        lat, wall = one_pass()
        passes.append((lat, wall, svc.stats))
    lat_s, wall, s = sorted(passes, key=lambda p: p[1])[1]
    p50, p99 = (float(v) for v in np.percentile(lat_s, [50, 99]))
    nq = max(1, s.n_requests)
    return {"qps": n_queries / wall, "p50_us": p50 * 1e6, "p99_us": p99 * 1e6,
            "query_encode_us": s.query_encode_s / nq * 1e6,
            "load_us": s.load_s / nq * 1e6,
            "combine_us": s.combine_s / nq * 1e6,
            "n_batches": float(s.n_batches),
            "join_dispatch": float(s.n_join_dispatch),
            "decode_dispatch": float(s.n_decode_dispatch),
            "pack_fill": s.pack_fill,
            "doc_cache_hit_rate": s.doc_cache_hit_rate,
            "h2d_mb": s.h2d_bytes / 2**20,
            "doc_hbm_mb": s.doc_hbm_bytes / 2**20,
            "resident_docs": float(s.resident_docs)}


def run_service(backend: str = "blocked", concurrency: int = 8,
                n_queries: int = 16, candidates: int = 48,
                micro_batch: int = 48, n_layers: int = 4, d_model: int = 64,
                l: int = 3, max_q: int = 16, max_d: int = 192,
                n_docs: int = 512, codec: str = "fp16", n_shards: int = 2,
                zipf: float = 1.3, doc_cache_mb: float = 32.0,
                store_layer_kv: bool = True, page_tokens: int = 32,
                shard_counts: tuple = (1, 2, 4, 8),
                write_bench: bool = True) -> list[dict]:
    """The serving perf trajectory: QPS / p50 / p99 / per-phase µs of the
    RankingService on a zipf candidate stream (``zipf`` > 0 skews candidate
    draws toward hot documents; 0 = uniform) over variable-length documents
    (uniform in ``[max_d/4, max_d)`` tokens), measured for three
    configurations over the same workload:

    * **legacy** — the PR-4 baseline: concat join, no stored K/V, no doc
      cache (every candidate is gathered, H2D-shipped and decoded per
      request);
    * **fused** — the fused split-KV join consuming the index's stored
      layer-``l`` K/V streams (when ``store_layer_kv``), with the
      device-resident hot-doc cache (``doc_cache_mb`` MiB);
    * **fused_int8_paged** — the same join over an int8 index (reps *and*
      K/V streams quantized): the cache pools hold raw int8 bytes in
      ``page_tokens``-token pages with per-batch page-table bucketing, and
      the join kernel dequantizes in-register — no standalone decode
      dispatch anywhere (``decode_dispatch = 0``);
    * **fused_int8_pruned** — the int8-paged configuration over a
      ``keep_frac=0.5`` token-pruned build of the same corpus: half the
      stored tokens per doc, served at the index's pruned ``max_doc_len``
      (half-width padded joins, half the bytes at every stage — the
      "shrink the stored document itself" operating point).

    Then the **scale-out curve**: the *fused* configuration served through
    the ``RankingRouter`` at each of ``shard_counts`` workers
    (shard-affinity routing, per-worker doc caches; workers pin to
    distinct jax devices when the host has enough, else share the default
    device) -> ``serving/sharded/{n}/...`` rows plus the aggregate
    ``serving/sharded/scaling_efficiency_qps`` ratio
    ``qps[max_shards] / (max_shards * qps[1])``.  On the single-device CI
    host the workers time-share one CPU, so the committed curve tracks
    *overhead* (routing + merge cost vs the single-process fused row —
    ``sharded/1`` must sit within the clock epsilon of ``fused``); on a
    real multi-device mesh the same rows measure genuine scale-out.

    The default sizes sit at the paper's headline operating point — ``l =
    n-1`` (the query-time join is just the CLS-only final layer), long
    documents, many candidates — where serving is *load*-bound (SDR's
    regime: moving doc representations dominates scoring them).  There the
    optimizations are visible separately in the phase split: the warm
    cache removes most of ``load_us``, the stored K/V removes the CLS
    layer's doc-side projections from ``combine_us``, and int8 paging
    halves the doc-side bytes the join touches (``doc_hbm_mb``).

    Writes the ``{name, value, unit}`` rows of all configurations (plus
    the speedups) to the repo-root ``BENCH_serving.json`` so future PRs can
    diff serving perf (``benchmarks/serving.py --check-baseline`` gates on
    it); the writer asserts the file schema.
    """
    import os as _os
    import tempfile

    import numpy as np

    from benchmarks.common import write_bench_serving
    from repro.core.prettr import PreTTRConfig, init_prettr
    from repro.data.synthetic_ir import pack_query
    from repro.index import IndexBuilder, TermRepIndex
    from repro.serving import RankingService

    attn_impl, compress_impl = impls_for(backend)
    e = d_model // 4
    bb = make_backbone(n_layers=n_layers, d_model=d_model, n_heads=4,
                       d_ff=4 * d_model, vocab_size=1024, l=l,
                       max_len=max_q + max_d, compute_dtype=jnp.float32,
                       block_kv=32, attn_impl=attn_impl,
                       compress_impl=compress_impl)
    cfg = PreTTRConfig(backbone=bb, l=l, max_query_len=max_q,
                       max_doc_len=max_d, compress_dim=e)
    params, _ = init_prettr(jax.random.PRNGKey(0), cfg)

    rng = np.random.default_rng(0)
    doc_lens = rng.integers(max_d // 4, max_d, size=n_docs)
    doc_lists = [rng.integers(5, 1000, size=int(n)) for n in doc_lens]
    queries = [pack_query(rng.integers(5, 1000, size=max_q - 2), max_q)
               for _ in range(n_queries)]
    if zipf > 0:     # skewed candidate stream: hot docs repeat across queries
        cand_lists = [list((np.minimum(rng.zipf(zipf, size=candidates),
                                       n_docs) - 1).astype(np.int64))
                      for _ in range(n_queries)]
    else:
        cand_lists = [list(rng.integers(0, n_docs, size=candidates))
                      for _ in range(n_queries)]

    rows = []
    units = {"qps": "qps", "p50_us": "us", "p99_us": "us",
             "query_encode_us": "us/query", "load_us": "us/query",
             "combine_us": "us/query", "n_batches": "count",
             "join_dispatch": "dispatches",
             "decode_dispatch": "dispatches", "pack_fill": "frac",
             "doc_cache_hit_rate": "frac", "h2d_mb": "MiB",
             "doc_hbm_mb": "MiB", "resident_docs": "docs"}
    with tempfile.TemporaryDirectory() as tmp:
        fp_dir = _os.path.join(tmp, "float")
        q_dir = _os.path.join(tmp, "int8")
        p_dir = _os.path.join(tmp, "int8_pruned")
        IndexBuilder(fp_dir, cfg, params, codec=codec, n_shards=n_shards,
                     batch_size=64,
                     store_layer_kv=store_layer_kv).build(doc_lists)
        IndexBuilder(q_dir, cfg, params, codec="int8", n_shards=n_shards,
                     batch_size=64, store_layer_kv=store_layer_kv,
                     kv_codec="int8" if store_layer_kv else None,
                     ).build(doc_lists)
        IndexBuilder(p_dir, cfg, params, codec="int8", n_shards=n_shards,
                     batch_size=64, store_layer_kv=store_layer_kv,
                     kv_codec="int8" if store_layer_kv else None,
                     keep_frac=0.5).build(doc_lists)
        idx = TermRepIndex.open(fp_dir)
        idx8 = TermRepIndex.open(q_dir)
        idx8p = TermRepIndex.open(p_dir)

        configs = [
            ("legacy", idx, dict(fused=False, use_layer_kv=False)),
            ("fused", idx, dict(fused=True, doc_cache_mb=doc_cache_mb)),
            ("fused_int8_paged", idx8,
             dict(fused=True, doc_cache_mb=doc_cache_mb,
                  page_tokens=page_tokens, page_bucket=True)),
            ("fused_int8_pruned", idx8p,
             dict(fused=True, doc_cache_mb=doc_cache_mb,
                  page_tokens=page_tokens, page_bucket=True)),
        ]
        results = {}
        import dataclasses as _dc
        for name, index, kw in configs:
            # a pruned index serves at its own (shorter) padded doc shape
            scfg = (_dc.replace(cfg, max_doc_len=index.max_doc_len)
                    if 0 < index.max_doc_len < cfg.max_doc_len else cfg)
            svc = RankingService(params, scfg, index,
                                 micro_batch=micro_batch, **kw)
            r = _drive_service(svc, queries, cand_lists, concurrency)
            results[name] = r
            print(f"[table5] service {backend} codec={index.codec.name} "
                  f"concurrency={concurrency} join={name}: "
                  f"QPS={r['qps']:.2f} p50={r['p50_us']/1e3:.1f}ms "
                  f"p99={r['p99_us']/1e3:.1f}ms "
                  f"(batches={r['n_batches']:.0f} "
                  f"join_dispatch={r['join_dispatch']:.0f} "
                  f"decode_dispatch={r['decode_dispatch']:.0f} "
                  f"pack_fill={r['pack_fill']:.2f} "
                  f"cache_hit={r['doc_cache_hit_rate']:.2f} "
                  f"h2d={r['h2d_mb']:.2f}MiB "
                  f"doc_hbm={r['doc_hbm_mb']:.2f}MiB "
                  f"resident={r['resident_docs']:.0f})")
            rows += [{"name": f"serving/{name}/{k}", "value": float(v),
                      "unit": units[k]} for k, v in r.items()]

        # fault-hook overhead: the serving hot path carries faults.hit()
        # probes at four sites; with no plan installed each is a single
        # truthiness check.  Re-drive the fused configuration under an
        # installed *empty* FaultPlan (worst inactive case: non-empty
        # plan stack, zero matching specs) and commit the QPS ratio vs
        # the plan-free fused row — ~1.0, gated directionally by the
        # --check-baseline machinery like every _qps row
        from repro.serving import FaultPlan
        svc = RankingService(params, cfg, idx, micro_batch=micro_batch,
                             fused=True, doc_cache_mb=doc_cache_mb)
        with FaultPlan([]):
            r_flt = _drive_service(svc, queries, cand_lists, concurrency)
        overhead = r_flt["qps"] / max(1e-9, results["fused"]["qps"])
        rows.append({"name": "serving/faults/overhead_ratio_qps",
                     "value": float(overhead), "unit": "x"})
        print(f"[table5] fault-hook overhead (fused QPS under empty "
              f"FaultPlan / without): {overhead:.2f}x")

        # scale-out curve: the fused configuration through the router at
        # each shard count, same index + workload (per-worker cache budget
        # so the fleet's aggregate cache grows with the shard count)
        from repro.serving import RankingRouter
        from repro.serving.sharded import worker_devices
        shard_qps = {}
        for n_sh in shard_counts:
            devices = worker_devices(n_sh)
            router = RankingRouter(params, cfg, idx, n_shards=n_sh,
                                   devices=devices, micro_batch=micro_batch,
                                   fused=True, doc_cache_mb=doc_cache_mb)
            r = _drive_service(router, queries, cand_lists, concurrency)
            shard_qps[n_sh] = r["qps"]
            print(f"[table5] service {backend} sharded n={n_sh} "
                  f"({'pinned' if devices is not None else 'unpinned'}): "
                  f"QPS={r['qps']:.2f} p50={r['p50_us']/1e3:.1f}ms "
                  f"p99={r['p99_us']/1e3:.1f}ms "
                  f"(batches={r['n_batches']:.0f} "
                  f"pack_fill={r['pack_fill']:.2f} "
                  f"cache_hit={r['doc_cache_hit_rate']:.2f} "
                  f"h2d={r['h2d_mb']:.2f}MiB)")
            rows += [{"name": f"serving/sharded/{n_sh}/{k}",
                      "value": float(v), "unit": units[k]}
                     for k, v in r.items()]
    n_max = max(shard_counts)
    efficiency = shard_qps[n_max] / max(1e-9, n_max * shard_qps[min(
        shard_counts)] / min(shard_counts))
    rows.append({"name": "serving/sharded/scaling_efficiency_qps",
                 "value": efficiency, "unit": "frac"})
    print(f"[table5] sharded scaling efficiency "
          f"(QPS[{n_max}] / ({n_max} x QPS[{min(shard_counts)}]/"
          f"{min(shard_counts)})): {efficiency:.2f}")
    speedup = results["fused"]["qps"] / max(1e-9, results["legacy"]["qps"])
    rows.append({"name": "serving/fused_over_legacy_qps", "value": speedup,
                 "unit": "x"})
    paged_x = (results["fused_int8_paged"]["qps"]
               / max(1e-9, results["fused"]["qps"]))
    rows.append({"name": "serving/int8_paged_over_fused_qps",
                 "value": paged_x, "unit": "x"})
    pruned_x = (results["fused_int8_pruned"]["qps"]
                / max(1e-9, results["fused_int8_paged"]["qps"]))
    rows.append({"name": "serving/int8_pruned_over_int8_paged_qps",
                 "value": pruned_x, "unit": "x"})
    print(f"[table5] fused+cache vs legacy QPS: {speedup:.2f}x; "
          f"int8+paged vs fused QPS: {paged_x:.2f}x; "
          f"pruned vs int8+paged QPS: {pruned_x:.2f}x")
    if write_bench:
        path = write_bench_serving(rows)
        print(f"[table5] wrote {len(rows)} rows -> {path}")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--backend", default="blocked",
                    choices=["plain", "blocked", "pallas"],
                    help="compute backend for every phase")
    ap.add_argument("--layers", type=int, default=N_LAYERS)
    ap.add_argument("--d-model", type=int, default=D_MODEL)
    ap.add_argument("--docs", type=int, default=None,
                    help=f"corpus size (default: {N_DOCS} for the l sweep, "
                         f"512 for --service)")
    ap.add_argument("--max-l", type=int, default=None,
                    help="stop the l sweep at this split (smoke runs)")
    ap.add_argument("--service", action="store_true",
                    help="measure RankingService QPS/p50/p99 (legacy vs "
                         "fused+cache on the same zipf workload, written "
                         "to BENCH_serving.json) instead of the per-query "
                         "phase split")
    ap.add_argument("--concurrency", type=int, default=8,
                    help="--service: queries in flight per wave")
    ap.add_argument("--queries", type=int, default=16,
                    help="--service: total queries to serve")
    ap.add_argument("--candidates", type=int, default=48,
                    help="--service: candidates per query")
    ap.add_argument("--micro-batch", type=int, default=48,
                    help="--service: packed micro-batch rows")
    ap.add_argument("--codec", default="fp16",
                    help="--service: storage codec of the built index")
    ap.add_argument("--index-shards", type=int, default=2,
                    help="--service: shard count of the built index")
    ap.add_argument("--zipf", type=float, default=1.3,
                    help="--service: zipf exponent of the candidate stream "
                         "(0 = uniform draws)")
    ap.add_argument("--doc-cache-mb", type=float, default=32.0,
                    help="--service: device hot-doc cache budget for the "
                         "fused configuration")
    ap.add_argument("--no-store-layer-kv", action="store_true",
                    help="--service: build the index without the stored "
                         "layer-l K/V streams")
    ap.add_argument("--page-tokens", type=int, default=32,
                    help="--service: doc-cache page size for the "
                         "fused_int8_paged configuration")
    ap.add_argument("--no-bench-file", action="store_true",
                    help="--service: skip writing BENCH_serving.json")
    args = ap.parse_args()
    if args.service:
        run_service(backend=args.backend, concurrency=args.concurrency,
                    n_queries=args.queries, candidates=args.candidates,
                    micro_batch=args.micro_batch, codec=args.codec,
                    n_docs=args.docs or 512,
                    n_shards=args.index_shards, zipf=args.zipf,
                    doc_cache_mb=args.doc_cache_mb,
                    store_layer_kv=not args.no_store_layer_kv,
                    page_tokens=args.page_tokens,
                    write_bench=not args.no_bench_file)
        return
    sizes = dict(n_layers=args.layers, d_model=args.d_model,
                 n_docs=args.docs or N_DOCS, max_l=args.max_l)
    if (args.backend == "pallas" and jax.default_backend() != "tpu"
            and (args.layers, args.d_model, args.docs)
            == (N_LAYERS, D_MODEL, None)):
        # interpret mode is ~2 orders slower than compiled XLA; keep the
        # default off-TPU sweep tractable (explicit size flags force full)
        print("[table5] pallas off-TPU -> interpret mode: scaling sweep to "
              "layers=4 d_model=64 docs=32 (pass --layers/--d-model/--docs "
              "to override)")
        sizes.update(n_layers=4, d_model=64, n_docs=32)
    run(backend=args.backend, **sizes)


if __name__ == "__main__":
    main()
