"""Serving driver: build (or load) a PreTTR index and serve re-ranking
queries.

Phases (paper Fig. 1):
  1. index: the offline pipeline (``repro.index.IndexBuilder``) —
     precompute doc term reps through layers 0..l, codec-encode
     (``--codec fp16|fp32|int8``), write ``--shards`` v2 shard directories
     with host writes overlapped against device encoding.  ``--load-index``
     skips the build and serves an existing index (built with
     ``repro.launch.build_index``) instead.
  2. serve: per query — encode once, load candidates, join, rank; report
     per-phase latency (Table 5's Query / Decompress / Combine split).

``--service`` switches phase 2 from the sequential per-query ``Reranker``
loop to the ``RankingService`` request/response API: ``--concurrency N``
queries are admitted at a time, their candidates are packed into fixed
cross-query micro-batches while the prefetcher overlaps index reads with
device compute, and throughput is reported as QPS with p50/p99 request
latency.  A degraded response with no ``FaultPlan`` installed is a failure:
the run exits non-zero.

``--config base`` serves the paper's ranker at its published widths
(``configs/prettr_bert.full_config``: 12 layers, d=768, 32 + 480 tokens,
default l=6, e=256) from a seeded random init; ``smoke`` (the default) is
the 4-layer d=64 toy.
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

import jax


def main() -> None:
    from repro.configs.prettr_bert import CONFIGS, config
    from repro.core.prettr import init_prettr
    from repro.data.synthetic_ir import (SyntheticIRWorld, pack_query,
                                         precision_at_k)
    from repro.index import IndexBuilder, TermRepIndex, available_codecs
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import Reranker, RankingService, RankRequest, faults

    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="smoke", choices=sorted(CONFIGS),
                    help="model widths: smoke (4L d=64) or base (the "
                         "paper's BERT-base ranker, seeded random init)")
    ap.add_argument("--l", type=int, default=None,
                    help="join layer (default: the config's)")
    ap.add_argument("--compress-dim", type=int, default=None,
                    help="compression e (default: the config's)")
    ap.add_argument("--n-docs", type=int, default=512)
    ap.add_argument("--n-queries", type=int, default=16)
    ap.add_argument("--candidates", type=int, default=64)
    ap.add_argument("--micro-batch", type=int, default=32)
    ap.add_argument("--index-dir", default="results/prettr_index")
    ap.add_argument("--index-batch", type=int, default=64)
    ap.add_argument("--codec", default="fp16", choices=available_codecs(),
                    help="storage codec for the built index (int8 decodes "
                         "on device after gather)")
    ap.add_argument("--shards", type=int, default=1,
                    help="shard count for the built index")
    ap.add_argument("--load-index", default=None,
                    help="serve this existing index directory instead of "
                         "building one (corpus/config flags must match the "
                         "build)")
    ap.add_argument("--backend", default="blocked",
                    choices=["plain", "blocked", "pallas"],
                    help="compute backend for indexing and serving "
                         "(pallas = flash/fused kernels; interpret off-TPU)")
    ap.add_argument("--service", action="store_true",
                    help="serve through the RankingService API (cross-query "
                         "micro-batch packing + prefetch) instead of the "
                         "sequential Reranker loop")
    ap.add_argument("--concurrency", type=int, default=4,
                    help="--service: queries admitted per scheduling wave")
    ap.add_argument("--serving-shards", type=int, default=0,
                    help="--service: serve through the scale-out "
                         "RankingRouter with N ShardWorkers (shard-affinity "
                         "candidate routing over the doc table; each worker "
                         "pinned to its own jax device — on a TPU there "
                         "must be N — with its own --doc-cache-mb budget); "
                         "0 = single-process RankingService")
    ap.add_argument("--store-layer-kv", action="store_true",
                    help="store the join layer's doc-side K/V streams in "
                         "the built index (fused join skips the layer-l "
                         "doc projections)")
    ap.add_argument("--kv-codec", default=None,
                    help="codec for the stored layer-l K/V streams "
                         "(requires --store-layer-kv; int8 dequantizes "
                         "in-register inside the join kernel)")
    ap.add_argument("--doc-cache-mb", type=float, default=0.0,
                    help="--service: device-resident hot-doc LRU cache "
                         "budget in MiB (0 = off); cache hits skip index "
                         "gather and H2D (raw stored bytes decode inside "
                         "the scoring jit)")
    ap.add_argument("--doc-cache-page", type=int, default=None,
                    help="--service: doc-cache page size in tokens "
                         "(default: whole-doc slots); small pages pack "
                         "variable-length docs tighter")
    ap.add_argument("--doc-cache-bucket", action="store_true",
                    help="--service: shrink each batch's page-table width "
                         "to its longest doc (bucketed powers of two)")
    ap.add_argument("--legacy-join", action="store_true",
                    help="--service: score through the legacy concat join "
                         "instead of the fused split-KV path")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="--service: bounded admission — shed requests "
                         "(ServiceOverloadError, counted in stats.n_shed) "
                         "beyond this queue depth; 0 = unbounded")
    ap.add_argument("--verify-reads", action="store_true",
                    help="re-verify the CRC-32C chunk checksums of every "
                         "gather's stored bytes (requires an index built "
                         "with checksums; turns silent bit-rot into "
                         "IndexIntegrityError)")
    args = ap.parse_args()

    from repro.models.backend import impls_for
    enable_compile_cache()
    attn_impl, compress_impl = impls_for(args.backend)
    cfg = config(args.config, l=args.l, compress_dim=args.compress_dim,
                 attn_impl=attn_impl, compress_impl=compress_impl)
    world = SyntheticIRWorld(n_docs=args.n_docs, n_queries=args.n_queries,
                             vocab_size=cfg.backbone.vocab_size,
                             doc_len=cfg.max_doc_len - 2, seed=0)
    params, _ = init_prettr(jax.random.PRNGKey(0), cfg)

    # ---- phase 1: index (offline pipeline) ---------------------------------
    if args.load_index:
        idx = TermRepIndex.open(args.load_index,
                                verify_reads=args.verify_reads)
        prune_note = (f", pruned keep_frac="
                      f"{idx.prune_policy['keep_frac']}"
                      if idx.prune_policy else "")
        print(f"[index] loaded {len(idx)} docs from {args.load_index} "
              f"(v{idx.version}, {idx.n_shards} shards, "
              f"codec={idx.codec.name}, "
              f"{idx.storage_bytes() / 2**20:.1f} MiB{prune_note})")
        if 0 < idx.max_doc_len < cfg.max_doc_len:
            # a pruned index caps stored doc lengths below the build
            # config — serve at the pruned shape (smaller padded joins)
            import dataclasses
            cfg = dataclasses.replace(cfg, max_doc_len=idx.max_doc_len)
    else:
        builder = IndexBuilder(args.index_dir, cfg, params,
                               codec=args.codec, n_shards=args.shards,
                               batch_size=args.index_batch,
                               backend=args.backend,
                               store_layer_kv=args.store_layer_kv,
                               kv_codec=args.kv_codec)
        report = builder.build(list(world.docs))
        idx = TermRepIndex.open(args.index_dir,
                                verify_reads=args.verify_reads)
        e = cfg.compress_dim or cfg.backbone.d_model
        raw = report.n_tokens * cfg.backbone.d_model * 4
        print(f"[index] {report.n_docs} docs in {report.wall_s:.1f}s "
              f"({report.n_shards} shards, codec={report.codec}, "
              f"encode={report.encode_s:.1f}s write={report.write_s:.1f}s), "
              f"{report.storage_bytes / 2**20:.1f} MiB "
              f"(e={e}; raw d={cfg.backbone.d_model} fp32 would be "
              f"{raw / 2**20:.1f} MiB)")

    # ---- phase 2: serve -----------------------------------------------------
    if args.service:
        if args.serving_shards > 0:
            from repro.serving import RankingRouter
            from repro.serving.sharded import worker_devices
            devices = worker_devices(args.serving_shards)
            svc = RankingRouter(params, cfg, idx,
                                n_shards=args.serving_shards,
                                devices=devices,
                                micro_batch=args.micro_batch,
                                fused=not args.legacy_join,
                                doc_cache_mb=args.doc_cache_mb,
                                page_tokens=args.doc_cache_page,
                                page_bucket=args.doc_cache_bucket,
                                max_queue=args.max_queue or None)
            pinned = "pinned" if devices is not None else "unpinned"
            print(f"[serve] scale-out: {args.serving_shards} shard workers "
                  f"({pinned}; "
                  + ", ".join(f"s{w.shard_id}={w.n_owned} docs"
                              for w in svc.workers) + ")")
        else:
            svc = RankingService(params, cfg, idx,
                                 micro_batch=args.micro_batch,
                                 fused=not args.legacy_join,
                                 doc_cache_mb=args.doc_cache_mb,
                                 page_tokens=args.doc_cache_page,
                                 page_bucket=args.doc_cache_bucket,
                                 max_queue=args.max_queue or None)
        # warm the jit caches (encode + the packed join shape) off the clock
        q0, qv0 = pack_query(world.queries[0], cfg.max_query_len)
        svc.rank(q0, qv0, list(world.candidates(0, k=args.candidates)),
                 request_id="warmup")
        svc.reset_stats()
        lat_s, p20 = [], []
        t0 = time.perf_counter()
        from repro.serving import ServiceOverloadError
        n_degraded = 0
        for lo in range(0, world.n_queries, args.concurrency):
            for qi in range(lo, min(lo + args.concurrency, world.n_queries)):
                q, qv = pack_query(world.queries[qi], cfg.max_query_len)
                req = RankRequest(
                    q, qv, list(world.candidates(qi, k=args.candidates)),
                    request_id=str(qi))
                try:
                    svc.submit(req)
                except ServiceOverloadError:
                    # bounded admission: drain the backlog, then resubmit
                    for resp in svc.drain():
                        ri = int(resp.request_id)
                        lat_s.append(resp.latency_s)
                        n_degraded += resp.degraded
                        p20.append(precision_at_k(
                            world.qrels[ri][np.asarray(resp.doc_ids)], 20))
                    svc.submit(req)
            for resp in svc.drain():
                qi = int(resp.request_id)
                lat_s.append(resp.latency_s)
                n_degraded += resp.degraded
                p20.append(precision_at_k(
                    world.qrels[qi][np.asarray(resp.doc_ids)], 20))
        wall = time.perf_counter() - t0
        p50, p99 = np.percentile(lat_s, [50, 99])
        s = svc.stats
        cache_note = (f" doc_cache_hit={s.doc_cache_hit_rate:.2f} "
                      f"resident_docs={s.resident_docs}"
                      if svc.doc_cache is not None else "")
        fault_note = ""
        if s.n_shed or s.n_retries or s.n_failovers or n_degraded:
            fault_note = (f" shed={s.n_shed} retries={s.n_retries} "
                          f"failovers={s.n_failovers} degraded={n_degraded}")
        print(f"[serve] service mode: {len(lat_s)} queries x "
              f"{args.candidates} candidates, concurrency={args.concurrency}"
              f" | QPS={len(lat_s)/wall:.2f} p50={p50*1e3:.1f}ms "
              f"p99={p99*1e3:.1f}ms | batches={s.n_batches} "
              f"pack_fill={s.pack_fill:.2f} "
              f"join_dispatch={s.n_join_dispatch} "
              f"decode_dispatch={s.n_decode_dispatch} "
              f"h2d={s.h2d_bytes / 2**20:.2f}MiB "
              f"doc_hbm={s.doc_hbm_bytes / 2**20:.2f}MiB{cache_note}"
              f"{fault_note} | P@20={np.mean(p20):.3f}")
        if n_degraded and not faults.active():
            print(f"[serve] FAILED: {n_degraded} degraded responses with no "
                  f"fault plan installed", file=sys.stderr)
            sys.exit(1)
        return

    rr = Reranker(params, cfg, idx, micro_batch=args.micro_batch)
    p20 = []
    for qi in range(world.n_queries):
        cands = list(world.candidates(qi, k=args.candidates))
        q, qv = pack_query(world.queries[qi], cfg.max_query_len)
        ranked, scores, _ = rr.rerank(q, qv, cands)
        p20.append(precision_at_k(world.qrels[qi][np.asarray(ranked)], 20))
        if qi == 0 and world.n_queries > 1:
            rr.reset_stats()               # drop the jit-warmup query
    s = rr.stats
    n = max(1, s.n_requests)
    qenc, load, comb = s.query_encode_s / n, s.load_s / n, s.combine_s / n
    print(f"[serve] {n} queries x {args.candidates} candidates | "
          f"query={qenc*1e3:.1f}ms load={load*1e3:.1f}ms "
          f"combine={comb*1e3:.1f}ms total={(qenc+load+comb)*1e3:.1f}ms | "
          f"P@20={np.mean(p20):.3f}")


if __name__ == "__main__":
    main()
