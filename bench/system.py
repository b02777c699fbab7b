"""The system under test, built from a configuration file: the program's
ranker config, its index, and its one-chip service or four-chip router.

This is the only benchmark module that imports the program (``repro``,
from ``<checkout>/src``).  It takes the weights the benchmark made and the
documents the traffic generator made; everything it returns is the
program's own objects.
"""
from __future__ import annotations

import shutil
import sys
from pathlib import Path


def import_program(root: Path):
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro  # noqa: F401  (fails here when the sources are missing)


def program_config(cfg: dict):
    """The program's ``PreTTRConfig`` for a configuration file."""
    import jax.numpy as jnp

    from repro.core.prettr import PreTTRConfig, make_backbone

    kern = cfg["kernels"]
    bb = make_backbone(
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], l=cfg["split_layer"],
        max_len=cfg["max_position_embeddings"],
        compute_dtype=jnp.dtype(cfg["compute_dtype"]),
        remat_block=2, block_kv=128, attn_impl=kern["attn_impl"],
        compress_impl=kern["compress_impl"])
    return PreTTRConfig(backbone=bb, l=cfg["split_layer"],
                        max_query_len=cfg["max_query_len"],
                        max_doc_len=cfg["max_doc_len"],
                        compress_dim=cfg["compress_dim"],
                        store_dtype=jnp.dtype(cfg["store_dtype"]))


def build_index(out: Path, pcfg, params, docs, cfg: dict, n_shards: int):
    """Write the index of ``docs`` with the program's ``IndexBuilder`` and
    open it for serving."""
    from repro.index import IndexBuilder, TermRepIndex

    ix = cfg["index"]
    shutil.rmtree(out, ignore_errors=True)
    # no integrity checksums: the index is written and read back by this
    # process, and verifying it serves no request (set-up stays short)
    IndexBuilder(str(out), pcfg, params, codec=ix["codec"],
                 n_shards=n_shards, batch_size=cfg["serving"]["micro_batch"],
                 store_layer_kv=ix["store_layer_kv"],
                 kv_codec=ix.get("kv_codec"),
                 checksum_chunk_bytes=0).build(docs)
    return TermRepIndex.open(str(out), verify=False)


def build_service(pcfg, params, index, serving: dict, devices):
    """``RankingService`` on one chip, ``RankingRouter`` with one shard
    worker per chip on more."""
    from repro.serving import RankingRouter, RankingService

    knobs = dict(micro_batch=serving["micro_batch"],
                 doc_cache_mb=serving.get("doc_cache_mb") or 0.0,
                 page_tokens=serving.get("page_tokens"))
    if len(devices) == 1:
        return RankingService(params, pcfg, index, **knobs)
    return RankingRouter(params, pcfg, index, n_shards=len(devices),
                         devices=list(devices), **knobs)


def request(tokens, valid, doc_ids, rid: str):
    from repro.serving import RankRequest

    return RankRequest(tokens, valid, [int(d) for d in doc_ids],
                       request_id=rid)


def doc_caches(svc) -> list:
    """The device doc caches behind a service or router (may be empty)."""
    workers = getattr(svc, "workers", None)
    engines = ([w.engine for w in workers] if workers is not None
               else [svc.engine])
    return [e.doc_cache for e in engines if e.doc_cache is not None]


def stats_dict(svc) -> dict:
    import dataclasses

    return dataclasses.asdict(svc.stats)
