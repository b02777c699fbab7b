#!/usr/bin/env python3
"""Calibrate ``chip_smoke.py``'s limits: plant faults in its joins and
print how far each moves the scores, beside the sound readings.

    python benchmarks/smoke_faults.py            # on a TPU, the smoke's sizes
    JAX_PLATFORMS=cpu python benchmarks/smoke_faults.py --small   # rehearsal

Serves the smoke's own corpus through phase (a) (fp16 index, dense join)
and phase (b) (int8 layer-l K/V, paged doc cache), then through phase (b)
with one fault planted in the paged join's operands at a time, and phase
(a) with one in the dense join's.  Each reading compares as the smoke
does: (a) against the fp32 reference, (b) against sound (a).  Prints one
JSON line per reading (max and median |dscore|, worst-query top-10
overlap and Spearman) and writes them all to
``results/smoke_faults.json``.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import chip_smoke as cs  # noqa: E402

# faults in the paged join's operands: each takes and returns the dict
# {kd, vd, pt, dval, ks, vs} (pools, page table, validity, scale pools)
PAGED_FAULTS = {
    "K/V pages of the next row": lambda a, jnp: {
        **a, "pt": jnp.roll(a["pt"], 1, axis=0)},
    "half of every page's validity dropped": lambda a, jnp: {
        **a, "dval": a["dval"].at[:, a["dval"].shape[1] // 2:].set(0)},
    "K scales one token out of place": lambda a, jnp: {
        **a, "ks": jnp.roll(a["ks"], 1, axis=1)},
    "V from the neighbouring pool page": lambda a, jnp: {
        **a, "vd": jnp.roll(a["vd"], 1, axis=0)},
}


def readings(got, want) -> dict:
    import numpy as np

    d = np.abs(got - want)
    return {"max_abs": float(d.max()), "median_abs": float(np.median(d)),
            "top10_min": cs.top10_overlap(got, want),
            "spearman_min": cs.spearman(got, want)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--small", action="store_true",
                    help="smoke-config widths and a 40-doc corpus (CPU "
                         "rehearsal; the readings do not calibrate)")
    args = ap.parse_args()
    if not args.small:
        cs.require_tpu(1)

    import jax
    import jax.numpy as jnp

    import repro.models.backend as backend
    from repro.configs.prettr_bert import full_config, smoke_config
    from repro.core.prettr import init_prettr
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import RankingService

    enable_compile_cache()
    micro, cache_mb, page, tile = (cs.MICRO_BATCH, cs.DOC_CACHE_MB,
                                   cs.PAGE_TOKENS, 128)
    if args.small:
        micro, cache_mb, page, tile = 8, 4, 8, 8
        cfg = smoke_config(attn_impl="pallas", compress_impl="pallas")
        corpus = cs.make_corpus(args.seed, cfg.backbone.vocab_size,
                                n_docs=40, max_doc_len=cfg.max_doc_len,
                                n_queries=2, n_candidates=12)
    else:
        cfg = full_config(l=cs.SPLIT_L, compress_dim=cs.COMPRESS_DIM,
                          max_query_len=cs.MAX_QUERY_LEN,
                          max_doc_len=cs.MAX_DOC_LEN, attn_impl="pallas",
                          compress_impl="pallas")
        corpus = cs.make_corpus(args.seed, cfg.backbone.vocab_size)
    docs, queries, cands = corpus
    params, _ = init_prettr(jax.random.PRNGKey(args.seed), cfg)
    work = ROOT / "results" / "smoke_faults"
    idx_a, _ = cs.build_index(work / "fp16", cfg, params, docs,
                              codec="fp16", batch_size=micro)
    idx_b, _ = cs.build_index(work / "int8kv", cfg, params, docs,
                              codec="fp16", batch_size=micro,
                              store_layer_kv=True, kv_codec="int8")
    ref = cs.reference_scores(params, cfg, docs, queries, cands)

    def serve_a():
        svc = RankingService(params, cfg, idx_a, micro_batch=micro)
        return cs.serve(svc, queries, cands, cfg.max_query_len)[0]

    def serve_b():
        svc = RankingService(params, cfg, idx_b, micro_batch=micro,
                             doc_cache_mb=cache_mb, page_tokens=page)
        return cs.serve(svc, queries, cands, cfg.max_query_len)[0]

    rows = {"score std": float(ref.std())}

    def report(name, got, want):
        rows[name] = readings(got, want)
        print(json.dumps({name: rows[name]}), flush=True)

    sound_a = serve_a()
    report("sound a vs fp32", sound_a, ref)
    report("sound b vs a", serve_b(), sound_a)

    paged0 = backend.join_flash_attention_paged
    for name, fault in PAGED_FAULTS.items():
        def faulty(q, kq, vq, kd, vd, pt, dval, kq_valid=None,
                   kd_scale_pages=None, vd_scale_pages=None, _f=fault,
                   **kw):
            a = _f(dict(kd=kd, vd=vd, pt=pt, dval=dval, ks=kd_scale_pages,
                        vs=vd_scale_pages), jnp)
            return paged0(q, kq, vq, a["kd"], a["vd"], a["pt"], a["dval"],
                          kq_valid=kq_valid, kd_scale_pages=a["ks"],
                          vd_scale_pages=a["vs"], **kw)
        backend.join_flash_attention_paged = faulty
        try:
            report(f"b, {name}, vs a", serve_b(), sound_a)
        finally:
            backend.join_flash_attention_paged = paged0

    dense0 = backend.join_flash_attention

    def skip_tiles(q, kq, vq, kd, vd, kq_valid=None, kd_valid=None, **kw):
        if kd_valid is not None and kd.shape[2] > tile:
            kd_valid = kd_valid.at[:, tile:].set(False)
        return dense0(q, kq, vq, kd, vd, kq_valid=kq_valid,
                      kd_valid=kd_valid, **kw)
    backend.join_flash_attention = skip_tiles
    try:
        report("a, doc tiles past the first skipped, vs fp32", serve_a(),
               ref)
    finally:
        backend.join_flash_attention = dense0

    shutil.rmtree(work, ignore_errors=True)
    (ROOT / "results" / "smoke_faults.json").write_text(
        json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
