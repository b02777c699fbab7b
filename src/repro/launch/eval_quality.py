"""Quality-evaluation driver: run the full retrieval cascade — synthetic
corpus -> codec-encoded index build -> pooled first-stage top-k ->
packed-service rerank — and report IR metrics for both stages.

This is the operational entry point for the quality loop (paper §6: any
storage codec or join-layer choice must not come "with a substantial
degradation in ranking performance").  One invocation evaluates one
operating point::

    PYTHONPATH=src python -m repro.launch.eval_quality \\
        --codec int8 --l 2 --k 32 --steps 40

``--sweep`` evaluates every codec at the given ``l`` in one process,
sharing the trained ranker (codecs only change storage, never training).
``--json PATH`` dumps per-stage metrics + run metadata for scripting.
The CI regression gate lives in ``benchmarks/quality.py``, which wraps
the same :func:`repro.eval.run_cascade` at pinned seeds and sizes and
diffs against the committed ``BENCH_quality.json``.
"""
from __future__ import annotations

import argparse
import json
import time


def _train(params, cfg, world, *, steps: int, batch: int, lr: float,
           seed: int):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core.prettr import rank_pairs_loss
    from repro.optim import OptimizerConfig, adam_update, init_opt_state

    opt_cfg = OptimizerConfig(lr=lr)
    opt = init_opt_state(params, opt_cfg)
    rng = np.random.default_rng(seed)

    @jax.jit
    def step(params, opt, pos, neg):
        loss, g = jax.value_and_grad(
            lambda p: rank_pairs_loss(p, cfg, pos, neg))(params)
        params, opt, _ = adam_update(g, opt, params, opt_cfg, lr=lr)
        return params, opt, loss

    loss = float("nan")
    for _ in range(steps):
        pos, neg = world.pair_batch(rng, batch, cfg.max_query_len,
                                    cfg.max_doc_len)
        params, opt, loss = step(params, opt,
                                 jax.tree.map(jnp.asarray, pos),
                                 jax.tree.map(jnp.asarray, neg))
    return params, float(loss)


def main() -> None:
    import jax

    from repro.configs.prettr_bert import smoke_config
    from repro.core.prettr import init_prettr
    from repro.data.synthetic_ir import SyntheticIRWorld
    from repro.eval.cascade import run_cascade
    from repro.index import available_codecs
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser(
        description="end-to-end cascade quality evaluation")
    ap.add_argument("--l", type=int, default=2, help="join layer")
    ap.add_argument("--codec", default="fp16", choices=available_codecs())
    ap.add_argument("--sweep", action="store_true",
                    help="evaluate every codec at this --l (one training)")
    ap.add_argument("--k", type=int, default=32,
                    help="first-stage candidate pool depth")
    ap.add_argument("--k-metric", type=int, default=10,
                    help="metric cutoff (mrr@k, ndcg@k, ...)")
    ap.add_argument("--n-docs", type=int, default=256)
    ap.add_argument("--n-queries", type=int, default=16)
    ap.add_argument("--seed", type=int, default=3, help="world seed")
    ap.add_argument("--train-seed", type=int, default=7)
    ap.add_argument("--steps", type=int, default=40,
                    help="ranker training steps (0 = untrained params)")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--compress-dim", type=int, default=0)
    ap.add_argument("--shards", type=int, default=1)
    ap.add_argument("--store-layer-kv", action="store_true",
                    help="store + serve the join layer's doc-side K/V "
                         "streams (the fused-join serving configuration)")
    ap.add_argument("--kv-codec", default=None,
                    help="codec for the stored layer-l K/V streams "
                         "(requires --store-layer-kv) — evaluates the "
                         "int8-KV operating point serving actually ships")
    ap.add_argument("--keep-frac", type=float, default=1.0,
                    help="index-time token pruning: keep this fraction of "
                         "each doc's highest-salience tokens (1.0 = off)")
    ap.add_argument("--max-kept-tokens", type=int, default=0,
                    help="hard cap on kept tokens per doc (0 = no cap)")
    ap.add_argument("--pool", default="mean", choices=["mean", "cls"],
                    help="first-stage doc pooling over stored term reps")
    ap.add_argument("--backend", default=None,
                    choices=["plain", "blocked", "pallas"],
                    help="compute backend override for every stage")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also dump metrics + metadata as JSON")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = smoke_config(l=args.l, compress_dim=args.compress_dim)
    world = SyntheticIRWorld(n_docs=args.n_docs, n_queries=args.n_queries,
                             vocab_size=cfg.backbone.vocab_size,
                             doc_len=cfg.max_doc_len - 4, seed=args.seed)
    params, _ = init_prettr(jax.random.PRNGKey(args.train_seed), cfg)
    if args.steps:
        t0 = time.time()
        params, loss = _train(params, cfg, world, steps=args.steps,
                              batch=args.batch, lr=args.lr,
                              seed=args.train_seed)
        print(f"[eval_quality] trained {args.steps} steps in "
              f"{time.time()-t0:.1f}s, final loss {loss:.4f}")

    codecs = available_codecs() if args.sweep else [args.codec]
    dump = []
    for codec in codecs:
        t0 = time.time()
        res = run_cascade(params, cfg, world, codec=codec, k=args.k,
                          k_metric=args.k_metric, n_shards=args.shards,
                          pool=args.pool, backend=args.backend,
                          store_layer_kv=args.store_layer_kv,
                          kv_codec=args.kv_codec,
                          keep_frac=args.keep_frac,
                          max_kept_tokens=args.max_kept_tokens)
        dt = time.time() - t0
        print(f"[eval_quality] codec={codec} l={args.l} k={args.k} "
              f"({dt:.1f}s incl. index build)")
        for stage, metrics in (("first_stage", res.first_stage),
                               ("rerank", res.rerank)):
            line = " ".join(f"{m}={v:.4f}" for m, v in metrics.items())
            print(f"  {stage:>11}: {line}")
        dump.append({"first_stage": dict(res.first_stage),
                     "rerank": dict(res.rerank), "meta": dict(res.meta)})

    if args.json:
        with open(args.json, "w") as f:
            json.dump(dump if args.sweep else dump[0], f, indent=1)
            f.write("\n")
        print(f"[eval_quality] wrote {args.json}")


if __name__ == "__main__":
    main()
