"""Offline index-build driver: corpus -> (optional compressor distillation)
-> encode -> sharded codec write -> verify.

The paper's indexing phase (Fig. 1 step 2) as a standalone CLI on top of
:class:`repro.index.IndexBuilder`:

.. code-block:: bash

    PYTHONPATH=src python -m repro.launch.build_index \\
        --out results/prettr_index_v2 --n-docs 512 \\
        --codec int8 --shards 8 --distill-steps 20 --verify

then serve it without rebuilding::

    PYTHONPATH=src python -m repro.launch.serve --service \\
        --load-index results/prettr_index_v2 --n-docs 512

The corpus, config and parameter seeds match ``launch/serve.py`` exactly,
so an index built here bit-matches the one ``serve`` would build inline
(pass the same ``--config`` / ``--l`` / ``--compress-dim`` / ``--n-docs``).
``--config base`` builds at the paper's BERT-base widths (seeded random
init; default l=6, e=256).

``--data-parallel`` gives every visible jax device ``--batch`` rows per
encode step (a ``("data",)`` mesh) — under ``XLA_FLAGS=--xla_force_host_platform_
device_count=8`` this exercises the same data-parallel path a TPU slice
uses, and the written shards are doc-for-doc identical to the single-host
build.  ``--distill-steps`` pre-trains the compression layer with the
paper's attention-MSE loss (Eq. 2) on CAR-style heading/paragraph pairs
before encoding.
"""
from __future__ import annotations

import argparse

import numpy as np

import jax
import jax.numpy as jnp


def distill_compressor(params, cfg, world, steps: int, seed: int = 0,
                       batch: int = 8):
    """Paper §4.2 stage 1: distill attention maps into the compressor
    (Eq. 2) on unlabeled CAR-style pairs; the backbone stays frozen."""
    from repro.core.compression import attention_mse_loss
    from repro.optim import OptimizerConfig, adam_update, init_opt_state

    comp = params["compressor"]
    opt_cfg = OptimizerConfig(lr=3e-3)
    opt = init_opt_state(comp, opt_cfg)
    rng = np.random.default_rng(seed)

    @jax.jit
    def step(comp, opt, tokens):
        loss, g = jax.value_and_grad(
            lambda c: attention_mse_loss(params["backbone"], c, cfg.backbone,
                                         tokens, l=cfg.l))(comp)
        comp, opt, _ = adam_update(g, opt, comp, opt_cfg, lr=opt_cfg.lr)
        return comp, opt, loss

    first = last = None
    for _ in range(steps):
        pairs = world.car_pairs(rng, batch, cfg.max_query_len,
                                cfg.max_doc_len)
        comp, opt, loss = step(comp, opt, jnp.asarray(pairs["tokens"]))
        first = first if first is not None else float(loss)
        last = float(loss)
    print(f"[build_index] distilled compressor {steps} steps: "
          f"attn-MSE {first:.3e} -> {last:.3e}")
    return comp


def main() -> None:
    from repro.configs.prettr_bert import CONFIGS, config
    from repro.core.prettr import init_prettr
    from repro.data.synthetic_ir import SyntheticIRWorld
    from repro.index import IndexBuilder, TermRepIndex, available_codecs, \
        verify_index
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.backend import impls_for

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="results/prettr_index",
                    help="index directory to create")
    ap.add_argument("--config", default="smoke", choices=sorted(CONFIGS),
                    help="model widths: smoke (4L d=64) or base (the "
                         "paper's BERT-base ranker, seeded random init)")
    ap.add_argument("--l", type=int, default=None,
                    help="join layer (default: the config's)")
    ap.add_argument("--compress-dim", type=int, default=None,
                    help="compression e (default: the config's)")
    ap.add_argument("--n-docs", type=int, default=512)
    ap.add_argument("--codec", default="fp16", choices=available_codecs())
    ap.add_argument("--shards", type=int, default=1,
                    help="number of shard-NNNNN/ output directories")
    ap.add_argument("--batch", type=int, default=64,
                    help="fixed encode batch shape (rows per device "
                         "under --data-parallel)")
    ap.add_argument("--backend", default="blocked",
                    choices=["plain", "blocked", "pallas"])
    ap.add_argument("--store-layer-kv", action="store_true",
                    help="also precompute + store the join layer's doc-side "
                         "K/V streams (layer_k/layer_v), letting the fused "
                         "query-time join skip all doc-side projections at "
                         "layer l")
    ap.add_argument("--kv-codec", default=None,
                    help="codec for the stored layer-l K/V streams "
                         "(requires --store-layer-kv; int8 dequantizes "
                         "in-register inside the join kernel)")
    ap.add_argument("--keep-frac", type=float, default=1.0,
                    help="index-time token pruning: keep this fraction of "
                         "each doc's highest-salience tokens, scored by "
                         "layer-l attention mass (1.0 = store every token)")
    ap.add_argument("--max-kept-tokens", type=int, default=0,
                    help="hard cap on kept tokens per doc (0 = no cap)")
    ap.add_argument("--distill-steps", type=int, default=0,
                    help="attention-MSE compressor distillation steps "
                         "before encoding (0 = keep the init compressor)")
    ap.add_argument("--data-parallel", action="store_true",
                    help="shard encode batches over all visible devices")
    ap.add_argument("--writer-depth", type=int, default=2,
                    help="device batches the overlapped writer may lag "
                         "(0 = synchronous writes)")
    ap.add_argument("--verify", action="store_true",
                    help="after the build: re-encode a doc sample and "
                         "compare the stored streams byte-for-byte")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    attn_impl, compress_impl = impls_for(args.backend)
    cfg = config(args.config, l=args.l, compress_dim=args.compress_dim,
                 attn_impl=attn_impl, compress_impl=compress_impl)
    world = SyntheticIRWorld(n_docs=args.n_docs,
                             vocab_size=cfg.backbone.vocab_size,
                             doc_len=cfg.max_doc_len - 2, seed=args.seed)
    params, _ = init_prettr(jax.random.PRNGKey(0), cfg)
    if args.distill_steps and cfg.compress_dim:
        params["compressor"] = distill_compressor(
            params, cfg, world, args.distill_steps, seed=args.seed)

    mesh = None
    if args.data_parallel:
        ndev = jax.device_count()
        if ndev > 1:
            mesh = jax.make_mesh((ndev,), ("data",))
            print(f"[build_index] data-parallel over {ndev} devices")
        else:
            print("[build_index] --data-parallel: one device visible, "
                  "running single-host")
    builder = IndexBuilder(args.out, cfg, params, codec=args.codec,
                           n_shards=args.shards, batch_size=args.batch,
                           mesh=mesh, writer_depth=args.writer_depth,
                           backend=args.backend,
                           store_layer_kv=args.store_layer_kv,
                           kv_codec=args.kv_codec,
                           keep_frac=args.keep_frac,
                           max_kept_tokens=args.max_kept_tokens)
    report = builder.build(list(world.docs))
    prune_note = ""
    if builder.prune:
        prune_note = (f" | pruned keep_frac={args.keep_frac} "
                      f"cap={builder.pruned_max_doc_len} tokens/doc")
    print(f"[build_index] {report.n_docs} docs / {report.n_tokens} tokens "
          f"-> {args.out} ({report.n_shards} shards, codec={report.codec}) | "
          f"{report.storage_bytes / 2**20:.2f} MiB "
          f"({report.bytes_per_doc:.0f} B/doc) | "
          f"encode={report.encode_s:.1f}s write={report.write_s:.1f}s "
          f"wall={report.wall_s:.1f}s{prune_note}")

    index = TermRepIndex.open(args.out)
    assert len(index) == report.n_docs
    if args.verify:
        n = verify_index(index, cfg, params, list(world.docs), sample=16,
                         seed=args.seed)
        print(f"[build_index] verify: {n} docs re-encoded, stored streams "
              f"byte-identical")


if __name__ == "__main__":
    main()
