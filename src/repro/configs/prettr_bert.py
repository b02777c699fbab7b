"""The paper's own model: Vanilla BERT-base PreTTR ranker (§5.2).
12L d_model=768 12H d_ff=3072 vocab=30522, split at l (swept 1..11 in the
benchmarks), compression e in {384, 256, 128}."""
import jax.numpy as jnp

from repro.configs import ArchSpec, LM_SHAPES
from repro.core.prettr import PreTTRConfig, make_backbone


def full_config(l: int = 6, compress_dim: int = 256,
                max_query_len: int = 32, max_doc_len: int = 480,
                attn_impl: str = "blocked",
                compress_impl: str = "plain") -> PreTTRConfig:
    return PreTTRConfig(
        backbone=make_backbone(
            n_layers=12, d_model=768, n_heads=12, d_ff=3072,
            vocab_size=30522, l=l, max_len=max_query_len + max_doc_len,
            compute_dtype=jnp.bfloat16, remat_block=2, block_kv=128,
            attn_impl=attn_impl, compress_impl=compress_impl),
        l=l, max_query_len=max_query_len, max_doc_len=max_doc_len,
        compress_dim=compress_dim)


def smoke_config(l: int = 2, compress_dim: int = 16,
                 attn_impl: str = "blocked",
                 compress_impl: str = "plain") -> PreTTRConfig:
    return PreTTRConfig(
        backbone=make_backbone(
            n_layers=4, d_model=64, n_heads=4, d_ff=128, vocab_size=512,
            l=l, max_len=48, compute_dtype=jnp.float32, remat_block=2,
            block_kv=16, attn_impl=attn_impl, compress_impl=compress_impl),
        l=l, max_query_len=8, max_doc_len=40, compress_dim=compress_dim)


#: the entry points' ``--config`` choices: ``base`` is the paper's ranker
#: at its published widths, ``smoke`` the 4-layer d=64 toy for CPU runs
CONFIGS = {"smoke": smoke_config, "base": full_config}


def config(name: str, **overrides) -> PreTTRConfig:
    """``CONFIGS[name]`` with ``overrides``; a ``None`` override keeps the
    config's own default (an unset command-line flag)."""
    return CONFIGS[name](**{k: v for k, v in overrides.items()
                            if v is not None})


def spec() -> ArchSpec:
    return ArchSpec(
        name="prettr-bert", family="prettr", config=full_config(),
        smoke=smoke_config(), shapes=LM_SHAPES,
        skip_shapes=("train_4k", "prefill_32k", "decode_32k", "long_500k"),
        notes="The paper's own ranker; exercised via the PreTTR benchmarks "
              "and its own dry-run cells (rank/index/serve).")
