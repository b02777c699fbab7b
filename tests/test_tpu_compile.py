"""The main-path Pallas kernels compile for a TPU v5e at BERT-base widths.

Interpret mode (every other kernel test) never checks what the TPU's
compiler refuses: block shapes off the native tiling, dtypes Mosaic cannot
load, scratch beyond VMEM.  These tests compile each kernel for a
*described* ``v5e:2x2`` topology — no chip attached, nothing runs — and
assert that the lowered program holds the kernel (``tpu_custom_call``).

Widths: B=32 candidates, 12 heads of 64, query segment 32, doc segment 480
(the kernels pad it to 512), e=256, bf16 compute and fp16 storage.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, H, D, LQ, LD, E, DM = 32, 12, 64, 32, 480, 256, 768
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:            # no libtpu / topology here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("sq", [LQ + LD, 1], ids=["joint", "cls_row"])
@pytest.mark.parametrize("kv", ["bf16", "fp16", "int8"])
def test_join_dense_compiles(one_chip, sq, kv):
    from repro.kernels.join_attention import join_flash_attention
    kv_dt = {"bf16": BF16, "fp16": jnp.float16, "int8": jnp.int8}[kv]
    shapes = [((B, H, sq, D), BF16), ((B, H, LQ, D), BF16),
              ((B, H, LQ, D), BF16), ((B, H, LD, D), kv_dt),
              ((B, H, LD, D), kv_dt), ((B, LQ), jnp.bool_),
              ((B, LD), jnp.bool_)]
    if kv == "int8":
        shapes += [((B, LD), jnp.float32), ((B, LD), jnp.float32)]
    _compile(lambda *a: join_flash_attention(*a, interpret=False),
             one_chip, *shapes)


def _paged_shapes(pool, page, kv):
    """Operands of the paged join as the doc cache holds them: token-major
    [P, page, H * D] K/V pools (fp16 as its uint16 bits), [P, page] int8
    validity and fp32 scale pools."""
    kv_dt = jnp.int8 if kv == "int8" else jnp.uint16
    shapes = [((B, H, 1, D), BF16), ((B, H, LQ, D), BF16),
              ((B, H, LQ, D), BF16), ((pool, page, H * D), kv_dt),
              ((pool, page, H * D), kv_dt), ((B, -(-LD // page)), jnp.int32),
              ((pool, page), jnp.int8), ((B, LQ), jnp.bool_)]
    if kv == "int8":
        shapes += [((pool, page), jnp.float32), ((pool, page), jnp.float32)]
    return shapes


@pytest.mark.parametrize("page", [32, 128, LD])
@pytest.mark.parametrize("kv", ["fp16", "int8"])
def test_join_paged_compiles(one_chip, page, kv):
    """Paged int8 (the doc cache's quantized pools) and fp16 (raw fp16
    index bytes) at page sizes from a small page to whole-doc slots."""
    from repro.kernels.join_attention import join_flash_attention_paged
    pool = 2 + B * -(-LD // page)
    _compile(lambda *a: join_flash_attention_paged(*a, interpret=False),
             one_chip, *_paged_shapes(pool, page, kv))


@pytest.mark.parametrize("layout", ["joint", "doc_only"])
def test_split_attention_compiles(one_chip, layout):
    """Layers 0..l: the joint 32+480 forward with the split mask at the
    segment boundary, and the index-time doc-only pass."""
    from repro.kernels.split_attention import split_flash_attention
    s = LQ + LD if layout == "joint" else LD
    boundary = LQ if layout == "joint" else -1
    qkv = ((B, H, s, D), BF16)
    _compile(lambda q, k, v, valid: split_flash_attention(
        q, k, v, None, k_valid=valid, seg_boundary=boundary,
        interpret=False), one_chip, qkv, qkv, qkv, ((B, s), jnp.bool_))


def test_decode_attention_compiles(one_chip):
    """The legacy CLS-only final layer (one row against the joint K/V)."""
    from repro.kernels.decode_attention import flash_decode_attention
    kv = ((B, H, LQ + LD, D), BF16)
    _compile(lambda q, k, v, valid: flash_decode_attention(
        q, k, v, None, k_valid=valid, interpret=False),
        one_chip, ((B, H, 1, D), BF16), kv, kv, ((B, LQ + LD), jnp.bool_))


def test_fused_compress_compiles(one_chip):
    """Index time: bf16 layer-l reps -> fp16 stored e=256 reps."""
    from repro.kernels.fused_compress import fused_compress
    _compile(lambda x, w, b: fused_compress(x, w, b, out_dtype=jnp.float16,
                                            interpret=False),
             one_chip, ((B, LD, DM), BF16), ((DM, E), jnp.float32),
             ((E,), jnp.float32))


def test_fused_decompress_compiles(one_chip):
    """Query time: fp16 stored reps -> bf16 join input, LayerNorm'd."""
    from repro.kernels.fused_compress import fused_decompress
    _compile(lambda r, w, b, g, beta: fused_decompress(
        r, w, b, g, beta, out_dtype=BF16, interpret=False),
        one_chip, ((B, LD, E), jnp.float16), ((E, DM), jnp.float32),
        ((DM,), jnp.float32), ((DM,), jnp.float32), ((DM,), jnp.float32))


#: pool pages of the l = 11 int8 K/V doc cache at 3,072 MiB and 128-token
#: pages (the benchmark's hot cell)
POOL_PAGES = 12234


def _pool_copies(text: str) -> list[str]:
    """``copy`` instructions of a compiled program whose result has a
    whole pool's leading dim: a relayout of a cache pool."""
    return [ln.strip()[:200] for ln in text.splitlines()
            if " copy(" in ln
            and re.search(rf"\[{POOL_PAGES},[\d,]*\]\S* copy\(", ln)]


@pytest.mark.parametrize("kv", ["int8", "fp16"])
def test_paged_scoring_no_pool_copy(one_chip, monkeypatch, kv):
    """At the hot cell's pool size, the paged join and the whole pool-fused
    scoring step (``jit__pool_score``) read the doc cache's pools where
    they lie: no pool is copied, relaid out or converted on a call, so
    the compiled programs hold almost no temporaries."""
    import sys

    from repro.configs.prettr_bert import full_config
    from repro.core.prettr import init_prettr
    from repro.index.codecs import get_codec
    from repro.kernels.join_attention import join_flash_attention_paged
    from repro.serving.service import encode_query_jit, pool_score_fn

    # the kernel wrappers ask for interpret mode off the chip; this
    # process compiles for the described chip, so steer them to Mosaic
    for name, mod in list(sys.modules.items()):
        if name.startswith("repro.kernels.") and hasattr(mod,
                                                         "interpret_mode"):
            monkeypatch.setattr(mod, "interpret_mode", lambda: False)

    page = 128
    shapes = _paged_shapes(POOL_PAGES, page, kv)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    kernel = jax.jit(lambda *a: join_flash_attention_paged(
        *a, interpret=False)).lower(*args).compile()

    cfg = full_config(l=11, attn_impl="pallas", compress_impl="pallas")
    codec, kv_codec = get_codec("fp16"), get_codec(kv)
    pools = {}
    for name, (dt, row) in {**codec.streams(cfg.compress_dim),
                            **kv_codec.stream_group("layer_k", DM),
                            **kv_codec.stream_group("layer_v", DM)}.items():
        dt = jnp.uint16 if dt == jnp.float16 else dt    # fp16 pools: bits
        pools[name] = jax.ShapeDtypeStruct((POOL_PAGES, page) + row, dt,
                                           sharding=one_chip)

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda k: init_prettr(k, cfg)[0], jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((B, LQ), jnp.int32, sharding=one_chip)
    qv = jax.ShapeDtypeStruct((B, LQ), jnp.bool_, sharding=one_chip)
    qr = on_chip(jax.eval_shape(encode_query_jit(cfg), params, tokens, qv))
    step = jax.jit(pool_score_fn(
        cfg, codec, list(codec.streams(cfg.compress_dim)),
        None if kv_codec.decode_is_identity else kv_codec))
    lowered = step.lower(params, qr, qv, pools, args[6], args[5])
    assert "jit__pool_score" in lowered.as_text()
    score = lowered.compile()
    for compiled in (kernel, score):
        text = compiled.as_text()
        assert "join_flash_attention_paged" in text
        assert _pool_copies(text) == []
        assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20
