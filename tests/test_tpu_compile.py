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


@pytest.mark.parametrize("page", [32, 128, LD])
@pytest.mark.parametrize("kv", ["fp16", "int8"])
def test_join_paged_compiles(one_chip, page, kv):
    """Paged int8 (the doc cache's quantized pools) and fp16 (raw fp16
    index bytes) at page sizes from a small page to whole-doc slots."""
    from repro.kernels.join_attention import join_flash_attention_paged
    kv_dt = jnp.int8 if kv == "int8" else jnp.float16
    n_p = -(-LD // page)
    pool = 2 + B * n_p
    shapes = [((B, H, 1, D), BF16), ((B, H, LQ, D), BF16),
              ((B, H, LQ, D), BF16), ((pool, H, page, D), kv_dt),
              ((pool, H, page, D), kv_dt), ((B, n_p), jnp.int32),
              ((pool, page), jnp.int8), ((B, LQ), jnp.bool_)]
    if kv == "int8":
        shapes += [((pool, page, 1), jnp.float32),
                   ((pool, page, 1), jnp.float32)]
    _compile(lambda *a: join_flash_attention_paged(*a, interpret=False),
             one_chip, *shapes)


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
