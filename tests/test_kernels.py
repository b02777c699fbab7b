"""Kernel sweeps: every Pallas kernel vs its pure-jnp oracle, across shapes,
dtypes, and mask configurations (interpret mode on CPU)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.split_attention import (split_flash_attention,
                                           split_attention_ref)
from repro.kernels.decode_attention import (flash_decode_attention,
                                            decode_attention_ref)
from repro.kernels.fused_compress import (fused_compress, fused_decompress,
                                          compress_ref, decompress_ref)
from repro.kernels.embedding_bag import (embedding_bag_pallas_op,
                                         embedding_bag_ref)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,hq,hkv,sq,d,causal,window,boundary",
    [
        (2, 4, 2, 64, 32, False, -1, -1),     # GQA bidirectional
        (2, 4, 2, 64, 32, True, -1, -1),      # causal
        (1, 4, 4, 96, 64, True, 16, -1),      # sliding window
        (2, 2, 2, 64, 32, False, -1, 32),     # PreTTR split, tile-aligned
        (2, 2, 1, 80, 32, False, -1, 24),     # PreTTR split, off-tile
        (1, 8, 8, 48, 128, True, 8, -1),      # window + causal, d=128
    ])
def test_split_attention_sweep(b, hq, hkv, sq, d, causal, window, boundary,
                               dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, hq, sq, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, sq, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, sq, d), dtype)
    lengths = jnp.asarray([sq, sq - 10][:b], jnp.int32)
    out = split_flash_attention(q, k, v, lengths, causal=causal,
                                window=window, seg_boundary=boundary,
                                block_q=16, block_k=16)
    ref = split_attention_ref(q, k, v, lengths, causal=causal, window=window,
                              seg_boundary=boundary)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("boundary", [-1, 24])
def test_split_attention_k_valid(boundary):
    """Non-prefix k_valid (PreTTR's padded-query + padded-doc two-prefix
    pattern) must mask exactly, on top of the split boundary."""
    b, hq, hkv, sq, d = 2, 4, 2, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    q = jax.random.normal(ks[0], (b, hq, sq, d))
    k = jax.random.normal(ks[1], (b, hkv, sq, d))
    v = jax.random.normal(ks[2], (b, hkv, sq, d))
    pos = jnp.arange(sq)[None]
    # two valid prefixes: [0, q_len) and [24, 24 + d_len)
    q_len = jnp.asarray([[13], [24]])
    d_len = jnp.asarray([[30], [17]])
    k_valid = (pos < q_len) | ((pos >= 24) & (pos < 24 + d_len))
    out = split_flash_attention(q, k, v, None, k_valid,
                                seg_boundary=boundary,
                                block_q=16, block_k=16)
    lengths = jnp.asarray([54, 41], jnp.int32)   # last valid index + 1
    ref = split_attention_ref(q, k, v, lengths, k_valid,
                              seg_boundary=boundary)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hq,hkv,s,d,window", [
    (2, 8, 2, 256, 32, -1),
    (2, 8, 2, 256, 32, 64),
    (1, 4, 4, 512, 64, -1),
    (3, 16, 8, 128, 64, 32),
])
def test_decode_attention_sweep(b, hq, hkv, s, d, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, hq, 1, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    lengths = jnp.asarray([s, s // 2, s - 7][:b], jnp.int32)
    out = flash_decode_attention(q, k, v, lengths, window=window, block_k=64)
    ref = decode_attention_ref(q, k, v, lengths, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_decode_attention_k_valid():
    """Flash decode with a non-prefix k_valid mask (the CLS-only final
    layer's padded-segment layout)."""
    b, hq, hkv, s, d = 2, 4, 2, 128, 32
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (b, hq, 1, d))
    k = jax.random.normal(ks[1], (b, hkv, s, d))
    v = jax.random.normal(ks[2], (b, hkv, s, d))
    pos = jnp.arange(s)[None]
    k_valid = (pos < jnp.asarray([[40], [11]])) \
        | ((pos >= 64) & (pos < jnp.asarray([[100], [80]])))
    out = flash_decode_attention(q, k, v, None, k_valid, block_k=32)
    lengths = jnp.asarray([100, 80], jnp.int32)
    ref = decode_attention_ref(q, k, v, lengths, k_valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("t,d,e", [(100, 64, 16), (256, 768, 128),
                                   (33, 256, 384), (512, 768, 256)])
def test_fused_compress_sweep(t, d, e):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (t, d))
    w = jax.random.normal(ks[1], (d, e)) * 0.05
    b = jax.random.normal(ks[2], (e,)) * 0.1
    out = fused_compress(x, w, b, block_t=64)
    ref = compress_ref(x, w, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=1e-2,
                               atol=1e-2)
    wd = jax.random.normal(ks[3], (e, d)) * 0.05
    bd = jax.random.normal(ks[4], (d,)) * 0.1
    gamma, beta = jnp.ones((d,)), jnp.zeros((d,))
    o2 = fused_decompress(out, wd, bd, gamma, beta, out_dtype=jnp.float32,
                          block_t=64)
    r2 = decompress_ref(out, wd, bd, gamma, beta)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(r2), rtol=1e-4,
                               atol=1e-4)


def test_fused_decompress_matches_core_module():
    """Kernel output == repro.core.compression.decompress (the serving path
    swaps one for the other)."""
    from repro.core.compression import init_compressor, compress, decompress
    d, e = 64, 16
    comp, _ = init_compressor(jax.random.PRNGKey(0), d, e)
    x = jax.random.normal(jax.random.PRNGKey(1), (40, d))
    r = compress(comp, x)
    ref = decompress(comp, r, compute_dtype=jnp.float32)
    out = fused_decompress(r, comp["w_decomp"], comp["b_decomp"],
                           comp["ln"]["scale"], comp["ln"]["bias"],
                           out_dtype=jnp.float32, block_t=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref, np.float32),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("rows,dim,nb,nnz,mode", [
    (100, 16, 8, 4, "sum"),
    (1000, 128, 16, 7, "mean"),
    (64, 8, 3, 1, "sum"),
])
def test_embedding_bag_sweep(rows, dim, nb, nnz, mode):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    table = jax.random.normal(ks[0], (rows, dim))
    ids = jax.random.randint(ks[1], (nb, nnz), 0, rows)
    w = (jax.random.uniform(ks[2], (nb, nnz)) > 0.3).astype(jnp.float32)
    out = embedding_bag_pallas_op(table, ids, w, mode=mode)
    ref = embedding_bag_ref(table, ids, w, mode=mode)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


def test_f16_bits_widen_every_pattern():
    """The in-register fp16 -> f32 widening (the TPU's Pallas compiler has
    no fp16) is exact for all 65536 bit patterns."""
    from repro.kernels.tpu import f16_bits_to_f32
    h = np.arange(1 << 16, dtype=np.uint16)
    want = h.view(np.float16).astype(np.float32)
    got = np.asarray(f16_bits_to_f32(jnp.asarray(h)))
    nan = np.isnan(want)
    np.testing.assert_array_equal(np.isnan(got), nan)
    np.testing.assert_array_equal(got[~nan].view(np.uint32),
                                  want[~nan].view(np.uint32))


def test_f32_to_f16_bits_rounds_like_convert():
    """The in-register f32 -> fp16 narrowing rounds to nearest-even like
    XLA's convert: every fp16 value, its float neighbours, the halfway
    points between fp16 values, overflow and random magnitudes."""
    from repro.kernels.tpu import f32_to_f16_bits
    halves = np.arange(1 << 16, dtype=np.uint16).view(np.float16)
    vals = halves[np.isfinite(halves)].astype(np.float32)
    vals = np.sort(np.unique(vals))
    mids = ((vals[:-1].astype(np.float64) + vals[1:]) / 2).astype(np.float32)
    rng = np.random.default_rng(0)
    x = np.concatenate([
        vals, mids, np.nextafter(vals, np.float32(np.inf)),
        np.nextafter(vals, np.float32(-np.inf)),
        np.float32([65519.99, 65520.0, 65536.0, 1e30, -1e30, np.inf,
                    -np.inf, -0.0]),
        *(rng.standard_normal(20000).astype(np.float32) * s
          for s in (1e-7, 1e-5, 1e-2, 1.0, 1e3, 6e4))])
    with np.errstate(over="ignore"):
        want = x.astype(np.float16).view(np.uint16)
    got = np.asarray(f32_to_f16_bits(jnp.asarray(x))).astype(np.uint16)
    np.testing.assert_array_equal(got, want)
    assert int(f32_to_f16_bits(jnp.float32(np.nan))) & 0x7FFF == 0x7E00


@pytest.mark.parametrize("t,e,d", [(40, 16, 64), (300, 32, 128)])
def test_fused_compress_fp16_bits_match_convert(t, e, d):
    """fp16 store and fp16 load cross the kernel as uint16 bits: the
    stored bytes equal the f32 kernel output converted by XLA, and
    decompressing them equals decompressing their f32 widening."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(ks[0], (t, d))
    w = jax.random.normal(ks[1], (d, e)) / np.sqrt(d)
    b = jax.random.normal(ks[2], (e,)) * 0.1
    got = fused_compress(x, w, b, out_dtype=jnp.float16)
    assert got.dtype == jnp.float16
    want = fused_compress(x, w, b, out_dtype=jnp.float32).astype(jnp.float16)
    np.testing.assert_array_equal(np.asarray(got).view(np.uint16),
                                  np.asarray(want).view(np.uint16))
    wd = jax.random.normal(ks[0], (e, d)) / np.sqrt(e)
    bd, g, beta = jnp.zeros(d), jnp.ones(d), jnp.zeros(d)
    out = fused_decompress(got, wd, bd, g, beta, out_dtype=jnp.float32)
    ref = fused_decompress(got.astype(jnp.float32), wd, bd, g, beta,
                           out_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
