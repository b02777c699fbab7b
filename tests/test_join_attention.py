"""The fused query-time join: split-KV join-attention kernel, the
JoinState dispatch in ``join_and_score``, stored layer-l K/V streams, and
the device-resident hot-doc cache.

The load-bearing invariants:

* kernel == oracle across shapes/GQA/validity (interpret mode on CPU);
* fused ``join_and_score`` is **bit-exact** vs the legacy concat path
  under the reference backends (plain/blocked) — under pallas the two
  paths run genuinely different kernels and agree to kernel tolerance;
* stored layer-l K/V streams reproduce the recomputed projections
  (bit-exact at fp32 storage, storage-rounding tolerance at fp16);
* the hot-doc cache returns bit-identical scores hit-vs-miss, and a
  packed drain issues exactly one scoring jit entry per micro-batch.
"""
import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.prettr import (PreTTRConfig, encode_query, init_prettr,
                               join_and_score, make_backbone,
                               precompute_doc_kv, precompute_docs,
                               rank_forward)
from repro.kernels.join_attention import (join_attention_ref,
                                          join_flash_attention)
from repro.models.backend import get_impl, impls_for

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

BACKENDS = ["plain", "blocked", "pallas"]
MAX_Q, MAX_D = 8, 24


# ---------------------------------------------------------------------------
# Kernel vs oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hq,hkv,sq,lq,ld,d", [
    (2, 4, 2, 32, 8, 24, 32),     # GQA, joint-shaped q
    (2, 2, 2, 24, 8, 96, 64),     # doc-segment-shaped q, multi-tile docs
    (1, 4, 1, 1, 16, 48, 32),     # CLS row (Sq=1), MQA
    (3, 8, 4, 40, 32, 8, 16),     # long query segment, short docs
])
def test_join_kernel_vs_oracle(b, hq, hkv, sq, lq, ld, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (b, hq, sq, d), dtype)
    kq = jax.random.normal(ks[1], (b, hkv, lq, d), dtype)
    vq = jax.random.normal(ks[2], (b, hkv, lq, d), dtype)
    kd = jax.random.normal(ks[3], (b, hkv, ld, d), dtype)
    vd = jax.random.normal(ks[4], (b, hkv, ld, d), dtype)
    kqv = jnp.arange(lq)[None] < jnp.asarray([[lq], [lq - 3], [5]][:b])
    kdv = jnp.arange(ld)[None] < jnp.asarray([[ld], [ld - 5], [1]][:b])
    out = join_flash_attention(q, kq, vq, kd, vd, kqv, kdv,
                               block_q=16, block_k=16)
    ref = join_attention_ref(q, kq, vq, kd, vd, kqv, kdv)
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol)


def test_join_kernel_non_prefix_valid():
    """Non-prefix doc validity (holes) must mask exactly; the doc-segment
    tile-skip bound derives from the last valid index."""
    b, hq, hkv, sq, lq, ld, d = 2, 4, 2, 16, 8, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(ks[0], (b, hq, sq, d))
    kq = jax.random.normal(ks[1], (b, hkv, lq, d))
    vq = jax.random.normal(ks[2], (b, hkv, lq, d))
    kd = jax.random.normal(ks[3], (b, hkv, ld, d))
    vd = jax.random.normal(ks[4], (b, hkv, ld, d))
    pos = jnp.arange(ld)[None]
    kdv = ((pos < jnp.asarray([[10], [3]]))
           | ((pos >= 32) & (pos < jnp.asarray([[50], [33]]))))
    kqv = jnp.arange(lq)[None] < jnp.asarray([[6], [8]])
    out = join_flash_attention(q, kq, vq, kd, vd, kqv, kdv,
                               block_q=8, block_k=16)
    ref = join_attention_ref(q, kq, vq, kd, vd, kqv, kdv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _quant_world(b, hq, hkv, sq, lq, ld, d, seed=11):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    q = jax.random.normal(ks[0], (b, hq, sq, d))
    kq = jax.random.normal(ks[1], (b, hkv, lq, d))
    vq = jax.random.normal(ks[2], (b, hkv, lq, d))
    kd_q = jax.random.randint(ks[3], (b, hkv, ld, d), -127, 128,
                              dtype=jnp.int8)
    vd_q = jax.random.randint(ks[4], (b, hkv, ld, d), -127, 128,
                              dtype=jnp.int8)
    kd_s = jax.random.uniform(ks[5], (b, ld), minval=1e-3, maxval=0.05)
    vd_s = jax.random.uniform(ks[6], (b, ld), minval=1e-3, maxval=0.05)
    return q, kq, vq, kd_q, vd_q, kd_s, vd_s


def test_join_kernel_int8_in_kernel_dequant_bit_exact():
    """The tentpole equivalence: dequantizing int8 doc K/V *inside* the
    KV-tile loop must be bit-exact vs the separate-dispatch reference
    (decode the whole stream, then run the float kernel) — same f32
    multiply on the same bytes, just moved into registers."""
    from repro.kernels.join_attention import (dequantize_kv,
                                              join_attention_ref_quant)
    b, hq, hkv, sq, lq, ld, d = 2, 4, 2, 16, 8, 48, 32
    q, kq, vq, kd_q, vd_q, kd_s, vd_s = _quant_world(b, hq, hkv, sq, lq,
                                                     ld, d)
    kqv = jnp.arange(lq)[None] < jnp.asarray([[6], [8]])
    kdv = jnp.arange(ld)[None] < jnp.asarray([[48], [29]])
    fused = join_flash_attention(q, kq, vq, kd_q, vd_q, kqv, kdv,
                                 kd_scales=kd_s, vd_scales=vd_s,
                                 block_q=16, block_k=16)
    two_pass = join_flash_attention(q, kq, vq,
                                    dequantize_kv(kd_q, kd_s),
                                    dequantize_kv(vd_q, vd_s),
                                    kqv, kdv, block_q=16, block_k=16)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(two_pass))
    ref = join_attention_ref_quant(q, kq, vq, kd_q, vd_q, kd_s, vd_s,
                                   kq_valid=kqv, kd_valid=kdv)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _paginate(kd, vd, kdv, page, kd_s=None, vd_s=None):
    """Pack dense [B, Hkv, Ld, D] doc K/V into the cache's token-major page
    pools ([P, page, Hkv * D] K/V, [P, page] validity and scales) with
    page 0 reserved all-zero; rows keep all their pages (dense table) so
    the paged kernel sees the same assembled positions as the dense
    kernel."""
    b, hkv, ld, d = kd.shape
    n_p = ld // page

    def _pages(x, row):             # [B, nP * page, *row] -> [B * nP, page, *row]
        x = np.asarray(x).reshape((b * n_p, page) + row)
        return np.concatenate([np.zeros_like(x[:1]), x])

    def _kv(x):                     # [B, Hkv, Ld, D] -> token rows
        return jnp.asarray(_pages(np.moveaxis(np.asarray(x), 1, 2),
                                  (hkv * d,)))

    pt = jnp.arange(1, 1 + b * n_p, dtype=jnp.int32).reshape(b, n_p)
    out = [_kv(kd), _kv(vd), pt,
           jnp.asarray(_pages(np.asarray(kdv, np.int32), ()))]
    if kd_s is not None:
        out += [jnp.asarray(_pages(np.asarray(s, np.float32), ()))
                for s in (kd_s, vd_s)]
    return out


@pytest.mark.parametrize("quant", [False, True])
def test_join_kernel_paged_vs_dense(quant):
    """The paged kernel walking a page table over pool tiles computes the
    same attention as the dense kernel on the assembled rows — bit-exact
    when the dense doc tile equals the page size (same accumulation
    order), quantized or not."""
    from repro.kernels.join_attention import (join_attention_ref_paged,
                                              join_flash_attention_paged)
    b, hq, hkv, sq, lq, ld, d, page = 2, 4, 2, 16, 8, 48, 32, 16
    q, kq, vq, kd_q, vd_q, kd_s, vd_s = _quant_world(b, hq, hkv, sq, lq,
                                                     ld, d, seed=13)
    kqv = jnp.arange(lq)[None] < jnp.asarray([[6], [8]])
    # row 1's last page is entirely invalid — its table entry still points
    # at a real (stale) page, which validity alone must mask
    kdv = jnp.arange(ld)[None] < jnp.asarray([[41], [page * 2]])
    if quant:
        kd, vd = kd_q, vd_q
        scales = dict(kd_scales=kd_s, vd_scales=vd_s)
        kd_pg, vd_pg, pt, dval_pg, ks_pg, vs_pg = _paginate(
            kd, vd, kdv, page, kd_s, vd_s)
        spools = dict(kd_scale_pages=ks_pg, vd_scale_pages=vs_pg)
    else:
        kd = jax.random.normal(jax.random.PRNGKey(5), (b, hkv, ld, d))
        vd = jax.random.normal(jax.random.PRNGKey(6), (b, hkv, ld, d))
        scales, spools = {}, {}
        kd_pg, vd_pg, pt, dval_pg = _paginate(kd, vd, kdv, page)
    paged = join_flash_attention_paged(q, kq, vq, kd_pg, vd_pg, pt,
                                       dval_pg, kqv, block_q=16, **spools)
    dense = join_flash_attention(q, kq, vq, kd, vd, kqv, kdv,
                                 block_q=16, block_k=page, **scales)
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(dense))
    ref = join_attention_ref_paged(
        q, kq, vq, kd_pg, vd_pg, pt, dval_pg, kq_valid=kqv, **spools)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_join_kernel_paged_zero_page_tail():
    """Short docs point their page-table tail at the reserved zero page;
    the assembled row must score identically to a dense row zero-padded
    to the same length."""
    from repro.kernels.join_attention import join_flash_attention_paged
    b, hq, hkv, sq, lq, ld, d, page = 1, 2, 1, 8, 8, 32, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(21), 5)
    q = jax.random.normal(ks[0], (b, hq, sq, d))
    kq = jax.random.normal(ks[1], (b, hkv, lq, d))
    vq = jax.random.normal(ks[2], (b, hkv, lq, d))
    kd = jax.random.normal(ks[3], (b, hkv, ld, d))
    vd = jax.random.normal(ks[4], (b, hkv, ld, d))
    kqv = jnp.ones((b, lq), bool)
    kdv = jnp.arange(ld)[None] < 13          # only the first page is real
    kd_pg, vd_pg, pt, dval_pg = _paginate(kd, vd, kdv, page)
    # drop the second page from the table: tail -> zero page 0
    pt_short = pt.at[0, 1].set(0)
    paged = join_flash_attention_paged(q, kq, vq, kd_pg, vd_pg, pt_short,
                                       dval_pg, kqv, block_q=8)
    dense = join_flash_attention(
        q, kq, vq,
        jnp.where(kdv[:, None, :, None], kd, 0),
        jnp.where(kdv[:, None, :, None], vd, 0),
        kqv, kdv, block_q=8, block_k=page)
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(dense))


@pytest.mark.parametrize("backend", BACKENDS)
def test_join_backend_impls_vs_oracle(backend):
    """Every registered join_attention impl computes the same attention
    (the reference impls via concat + the regular cores, pallas via the
    split kernel)."""
    b, hq, hkv, lq, ld, d = 2, 4, 2, 8, 24, 16
    cfg = make_backbone(n_layers=2, d_model=hq * d, n_heads=hq, d_ff=32,
                        vocab_size=64, l=0, max_len=64, n_kv_heads=hkv,
                        block_kv=16)
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    # model layout [B, S, H, D]
    q = jax.random.normal(ks[0], (b, lq + ld, hq, d))
    kq = jax.random.normal(ks[1], (b, lq, hkv, d))
    vq = jax.random.normal(ks[2], (b, lq, hkv, d))
    kd = jax.random.normal(ks[3], (b, ld, hkv, d))
    vd = jax.random.normal(ks[4], (b, ld, hkv, d))
    kqv = jnp.arange(lq)[None] < jnp.asarray([[6], [8]])
    kdv = jnp.arange(ld)[None] < jnp.asarray([[24], [11]])
    out = get_impl("join_attention", backend)(
        q, kq, vq, kd, vd, cfg=cfg, scale=1.0 / np.sqrt(d),
        q_valid=jnp.ones((b, lq + ld), bool), kq_valid=kqv, kd_valid=kdv)
    ref = join_attention_ref(q.transpose(0, 2, 1, 3),
                             kq.transpose(0, 2, 1, 3),
                             vq.transpose(0, 2, 1, 3),
                             kd.transpose(0, 2, 1, 3),
                             vd.transpose(0, 2, 1, 3), kqv, kdv)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.transpose(0, 2, 1, 3)),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Fused join == legacy concat join (the PR's central equivalence)
# ---------------------------------------------------------------------------


def _cfg(l=2, compress_dim=0, store_dtype=jnp.float32, backend="blocked",
         n_kv_heads=None):
    attn_impl, compress_impl = impls_for(backend)
    bb = make_backbone(n_layers=4, d_model=64, n_heads=4, d_ff=128,
                       vocab_size=512, l=l, max_len=64,
                       compute_dtype=jnp.float32, block_kv=16, remat_block=2,
                       n_kv_heads=n_kv_heads, attn_impl=attn_impl,
                       compress_impl=compress_impl)
    return PreTTRConfig(backbone=bb, l=l, max_query_len=MAX_Q,
                        max_doc_len=MAX_D, compress_dim=compress_dim,
                        store_dtype=store_dtype)


def _world(cfg, batch=3, seed=1):
    kq, kd, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.randint(kq, (batch, MAX_Q), 5, 512)
    d = jax.random.randint(kd, (batch, MAX_D), 5, 512)
    qv = jnp.arange(MAX_Q)[None] < jax.random.randint(kv, (batch, 1), 3,
                                                      MAX_Q + 1)
    dv = jnp.arange(MAX_D)[None] < jax.random.randint(kv, (batch, 1), 5,
                                                      MAX_D + 1)
    return q, d, qv, dv


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("l,compress_dim,n_kv_heads", [
    (0, 0, None),          # whole model is the join
    (2, 0, None),
    (2, 16, 2),            # compression + GQA
    (3, 0, 2),             # join == CLS-only final layer
])
def test_fused_join_matches_concat(backend, l, compress_dim, n_kv_heads):
    """Fused split-KV join vs legacy concat join on identical inputs.
    Under the reference backends the fused path concatenates K/V inside
    the attention op and runs the same cores, so scores are bit-equal;
    the pallas paths run two different flash kernels (split vs concat)
    and agree to kernel tolerance."""
    cfg = _cfg(l=l, compress_dim=compress_dim, backend=backend,
               n_kv_heads=n_kv_heads)
    params, _ = init_prettr(jax.random.PRNGKey(0), cfg)
    q, d, qv, dv = _world(cfg)
    store = precompute_docs(params, cfg, d, dv)
    qr = encode_query(params, cfg, q, qv)
    legacy = jax.jit(lambda p, a, b_, c, e: join_and_score(
        p, cfg, a, b_, c, e, fused=False))
    fused = jax.jit(lambda p, a, b_, c, e: join_and_score(
        p, cfg, a, b_, c, e, fused=True))
    s_legacy = np.asarray(legacy(params, qr, qv, store, dv))
    s_fused = np.asarray(fused(params, qr, qv, store, dv))
    if backend == "pallas":
        np.testing.assert_allclose(s_fused, s_legacy, rtol=2e-5, atol=2e-5)
    else:
        np.testing.assert_array_equal(s_fused, s_legacy)


@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_join_matches_rank_forward(backend):
    """The PreTTR soundness invariant holds through the fused path, with
    and without stored layer-l K/V."""
    cfg = _cfg(l=2, compress_dim=16, store_dtype=jnp.float16,
               backend=backend)
    params, _ = init_prettr(jax.random.PRNGKey(0), cfg)
    q, d, qv, dv = _world(cfg)
    tokens = jnp.concatenate([q, d], axis=1)
    segs = jnp.concatenate([jnp.zeros((3, MAX_Q), jnp.int32),
                            jnp.ones((3, MAX_D), jnp.int32)], axis=1)
    valid = jnp.concatenate([qv, dv], axis=1)
    s_joint = np.asarray(rank_forward(params, cfg, tokens, segs, valid))
    store = precompute_docs(params, cfg, d, dv)
    qr = encode_query(params, cfg, q, qv)
    s_fused = np.asarray(join_and_score(params, cfg, qr, qv, store, dv))
    doc_kv = precompute_doc_kv(params, cfg, store)
    s_kv = np.asarray(join_and_score(params, cfg, qr, qv, store, dv,
                                     doc_kv=doc_kv))
    np.testing.assert_allclose(s_joint, s_fused, rtol=5e-3, atol=5e-3)
    np.testing.assert_allclose(s_joint, s_kv, rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("backend", BACKENDS)
def test_stored_layer_kv_matches_recompute(backend):
    """At fp32 storage, layer-l K/V loaded from ``precompute_doc_kv``
    must reproduce the in-join recomputation *bit-for-bit* (plain/blocked;
    pallas to kernel tolerance) — the streams are the same ops on the same
    bytes, just moved to index time."""
    cfg = _cfg(l=1, compress_dim=16, store_dtype=jnp.float32,
               backend=backend, n_kv_heads=2)
    params, _ = init_prettr(jax.random.PRNGKey(0), cfg)
    q, d, qv, dv = _world(cfg)
    store = precompute_docs(params, cfg, d, dv)
    qr = encode_query(params, cfg, q, qv)
    doc_kv = precompute_doc_kv(params, cfg, store)
    s_re = np.asarray(join_and_score(params, cfg, qr, qv, store, dv))
    s_kv = np.asarray(join_and_score(params, cfg, qr, qv, store, dv,
                                     doc_kv=doc_kv))
    np.testing.assert_array_equal(s_kv, s_re)


def test_fused_rejects_unsupported_shapes():
    cfg = _cfg(l=2)
    params, _ = init_prettr(jax.random.PRNGKey(0), cfg)
    q, d, qv, dv = _world(cfg)
    store = precompute_docs(params, cfg, d, dv)
    qr = encode_query(params, cfg, q, qv)
    doc_kv = precompute_doc_kv(params, cfg, store)
    with pytest.raises(ValueError, match="fused"):
        join_and_score(params, cfg, qr, qv, store, dv, doc_kv=doc_kv,
                       fused=False)
    windowed = dataclasses.replace(
        cfg, backbone=dataclasses.replace(cfg.backbone,
                                          window_pattern=(64,)))
    with pytest.raises(ValueError, match="fused join"):
        join_and_score(params, windowed, qr, qv, store, dv)
    # the split CLS-only layer shares project_q/kv with the join; rope /
    # qk-norm backbones would silently diverge from the legacy CLS layer
    roped = dataclasses.replace(
        cfg, backbone=dataclasses.replace(cfg.backbone, rope=True))
    with pytest.raises(ValueError, match="CLS-only"):
        join_and_score(params, roped, qr, qv, store, dv)


# ---------------------------------------------------------------------------
# Index-side: stored K/V streams through builder + store + serving
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def kv_index(tmp_path_factory):
    from repro.data.synthetic_ir import SyntheticIRWorld
    from repro.index import IndexBuilder, TermRepIndex

    cfg = _cfg(l=1, compress_dim=16, store_dtype=jnp.float16)
    world = SyntheticIRWorld(n_docs=48, n_queries=8,
                             vocab_size=cfg.backbone.vocab_size,
                             doc_len=MAX_D - 2, seed=0)
    params, _ = init_prettr(jax.random.PRNGKey(0), cfg)
    path = str(tmp_path_factory.mktemp("kvidx") / "idx")
    IndexBuilder(path, cfg, params, codec="fp16", n_shards=2, batch_size=16,
                 store_layer_kv=True).build(list(world.docs))
    return cfg, params, world, path, TermRepIndex.open(path)


def test_kv_streams_on_disk_and_accounting(kv_index):
    cfg, params, world, path, idx = kv_index
    assert idx.has_layer_kv
    d_kv = cfg.backbone.n_kv_heads * cfg.backbone.dh
    assert idx.kv_dim == d_kv
    spec = idx.streams_spec()
    assert set(spec) == {"reps", "layer_k", "layer_v"}
    # bytes/token = codec reps (e * 2B) + 2 KV streams (d_kv * 2B each)
    assert idx.bytes_per_token() == 16 * 2 + 2 * d_kv * 2
    n_tok = int(idx.doc_lengths.sum())
    assert idx.storage_bytes() == n_tok * idx.bytes_per_token()
    for name in spec:
        sz = sum(os.path.getsize(os.path.join(path, f"shard-{s:05d}",
                                              f"{name}.bin"))
                 for s in range(idx.n_shards))
        dt, shape = spec[name]
        assert sz == n_tok * dt.itemsize * int(np.prod(shape, dtype=int))


def test_kv_streams_verify_byte_exact(kv_index):
    from repro.index import verify_index
    cfg, params, world, path, idx = kv_index
    assert verify_index(idx, cfg, params, list(world.docs), sample=8) == 8


def test_gather_raw_stream_filter(kv_index):
    cfg, params, world, path, idx = kv_index
    parts, _ = idx.gather_raw([0, 1], streams=["reps"])
    assert set(parts) == {"reps"}
    with pytest.raises(ValueError, match="unknown stream"):
        idx.gather_raw([0], streams=["nope"])


def test_served_kv_matches_inline_join(kv_index):
    """Serving with index-loaded K/V streams == the in-memory fused join
    on the same stored reps, to fp16 storage rounding."""
    from repro.data.synthetic_ir import pack_query
    cfg, params, world, path, idx = kv_index
    parts, valid = idx.gather_raw(list(range(6)), pad_to=MAX_D)
    q, qv = pack_query(world.queries[0], MAX_Q)
    qr = encode_query(params, cfg, jnp.asarray(q)[None],
                      jnp.asarray(qv)[None])
    qr6 = jnp.broadcast_to(qr, (6, MAX_Q, cfg.backbone.d_model))
    qv6 = jnp.broadcast_to(jnp.asarray(qv)[None], (6, MAX_Q))
    s_kv = join_and_score(params, cfg, qr6, qv6, jnp.asarray(parts["reps"]),
                          jnp.asarray(valid),
                          doc_kv=(jnp.asarray(parts["layer_k"]),
                                  jnp.asarray(parts["layer_v"])))
    s_re = join_and_score(params, cfg, qr6, qv6, jnp.asarray(parts["reps"]),
                          jnp.asarray(valid))
    np.testing.assert_allclose(np.asarray(s_kv), np.asarray(s_re),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# Serving: hot-doc cache + dispatch-count regression
# ---------------------------------------------------------------------------


def _zipf_workload(world, rng, n_queries, candidates, n_docs, alpha=1.3):
    from repro.data.synthetic_ir import pack_query
    reqs = []
    for qi in range(n_queries):
        q, qv = pack_query(world.queries[qi % world.n_queries], MAX_Q)
        cands = list((np.minimum(rng.zipf(alpha, size=candidates), n_docs)
                      - 1).astype(np.int64))
        reqs.append((q, qv, cands))
    return reqs


def _drain_scores(svc, reqs):
    from repro.serving import RankRequest
    for i, (q, qv, cands) in enumerate(reqs):
        svc.submit(RankRequest(q, qv, cands, request_id=str(i)))
    return {r.request_id: r.scores for r in svc.drain()}


def test_doc_cache_scores_identical_hit_vs_miss(kv_index):
    """Zipf workload through the cached service: the warm (all-hit) pass
    returns bit-identical scores to the cold (all-miss) pass, and both
    match the uncached service."""
    from repro.serving import RankingService
    cfg, params, world, path, idx = kv_index
    rng = np.random.default_rng(0)
    reqs = _zipf_workload(world, rng, 8, 8, len(idx))
    plain = RankingService(params, cfg, idx, micro_batch=8)
    cached = RankingService(params, cfg, idx, micro_batch=8, doc_cache_mb=4)
    ref = _drain_scores(plain, reqs)
    cold = _drain_scores(cached, reqs)
    assert cached.stats.n_doc_cache_hit > 0          # zipf repeats in-pass
    warm = _drain_scores(cached, reqs)
    assert cached.doc_cache.hits > cached.doc_cache.misses
    for k in ref:
        np.testing.assert_array_equal(cold[k], warm[k])
        np.testing.assert_array_equal(ref[k], cold[k])


def test_doc_cache_eviction_under_tiny_budget(kv_index):
    """A cache smaller than the corpus must evict and still score
    correctly (pinned in-flight docs are never evicted)."""
    from repro.serving import RankingService
    cfg, params, world, path, idx = kv_index
    probe = RankingService(params, cfg, idx, micro_batch=4, doc_cache_mb=64)
    # just over the scheduler minimum: 2*micro_batch + 1 docs, plus the two
    # reserved (zero/scratch) pages
    cap_bytes = (probe.doc_cache.entry_bytes * (2 * 4 + 1)
                 + 2 * probe.doc_cache.page_bytes)
    svc = RankingService(params, cfg, idx, micro_batch=4,
                         doc_cache_mb=cap_bytes / 2**20)
    rng = np.random.default_rng(1)
    reqs = _zipf_workload(world, rng, 6, 6, len(idx), alpha=1.1)
    ref = _drain_scores(RankingService(params, cfg, idx, micro_batch=4),
                        reqs)
    got = _drain_scores(svc, reqs)
    assert svc.doc_cache.evictions > 0
    for k in ref:
        np.testing.assert_array_equal(ref[k], got[k])


def test_doc_cache_too_small_raises(kv_index):
    from repro.serving import RankingService
    cfg, params, world, path, idx = kv_index
    with pytest.raises(ValueError, match="doc cache"):
        RankingService(params, cfg, idx, micro_batch=32,
                       doc_cache_mb=0.001)


def test_doc_cache_rejects_injected_join_fn(kv_index):
    """The pool-fused scoring jit cannot honor an injected join_fn — the
    combination must fail loudly, not silently score with the real model."""
    from repro.serving import RankingService
    cfg, params, world, path, idx = kv_index
    with pytest.raises(ValueError, match="join_fn"):
        RankingService(params, cfg, idx, doc_cache_mb=4,
                       join_fn=lambda *a: None)


def test_use_layer_kv_validation(kv_index):
    from repro.serving import RankingService
    from repro.index import TermRepIndex
    cfg, params, world, path, idx = kv_index
    with pytest.raises(ValueError, match="fused"):
        RankingService(params, cfg, idx, fused=False, use_layer_kv=True)
    # an index without the streams cannot be asked for them
    bare = TermRepIndex.open(path)
    bare.layer_kv = None
    with pytest.raises(ValueError, match="layer_k"):
        RankingService(params, cfg, bare, use_layer_kv=True)
    # mismatched K/V width is rejected at construction
    bad = TermRepIndex.open(path)
    bad.layer_kv = {"dtype": "<f2", "d_kv": 8}
    with pytest.raises(ValueError, match="K/V width|kv"):
        RankingService(params, cfg, bad)


@pytest.mark.parametrize("doc_cache_mb", [0.0, 4.0])
def test_one_join_dispatch_per_micro_batch(kv_index, doc_cache_mb):
    """Dispatch-count regression guard: a packed drain must issue exactly
    one scoring jit entry per micro-batch — per-candidate (or per-request)
    dispatch must never sneak back in, cache or no cache."""
    from repro.serving import RankingService
    cfg, params, world, path, idx = kv_index
    svc = RankingService(params, cfg, idx, micro_batch=4,
                         doc_cache_mb=doc_cache_mb)
    calls = [0]

    def counting(fn):
        def wrapped(*a):
            calls[0] += 1
            return fn(*a)
        return wrapped

    # wrap every scoring entry point (direct, raw-stream, pool-fused)
    for attr in ("_join", "_join_raw", "_join_pool"):
        fn = getattr(svc, attr, None)
        if fn is not None:
            setattr(svc, attr, counting(fn))
    rng = np.random.default_rng(2)
    reqs = _zipf_workload(world, rng, 5, 6, len(idx))
    _drain_scores(svc, reqs)
    n_rows = sum(len(c) for _, _, c in reqs)
    expect_batches = -(-n_rows // 4)
    assert calls[0] == expect_batches
    assert svc.stats.n_join_dispatch == calls[0]
    assert svc.stats.n_batches == expect_batches


# ---------------------------------------------------------------------------
# Bench-file schema (the serving perf trajectory contract)
# ---------------------------------------------------------------------------


def test_bench_serving_schema_contract():
    from benchmarks.common import assert_bench_schema
    good = [{"name": "serving/fused/qps", "value": 12.5, "unit": "qps"}]
    assert_bench_schema(good)
    for bad in (
        [],
        [{"name": "x", "value": float("nan"), "unit": "u"}],
        [{"name": "x", "value": 1.0}],
        [{"name": "x", "value": True, "unit": "u"}],
        [{"name": "x", "value": 1.0, "unit": "u"}] * 2,
    ):
        with pytest.raises(AssertionError):
            assert_bench_schema(bad)


def test_empty_and_duplicate_candidates_through_fused_service(kv_index):
    """The fused+cached service handles empty candidate lists and
    duplicate doc ids exactly like the uncached legacy service."""
    from repro.data.synthetic_ir import pack_query
    from repro.serving import RankingService, RankRequest
    cfg, params, world, path, idx = kv_index
    q, qv = pack_query(world.queries[0], MAX_Q)
    cands = [[3, 3, 5, 9, 3], [], list(range(7))]
    legacy = RankingService(params, cfg, idx, micro_batch=4, fused=False,
                            use_layer_kv=False)
    fused = RankingService(params, cfg, idx, micro_batch=4, doc_cache_mb=4)
    for svc in (legacy, fused):
        for i, c in enumerate(cands):
            svc.submit(RankRequest(q, qv, c, request_id=f"q{i}"))
    r_leg = {r.request_id: r for r in legacy.drain()}
    r_fus = {r.request_id: r for r in fused.drain()}
    assert r_fus["q1"].doc_ids == [] and r_fus["q1"].scores.shape == (0,)
    for k in r_leg:
        assert r_leg[k].doc_ids == r_fus[k].doc_ids
        np.testing.assert_allclose(r_leg[k].scores, r_fus[k].scores,
                                   rtol=2e-3, atol=2e-3)
