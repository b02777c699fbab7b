"""Offline index pipeline: sharded v2 builds, codec-aware reads, format
errors, end-to-end serving equivalence, and the data-parallel build."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.prettr import (PreTTRConfig, encode_query, init_prettr,
                               join_and_score, make_backbone, precompute_docs,
                               rank_forward)
from repro.data.synthetic_ir import pack_doc_batch, pack_query
from repro.index import (IndexBuilder, IndexFormatError, TermRepIndex,
                         verify_index)
from repro.serving import RankingService, Reranker

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _cfg(l=1, compress_dim=16, n_layers=3, d_model=32):
    bb = make_backbone(n_layers=n_layers, d_model=d_model, n_heads=2,
                       d_ff=64, vocab_size=128, l=l, max_len=24,
                       compute_dtype=jnp.float32, block_kv=8)
    return PreTTRConfig(backbone=bb, l=l, max_query_len=8, max_doc_len=16,
                        compress_dim=compress_dim)


def _docs(n=11, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(5, 128, size=rng.integers(4, 15)) for _ in range(n)]


def _build(tmp_path, codec="fp16", n_shards=3, n_docs=11, batch_size=4,
           compress_dim=16, **kw):
    cfg = _cfg(compress_dim=compress_dim)
    params, _ = init_prettr(jax.random.PRNGKey(0), cfg)
    docs = _docs(n_docs)
    builder = IndexBuilder(str(tmp_path / "idx"), cfg, params, codec=codec,
                           n_shards=n_shards, batch_size=batch_size, **kw)
    report = builder.build(docs)
    return cfg, params, docs, report


# -- build + read ------------------------------------------------------------


@pytest.mark.parametrize("codec", ["fp32", "fp16", "int8"])
def test_build_verify_roundtrip(tmp_path, codec):
    cfg, params, docs, report = _build(tmp_path, codec=codec)
    assert report.n_docs == len(docs) and report.n_shards == 3
    idx = TermRepIndex.open(str(tmp_path / "idx"))
    assert idx.version == 2 and idx.n_shards == 3 and len(idx) == len(docs)
    assert idx.codec.name == codec
    np.testing.assert_array_equal(
        idx.doc_lengths, [min(len(d) + 1, cfg.max_doc_len) for d in docs])
    # stored streams byte-match a fresh encode of every doc
    assert verify_index(idx, cfg, params, docs, sample=len(docs)) == len(docs)
    # accounting: manifest-derived bytes == bytes on disk
    assert idx.storage_bytes() == report.storage_bytes
    assert report.storage_bytes == int(idx.doc_lengths.sum()) * \
        idx.codec.bytes_per_token(idx.rep_dim)


def test_multi_shard_gather_matches_single_shard(tmp_path):
    cfg, params, docs, _ = _build(tmp_path, n_shards=4)
    many = TermRepIndex.open(str(tmp_path / "idx"))
    builder = IndexBuilder(str(tmp_path / "one"), cfg, params, codec="fp16",
                           n_shards=1, batch_size=4)
    builder.build(docs)
    one = TermRepIndex.open(str(tmp_path / "one"))
    for ids in [list(range(len(docs))), [10, 0, 7, 0, 3], [], [5]]:
        ra, va = many.gather(ids, pad_to=16)
        rb, vb = one.gather(ids, pad_to=16)
        np.testing.assert_array_equal(ra, rb)
        np.testing.assert_array_equal(va, vb)


def test_sync_writer_matches_threaded(tmp_path):
    cfg, params, docs, _ = _build(tmp_path, codec="int8", writer_depth=2)
    builder = IndexBuilder(str(tmp_path / "sync"), cfg, params, codec="int8",
                           n_shards=3, batch_size=4, writer_depth=0)
    builder.build(docs)
    a = TermRepIndex.open(str(tmp_path / "idx"))
    b = TermRepIndex.open(str(tmp_path / "sync"))
    pa, va = a.gather_raw(list(range(len(docs))))
    pb, vb = b.gather_raw(list(range(len(docs))))
    for name in pa:
        np.testing.assert_array_equal(pa[name], pb[name])
    np.testing.assert_array_equal(va, vb)


def test_zero_doc_v2_build(tmp_path):
    cfg, params, _, report = _build(tmp_path, n_docs=0, n_shards=2)
    assert report.n_docs == 0
    idx = TermRepIndex.open(str(tmp_path / "idx"))
    assert len(idx) == 0 and idx.storage_bytes() == 0
    reps, valid = idx.gather([], pad_to=16)
    assert reps.shape == (0, 16, 16) and valid.shape == (0, 16)


def test_v1_write_path_still_opens(tmp_path):
    cfg = _cfg()
    params, _ = init_prettr(jax.random.PRNGKey(0), cfg)
    docs = _docs(5)
    tokens, lengths, valid = pack_doc_batch(docs, cfg.max_doc_len)
    reps = precompute_docs(params, cfg, jnp.asarray(tokens),
                           jnp.asarray(valid))
    v1 = TermRepIndex(str(tmp_path / "v1"), rep_dim=16, dtype="float16",
                      l=1, compressed=True, max_doc_len=16)
    v1.add_docs(np.asarray(reps), [int(n) for n in lengths])
    v1.finalize()
    idx = TermRepIndex.open(str(tmp_path / "v1"))
    assert idx.version == 1 and idx.n_shards == 1
    assert idx.codec.name == "fp16"
    got, gv = idx.gather(list(range(5)), pad_to=16)
    want = np.where(np.asarray(valid)[..., None],
                    np.asarray(reps, np.float16), 0)
    np.testing.assert_array_equal(got, want)


def test_v1_writer_rejects_int8(tmp_path):
    with pytest.raises(ValueError, match="IndexBuilder"):
        idx = TermRepIndex(str(tmp_path / "x"), rep_dim=8, dtype="int8",
                           codec="int8")
        idx.add_docs(np.zeros((1, 4, 8), np.float32), [4])


# -- format errors (satellite: clear IndexFormatError, not raw tracebacks) ---


def test_open_missing_index_raises_format_error(tmp_path):
    with pytest.raises(IndexFormatError, match="meta.msgpack"):
        TermRepIndex.open(str(tmp_path / "nope"))


def test_open_corrupt_meta_raises_format_error(tmp_path):
    d = tmp_path / "bad"
    d.mkdir()
    (d / "meta.msgpack").write_bytes(b"\xc1 definitely not msgpack")
    with pytest.raises(IndexFormatError, match="corrupt"):
        TermRepIndex.open(str(d))


def test_open_incomplete_meta_raises_format_error(tmp_path):
    import msgpack

    d = tmp_path / "partial"
    d.mkdir()
    (d / "meta.msgpack").write_bytes(msgpack.packb({"rep_dim": 8}))
    with pytest.raises(IndexFormatError, match="malformed v1"):
        TermRepIndex.open(str(d))


def test_open_version_mismatch_raises_format_error(tmp_path):
    import msgpack

    d = tmp_path / "future"
    d.mkdir()
    (d / "manifest.msgpack").write_bytes(msgpack.packb(
        {"version": 3, "codec": "fp16", "rep_dim": 8, "l": 1,
         "compressed": False, "max_doc_len": 8, "n_docs": 0, "shards": []}))
    with pytest.raises(IndexFormatError, match="expects version 2"):
        TermRepIndex.open(str(d))


def test_open_unknown_codec_raises_format_error(tmp_path):
    import msgpack

    d = tmp_path / "codecless"
    d.mkdir()
    (d / "manifest.msgpack").write_bytes(msgpack.packb(
        {"version": 2, "codec": "zstd", "rep_dim": 8, "l": 1,
         "compressed": False, "max_doc_len": 8, "n_docs": 0, "shards": []}))
    with pytest.raises(IndexFormatError, match="malformed v2"):
        TermRepIndex.open(str(d))


def test_open_missing_shard_stream_raises_format_error(tmp_path):
    cfg, params, docs, _ = _build(tmp_path, codec="int8")
    os.remove(str(tmp_path / "idx" / "shard-00001" / "scales.bin"))
    with pytest.raises(IndexFormatError, match="scales.bin"):
        TermRepIndex.open(str(tmp_path / "idx"))


def test_open_truncated_shard_stream_raises_format_error(tmp_path):
    """An interrupted copy (short reps.bin) must raise IndexFormatError,
    not a raw np.memmap ValueError."""
    cfg, params, docs, _ = _build(tmp_path, codec="fp16")
    p = str(tmp_path / "idx" / "shard-00000" / "reps.bin")
    data = open(p, "rb").read()
    with open(p, "wb") as f:
        f.write(data[: len(data) // 2])
    with pytest.raises(IndexFormatError, match="corrupt index stream"):
        TermRepIndex.open(str(tmp_path / "idx"))


def test_open_malformed_v1_offsets_raises_format_error(tmp_path):
    import msgpack

    d = tmp_path / "badoffsets"
    d.mkdir()
    (d / "meta.msgpack").write_bytes(msgpack.packb(
        {"rep_dim": 8, "dtype": "<f2", "l": 1, "compressed": False,
         "max_doc_len": 8, "offsets": [[0, 4, 99]]}))   # 3-element entry
    with pytest.raises(IndexFormatError, match="malformed v1"):
        TermRepIndex.open(str(d))


# -- end-to-end serving equivalence (satellite: codec numerics) --------------


def test_fp16_served_scores_bit_match_in_memory(tmp_path):
    """Serving a v2 multi-shard fp16 index returns bit-identical scores to
    the in-memory precompute+join path (the index adds nothing but I/O)."""
    cfg, params, docs, _ = _build(tmp_path, codec="fp16", n_shards=3)
    idx = TermRepIndex.open(str(tmp_path / "idx"))
    n = len(docs)
    q, qv = pack_query(np.asarray([7, 9, 11]), cfg.max_query_len)

    svc = RankingService(params, cfg, idx, micro_batch=n)
    resp = svc.rank(q, qv, list(range(n)))
    order = np.argsort(resp.doc_ids)            # back to doc-id order
    served = np.asarray(resp.scores)[order]

    q_reps = jax.jit(lambda p, t, v: encode_query(p, cfg, t, v))(
        params, q[None], qv[None])
    reps, dvalid = idx.gather(list(range(n)), pad_to=cfg.max_doc_len)
    direct = jax.jit(
        lambda p, qr, qv_, st, dv: join_and_score(p, cfg, qr, qv_, st, dv))(
        params, jnp.concatenate([q_reps] * n),
        jnp.broadcast_to(jnp.asarray(qv), (n, cfg.max_query_len)),
        jnp.asarray(reps), jnp.asarray(dvalid))
    np.testing.assert_array_equal(served, np.asarray(direct))


@pytest.mark.parametrize("codec,tol", [("fp16", 5e-3), ("int8", 5e-2)])
def test_served_scores_match_rank_forward(tmp_path, codec, tol):
    """End-to-end: scores served through the on-disk index agree with the
    training-time joint rank_forward (fp16 within storage rounding, int8
    within quantization tolerance)."""
    cfg, params, docs, _ = _build(tmp_path, codec=codec, n_shards=3)
    idx = TermRepIndex.open(str(tmp_path / "idx"))
    n = len(docs)
    q, qv = pack_query(np.asarray([7, 9, 11]), cfg.max_query_len)
    tokens_d, _, valid_d = pack_doc_batch(docs, cfg.max_doc_len)
    tokens = np.concatenate([np.broadcast_to(q, (n, cfg.max_query_len)),
                             tokens_d], axis=1)
    segs = np.concatenate([np.zeros((n, cfg.max_query_len), np.int32),
                           np.ones((n, cfg.max_doc_len), np.int32)], axis=1)
    valid = np.concatenate([np.broadcast_to(qv, (n, cfg.max_query_len)),
                            valid_d], axis=1)
    ref = np.asarray(rank_forward(params, cfg, jnp.asarray(tokens),
                                  jnp.asarray(segs), jnp.asarray(valid)))

    rr = Reranker(params, cfg, idx, micro_batch=4)
    ranked, scores, _ = rr.rerank(q, qv, list(range(n)))
    served = np.asarray(scores)[np.argsort(ranked)]
    np.testing.assert_allclose(served, ref, rtol=tol, atol=tol)


def test_int8_service_decodes_on_device(tmp_path):
    """The prefetcher ships raw int8 streams and decodes after H2D —
    inside the scoring jit, with no standalone decode dispatch: the
    service path must agree with host-side gather()+join."""
    cfg, params, docs, _ = _build(tmp_path, codec="int8", n_shards=2)
    idx = TermRepIndex.open(str(tmp_path / "idx"))
    svc = RankingService(params, cfg, idx, micro_batch=len(docs))
    assert svc._join_raw is not None            # in-jit decode installed
    assert svc._decode is None                  # no separate decode dispatch
    q, qv = pack_query(np.asarray([3, 4]), cfg.max_query_len)
    resp = svc.rank(q, qv, list(range(len(docs))))
    order = np.argsort(resp.doc_ids)

    q_reps = svc._encode(params, q[None], qv[None])
    reps, dvalid = idx.gather(list(range(len(docs))), pad_to=cfg.max_doc_len)
    direct = svc._join(params, jnp.concatenate([q_reps] * len(docs)),
                       jnp.broadcast_to(jnp.asarray(qv),
                                        (len(docs), cfg.max_query_len)),
                       jnp.asarray(reps), jnp.asarray(dvalid))
    np.testing.assert_allclose(np.asarray(resp.scores)[order],
                               np.asarray(direct), rtol=1e-5, atol=1e-5)


def test_reranker_validates_v2_index_compat(tmp_path):
    cfg, params, docs, _ = _build(tmp_path, codec="int8")
    idx = TermRepIndex.open(str(tmp_path / "idx"))
    with pytest.raises(ValueError, match="truncate"):
        Reranker(params, dataclasses.replace(cfg, max_doc_len=8), idx)
    with pytest.raises(ValueError, match="rep_dim"):
        Reranker(params, dataclasses.replace(cfg, compress_dim=32), idx)
    Reranker(params, cfg, idx)


# -- data-parallel build (8 forced host devices, subprocess) -----------------


def test_sharded_build_matches_single_host():
    """Acceptance: a data-parallel build over 8 forced host devices writes
    byte-identical shard files to the single-host build."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    snippet = """
    import os, tempfile
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core.prettr import PreTTRConfig, make_backbone, init_prettr
    from repro.index import IndexBuilder

    assert jax.device_count() == 8, jax.device_count()
    bb = make_backbone(n_layers=2, d_model=32, n_heads=2, d_ff=64,
                       vocab_size=128, l=1, max_len=24,
                       compute_dtype=jnp.float32, block_kv=8)
    cfg = PreTTRConfig(backbone=bb, l=1, max_query_len=8, max_doc_len=16,
                       compress_dim=8)
    params, _ = init_prettr(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    docs = [rng.integers(5, 128, size=rng.integers(4, 15))
            for _ in range(26)]
    mesh = jax.make_mesh((8,), ("data",))
    with tempfile.TemporaryDirectory() as a, \\
            tempfile.TemporaryDirectory() as b:
        IndexBuilder(a, cfg, params, codec="int8", n_shards=3,
                     batch_size=8).build(docs)
        IndexBuilder(b, cfg, params, codec="int8", n_shards=3,
                     batch_size=8, mesh=mesh).build(docs)
        n = 0
        for root, _, files in os.walk(a):
            for f in files:
                if not f.endswith(".bin"):
                    continue
                rel = os.path.relpath(os.path.join(root, f), a)
                wa = open(os.path.join(a, rel), "rb").read()
                wb = open(os.path.join(b, rel), "rb").read()
                assert wa == wb, f"shard stream {rel} differs"
                n += 1
        assert n >= 6          # 3 shards x (reps + scales)
    print("OK sharded build", n)
    """
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(snippet)],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, \
        f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "OK sharded build" in out.stdout


def test_data_parallel_batch_is_per_device():
    """Under a mesh, ``batch_size`` rows go to every device: the manifest
    records that per-device shape, and ``verify_index`` replays it on one
    device byte-for-byte."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    snippet = """
    import tempfile
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core.prettr import PreTTRConfig, make_backbone, init_prettr
    from repro.index import IndexBuilder, TermRepIndex, verify_index

    bb = make_backbone(n_layers=2, d_model=32, n_heads=2, d_ff=64,
                       vocab_size=128, l=1, max_len=24,
                       compute_dtype=jnp.float32, block_kv=8)
    cfg = PreTTRConfig(backbone=bb, l=1, max_query_len=8, max_doc_len=16,
                       compress_dim=8)
    params, _ = init_prettr(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    docs = [rng.integers(5, 128, size=rng.integers(4, 15))
            for _ in range(11)]
    with tempfile.TemporaryDirectory() as d:
        b = IndexBuilder(d, cfg, params, codec="int8", batch_size=4,
                         mesh=jax.make_mesh((2,), ("data",)))
        assert b.batch_size == 4
        b.build(docs)
        idx = TermRepIndex.open(d)
        assert idx.encode_batch == 4 and len(idx) == 11
        assert verify_index(idx, cfg, params, docs, sample=11) == 11
    print("OK per-device batch")
    """
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(snippet)],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, \
        f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "OK per-device batch" in out.stdout
