"""Offline sharded index builder — the paper's indexing phase (Fig. 1
step 2: "we precompute part of the document term representations at
indexing time"), production-shaped.

:class:`IndexBuilder` drives :func:`repro.core.prettr.precompute_docs` over
a corpus and writes a format-v2 index (``manifest.msgpack`` +
``shard-NNNNN/`` stream files — see ``repro.index.store``):

* **Fixed-shape batches** — documents are packed to ``[batch, max_doc_len]``
  (last batch padded with empty rows, results dropped), so the whole build
  hits one jit cache entry.
* **Data-parallel over the ``repro.dist`` mesh** — given a mesh, each
  device encodes its own ``batch_size`` rows (weights replicated).  XLA's
  output differs at the ulp across batch *shapes* but not across row
  positions, so keeping the per-device shape equal to the single-host one
  is what makes the sharded build doc-for-doc bit-identical to the
  single-host build (and replayable by ``verify_index``).
* **Overlapped host writes** — a writer thread materializes each batch on
  the host, codec-encodes it, and appends to the shard files while the
  device encodes the *next* batch (the PR-3 serving prefetch thread, in
  reverse: there host reads overlap device compute, here host writes do).
* **Per-shard writers** — documents map to ``n_shards`` contiguous ranges;
  each shard directory gets one append-only file per codec stream plus its
  row in the manifest, written once at finalize.

* **Trained codecs** — a codec with ``needs_fit`` (the ``"pq"`` product
  quantizer) gets a fit pass first: a prefix sample of the corpus is
  encoded through the same fixed-shape jit, the valid-token reps are
  collected host-side, and the fitted state lands in the manifest's
  ``codec_state`` key (the codebook-in-manifest contract in
  ``repro.index.codecs``).
* **Index-time token pruning** — ``keep_frac`` / ``max_kept_tokens``
  switch on a salience pass (:func:`repro.core.prettr.doc_salience`:
  attention mass received at join layer ``l``) and only each doc's
  highest-salience tokens are written; the manifest records the policy
  under ``prune``, each shard's pre-pruning token counts under
  ``orig_lengths``, and ``max_doc_len`` as the *pruned* cap, so serving
  configs can shrink their padded doc shapes to match.  Rejected for
  RoPE backbones (dropping rows would shift every survivor's rope
  phase); PreTTR's BERT bakes learned positions into the stored reps at
  embed time, so surviving rows keep their exact joint-forward values.

:func:`verify_index` re-encodes a sample of documents and checks the stored
streams byte-for-byte (codecs are deterministic, so this is exact for every
codec, int8 and pq included; prune selections replay via the same salience
jit at the build's batch shape).
"""
from __future__ import annotations

import dataclasses
import os
import queue
import threading
import time
from typing import Sequence

import msgpack
import numpy as np

import jax
import jax.numpy as jnp

from repro.core import prettr as P
from repro.data.synthetic_ir import pack_doc_batch
from repro.index.codecs import StorageCodec, get_codec
from repro.index.integrity import file_chunk_checksums
from repro.index.store import FORMAT_VERSION, TermRepIndex

_STOP = object()


@dataclasses.dataclass
class BuildReport:
    """What one ``build()`` run did, for logs and the storage benchmark."""
    n_docs: int
    n_tokens: int
    n_shards: int
    codec: str
    storage_bytes: int                 # actual bytes on disk (all streams)
    encode_s: float                    # device encode wall (dispatch side)
    write_s: float                     # host materialize + codec + file IO
    wall_s: float

    @property
    def bytes_per_doc(self) -> float:
        return self.storage_bytes / max(1, self.n_docs)


def shard_ranges(n_docs: int, n_shards: int) -> list[tuple[int, int]]:
    """Contiguous [lo, hi) doc ranges, balanced like ``np.array_split``."""
    bounds = np.linspace(0, n_docs, n_shards + 1).astype(np.int64)
    return [(int(bounds[i]), int(bounds[i + 1])) for i in range(n_shards)]


def prune_selection(salience: np.ndarray, n_tokens: int, keep_frac: float,
                    max_kept_tokens: int) -> np.ndarray:
    """Token indices a prune policy keeps for one doc, in ascending
    (original) order: the ``max(1, ceil(keep_frac * n))`` highest-salience
    tokens, capped by ``max_kept_tokens`` when > 0.  Stable argsort with
    first-index tie-breaks, so the selection is bit-deterministic given
    the salience floats — ``verify_index`` replays it exactly."""
    n = int(n_tokens)
    keep = max(1, int(np.ceil(keep_frac * n)))
    if max_kept_tokens > 0:
        keep = min(keep, int(max_kept_tokens))
    keep = max(1, min(keep, n))
    order = np.argsort(-np.asarray(salience[:n], np.float32), kind="stable")
    return np.sort(order[:keep])


class _ShardWriter:
    """Append-only writer for one shard directory: one open file per
    per-token stream (the codec's, plus the optional layer-l K/V pair),
    plus the per-doc token counts the manifest needs."""

    def __init__(self, root: str, shard_id: int, stream_names,
                 checksum_chunk_bytes: int = 0):
        self.dir_name = f"shard-{shard_id:05d}"
        self.path = os.path.join(root, self.dir_name)
        os.makedirs(self.path, exist_ok=True)
        self._handles = {
            name: open(os.path.join(self.path, f"{name}.bin"), "wb")
            for name in stream_names}
        self.lengths: list[int] = []
        self.orig_lengths: list[int] = []
        self.checksum_chunk_bytes = int(checksum_chunk_bytes)
        self.checksums: dict[str, list[int]] | None = None

    def append(self, parts: dict[str, np.ndarray], n_tokens: int,
               orig_tokens: int | None = None):
        for name, h in self._handles.items():
            h.write(np.ascontiguousarray(parts[name]).tobytes())
        self.lengths.append(int(n_tokens))
        self.orig_lengths.append(int(orig_tokens if orig_tokens is not None
                                     else n_tokens))

    def close(self):
        for h in self._handles.values():
            h.flush()
            os.fsync(h.fileno())
            h.close()
        # checksum pass after the fsync: the CRCs cover exactly the bytes
        # that hit the disk, computed once per stream at finalize (the
        # append hot path stays untouched)
        if self.checksum_chunk_bytes > 0:
            self.checksums = {
                name: file_chunk_checksums(
                    os.path.join(self.path, f"{name}.bin"),
                    self.checksum_chunk_bytes)
                for name in self._handles}

    def manifest_row(self, with_orig: bool = False) -> dict:
        row = {"dir": self.dir_name, "n_docs": len(self.lengths),
               "lengths": self.lengths}
        if with_orig:
            row["orig_lengths"] = self.orig_lengths
        if self.checksums is not None:
            row["checksums"] = self.checksums
        return row


class IndexBuilder:
    """Build a sharded, codec-encoded term-rep index from raw documents.

    Usage::

        builder = IndexBuilder(out_dir, cfg, params, codec="int8",
                               n_shards=8, batch_size=64, mesh=mesh)
        report = builder.build(doc_token_lists)
        index = TermRepIndex.open(out_dir)

    ``mesh`` (optional): a jax Mesh with a ``"data"`` axis; each encode
    step shards ``batch_size`` rows per device over it (the build's
    program is left to the partitioner, so the mesh's axes are used as
    ``Auto`` whatever their declared type).  ``writer_depth`` bounds
    the in-flight device batches the writer thread may lag behind
    (``0`` = synchronous writes, for debugging).  ``backend`` reroutes the
    encode through a compute-backend family exactly as on the serving
    classes.  ``store_layer_kv=True`` additionally precomputes the join
    layer's doc-side K/V (``precompute_doc_kv``) and writes them as the
    ``layer_k``/``layer_v`` streams, so the fused query-time join skips
    all doc-side K/V projections at layer ``l`` (costs
    ``2 * n_kv_heads * head_dim`` extra stored values per token).
    ``kv_codec`` (requires ``store_layer_kv``) additionally encodes those
    K/V streams through a storage codec — ``kv_codec="int8"`` writes raw
    int8 payload plus per-token fp32 scale streams
    (``layer_k_scales``/``layer_v_scales``) that serving ships to the
    device undecoded and the join kernel dequantizes in-register.
    ``keep_frac`` / ``max_kept_tokens`` switch on index-time token
    pruning: a :func:`repro.core.prettr.doc_salience` pass scores every
    stored token and only the survivors of :func:`prune_selection` are
    written (shorter ``doc_lengths`` end to end; manifest ``prune`` +
    per-shard ``orig_lengths`` keep the accounting exact).  A codec with
    ``needs_fit`` (pq) is trained on the reps of the first ``fit_sample``
    docs before anything is encoded (``fit_seed`` seeds the k-means).
    """

    def __init__(self, out_dir: str, cfg: P.PreTTRConfig, params, *,
                 codec: str | StorageCodec = "fp16", n_shards: int = 1,
                 batch_size: int = 64, mesh=None, writer_depth: int = 2,
                 backend: str | None = None, store_layer_kv: bool = False,
                 kv_codec: str | StorageCodec | None = None,
                 keep_frac: float = 1.0, max_kept_tokens: int = 0,
                 fit_sample: int = 256, fit_seed: int = 0,
                 checksum_chunk_bytes: int = 1 << 16):
        if backend is not None:
            from repro.models.backend import apply_backend
            cfg = apply_backend(cfg, backend)
        self.codec = get_codec(codec) if isinstance(codec, str) else codec
        if not 0.0 < keep_frac <= 1.0:
            raise ValueError(f"keep_frac must be in (0, 1], got {keep_frac}")
        if max_kept_tokens < 0:
            raise ValueError(
                f"max_kept_tokens must be >= 0, got {max_kept_tokens}")
        self.keep_frac = float(keep_frac)
        self.max_kept_tokens = int(max_kept_tokens)
        self.prune = keep_frac < 1.0 or max_kept_tokens > 0
        if self.prune and cfg.backbone.rope:
            raise ValueError(
                "token pruning requires a learned-position backbone: the "
                "join layers rope surviving rows by their *pruned* index, "
                "which would shift every survivor's phase (rope=False for "
                "PreTTR's BERT config)")
        self._fit_sample = max(1, int(fit_sample))
        self._fit_seed = int(fit_seed)
        if checksum_chunk_bytes < 0:
            raise ValueError(
                f"checksum_chunk_bytes must be >= 0 (0 disables integrity "
                f"checksums), got {checksum_chunk_bytes}")
        self.checksum_chunk_bytes = int(checksum_chunk_bytes)
        # the optional layer-l K/V streams keep the *model's* storage dtype
        # (raw float projections) unless a kv_codec re-encodes them
        self.store_layer_kv = bool(store_layer_kv)
        self.kv_codec = (get_codec(kv_codec) if isinstance(kv_codec, str)
                         else kv_codec)
        if self.kv_codec is not None and not self.store_layer_kv:
            raise ValueError("kv_codec requires store_layer_kv=True")
        if self.kv_codec is not None:
            # materialize K/V in the codec's encode dtype (full precision
            # for quantizing codecs); the payload dtype lands in the
            # manifest so readers size the streams correctly
            self._kv_dtype = np.dtype(self.kv_codec.encode_dtype)
            self._kv_payload_dtype = self.kv_codec.stream_group(
                "layer_k", 1)["layer_k"][0]
        else:
            self._kv_dtype = np.dtype(jnp.dtype(cfg.store_dtype).name)
            self._kv_payload_dtype = self._kv_dtype
        # quantizing codecs encode from full precision; float codecs store
        # the model's own store_dtype bytes unchanged (fp16 stays bit-exact
        # with the in-memory rank_forward round-trip)
        store_dtype = jnp.dtype(np.dtype(self.codec.encode_dtype))
        self.cfg = dataclasses.replace(cfg, store_dtype=store_dtype) \
            if store_dtype != jnp.dtype(cfg.store_dtype) else cfg
        self.out_dir = out_dir
        self.params = params
        self.n_shards = max(1, int(n_shards))
        if mesh is not None:
            from jax.sharding import AxisType, Mesh
            mesh = Mesh(mesh.devices, mesh.axis_names,
                        axis_types=(AxisType.Auto,) * len(mesh.axis_names))
        self.mesh = mesh
        self.writer_depth = max(0, writer_depth)
        self.rep_dim = cfg.compress_dim or cfg.backbone.d_model
        self.kv_dim = cfg.backbone.n_kv_heads * cfg.backbone.dh
        ndev = mesh.size if mesh is not None else 1
        # fixed per-device jit shape (the manifest's encode_batch); one
        # encode step takes batch_size rows on every device
        self.batch_size = max(1, batch_size)
        self._step_rows = self.batch_size * ndev
        self._params_replicated = None
        self._encode = jax.jit(
            lambda p, d, v: P.precompute_docs(p, self.cfg, d, v))
        # stored K/V must be computed from the bytes the index will serve,
        # i.e. after the codec round trip: identity codecs feed the encode
        # output straight through; quantizing codecs (int8) re-decode the
        # encoded streams on device first (what the query-time join sees)
        self._encode_kv = jax.jit(
            lambda p, st: P.precompute_doc_kv(p, self.cfg, st))
        self._encode_kv_raw = jax.jit(
            lambda p, parts: P.precompute_doc_kv(
                p, self.cfg, self.codec.decode(parts)))
        # pruned cap: what the manifest records as max_doc_len, so serving
        # configs (and gather_raw's default pad) shrink to the kept shape;
        # policy-derived (not data-derived) so it's known before the build
        cap = int(cfg.max_doc_len)
        if self.prune:
            cap = min(cap, int(np.ceil(self.keep_frac * cap)))
            if self.max_kept_tokens > 0:
                cap = min(cap, self.max_kept_tokens)
        self.pruned_max_doc_len = max(1, cap)
        self._salience = jax.jit(
            lambda p, st, v: P.doc_salience(p, self.cfg, st, v)) \
            if self.prune else None

    def _batch_kv(self, reps_dev):
        """Layer-l K/V for one encoded batch, from codec-roundtripped
        reps.  The quantizing-codec branch materializes the batch on the
        host to run the (numpy) encoder — it costs the encode/write
        overlap, which only store_layer_kv int8 builds pay."""
        if self.codec.decode_is_identity:
            return self._encode_kv(self._params_for_encode(), reps_dev)
        parts = self.codec.encode(np.asarray(reps_dev))
        return self._encode_kv_raw(self._params_for_encode(),
                                   jax.device_put(parts))

    def _stream_names(self):
        names = list(self.codec.streams(self.rep_dim))
        if self.store_layer_kv:
            if self.kv_codec is not None:
                names += list(self.kv_codec.stream_group("layer_k",
                                                         self.kv_dim))
                names += list(self.kv_codec.stream_group("layer_v",
                                                         self.kv_dim))
            else:
                names += ["layer_k", "layer_v"]
        return names

    # -- device side -----------------------------------------------------------
    def _device_batch(self, tokens: np.ndarray, valid: np.ndarray):
        """Pad to the fixed batch shape, place on the mesh, encode ->
        ``(reps, valid)`` (valid padded to the batch shape, for the
        salience pass)."""
        n = len(tokens)
        if n < self._step_rows:
            pad = self._step_rows - n
            tokens = np.concatenate(
                [tokens, np.zeros((pad, tokens.shape[1]), tokens.dtype)])
            valid = np.concatenate(
                [valid, np.zeros((pad, valid.shape[1]), bool)])
        params = self.params
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as PS
            data = NamedSharding(self.mesh, PS("data", None))
            tokens = jax.device_put(tokens, data)
            valid = jax.device_put(valid, data)
            if self._params_replicated is None:
                self._params_replicated = jax.device_put(
                    params, NamedSharding(self.mesh, PS()))
            params = self._params_replicated
        valid = jnp.asarray(valid)
        return self._encode(params, jnp.asarray(tokens), valid), valid

    def _fit_codec(self, docs: Sequence[np.ndarray]):
        """Fit pass for trained codecs (pq): encode the first
        ``fit_sample`` docs through the build's fixed-shape jit and train
        on their valid-token reps."""
        buf = []
        n_fit = min(len(docs), self._fit_sample)
        for lo in range(0, n_fit, self._step_rows):
            chunk = docs[lo: lo + self._step_rows]
            tokens, lengths, valid = pack_doc_batch(
                chunk, self.cfg.max_doc_len)
            reps_dev, _ = self._device_batch(tokens, valid)
            reps = np.asarray(reps_dev)
            for i, n in enumerate(lengths):
                buf.append(np.asarray(reps[i, : int(n)], np.float32))
        if not buf:
            raise ValueError(
                f"codec {self.codec.name!r} needs a fit sample but the "
                f"corpus is empty")
        self.codec.fit(np.concatenate(buf), seed=self._fit_seed)

    # -- host side (writer thread) ---------------------------------------------
    def _write_loop(self, work_q: queue.Queue, writers: list[_ShardWriter],
                    boundaries: np.ndarray, err: list, write_s: list):
        while True:
            item = work_q.get()
            if item is _STOP:
                return
            try:
                self._write_batch(*item, writers, boundaries, write_s)
            except Exception as e:                    # noqa: BLE001
                err.append(e)
                return

    # -- the pipeline ----------------------------------------------------------
    def build(self, docs: Sequence[np.ndarray]) -> BuildReport:
        """Encode ``docs`` (raw token arrays; packed to ``[SEP]``-terminated
        fixed shapes here) and write the sharded v2 index."""
        t_wall = time.perf_counter()
        n_docs = len(docs)
        if self.codec.needs_fit:
            self._fit_codec(docs)
        ranges = shard_ranges(n_docs, self.n_shards)
        boundaries = np.asarray([lo for lo, _ in ranges], np.int64)
        writers = [_ShardWriter(self.out_dir, s, self._stream_names(),
                                self.checksum_chunk_bytes)
                   for s in range(self.n_shards)]
        err: list = []
        write_s = [0.0]
        work_q: queue.Queue = queue.Queue(maxsize=max(1, self.writer_depth))
        worker = None
        if self.writer_depth > 0:
            worker = threading.Thread(
                target=self._write_loop,
                args=(work_q, writers, boundaries, err, write_s), daemon=True)
            worker.start()

        encode_s = 0.0
        try:
            for lo in range(0, n_docs, self._step_rows):
                chunk = docs[lo: lo + self._step_rows]
                tokens, lengths, valid = pack_doc_batch(
                    chunk, self.cfg.max_doc_len)
                t0 = time.perf_counter()
                reps_dev, valid_dev = self._device_batch(tokens, valid)
                sal_dev = (self._salience(self._params_for_encode(),
                                          reps_dev, valid_dev)
                           if self.prune else None)
                kv_dev = (self._batch_kv(reps_dev)
                          if self.store_layer_kv else None)
                encode_s += time.perf_counter() - t0
                if worker is not None:
                    # bounded put that never deadlocks on a dead writer
                    while not err:
                        try:
                            work_q.put(
                                (reps_dev, kv_dev, sal_dev, lengths, lo),
                                timeout=0.1)
                            break
                        except queue.Full:
                            continue
                    if err:
                        break
                else:                       # synchronous debug path
                    self._write_batch(reps_dev, kv_dev, sal_dev, lengths, lo,
                                      writers, boundaries, write_s)
        finally:
            if worker is not None:
                while worker.is_alive():
                    try:
                        work_q.put(_STOP, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                worker.join()
            for w in writers:
                w.close()
        if err:
            raise err[0]

        manifest = {"version": FORMAT_VERSION, "codec": self.codec.name,
                    "rep_dim": self.rep_dim, "l": self.cfg.l,
                    "compressed": bool(self.cfg.compress_dim),
                    # pruned builds record the *kept* cap so serving
                    # configs (and gather_raw's default pad) can shrink
                    "max_doc_len": self.pruned_max_doc_len if self.prune
                    else self.cfg.max_doc_len,
                    "n_docs": n_docs,
                    # XLA output differs at the ulp across *batch shapes*
                    # (not row positions), so byte-exact re-verification
                    # must replay the build's fixed shape
                    "encode_batch": self.batch_size,
                    "shards": [w.manifest_row(with_orig=self.prune)
                               for w in writers]}
        if self.checksum_chunk_bytes > 0:
            manifest["checksum"] = {"algo": "crc32c",
                                    "chunk_bytes": self.checksum_chunk_bytes}
        state = self.codec.state_dict()
        if state is not None:
            manifest["codec_state"] = state
        if self.prune:
            manifest["prune"] = {"keep_frac": self.keep_frac,
                                 "max_kept_tokens": self.max_kept_tokens,
                                 "layer": self.cfg.l}
        if self.store_layer_kv:
            manifest["layer_kv"] = {"dtype": self._kv_payload_dtype.str,
                                    "d_kv": self.kv_dim}
            if self.kv_codec is not None:
                manifest["layer_kv"]["codec"] = self.kv_codec.name
        with open(os.path.join(self.out_dir, "manifest.msgpack"), "wb") as f:
            f.write(msgpack.packb(manifest))

        n_tokens = sum(sum(w.lengths) for w in writers)
        on_disk = sum(
            os.path.getsize(os.path.join(w.path, f"{name}.bin"))
            for w in writers for name in self._stream_names())
        return BuildReport(
            n_docs=n_docs, n_tokens=n_tokens, n_shards=self.n_shards,
            codec=self.codec.name, storage_bytes=on_disk,
            encode_s=encode_s, write_s=write_s[0],
            wall_s=time.perf_counter() - t_wall)

    def _params_for_encode(self):
        return (self._params_replicated
                if self._params_replicated is not None else self.params)

    def _write_batch(self, reps_dev, kv_dev, sal_dev, lengths, doc_lo,
                     writers, boundaries, write_s):
        """Materialize one device batch and append it to its shards.  The
        ``np.asarray`` blocks on the device — in the threaded path
        everything after it overlaps the device encoding the next batch.
        When pruning, each doc's surviving token rows are sliced here
        (encode and the K/V projections are per-token, so slicing before
        or after them is byte-identical)."""
        t0 = time.perf_counter()
        reps = np.asarray(reps_dev)
        sal = np.asarray(sal_dev) if sal_dev is not None else None
        kv = None
        if kv_dev is not None:
            kv = (np.asarray(kv_dev[0]).astype(self._kv_dtype),
                  np.asarray(kv_dev[1]).astype(self._kv_dtype))
        for i, n in enumerate(lengths):
            shard = int(np.searchsorted(boundaries, doc_lo + i,
                                        side="right") - 1)
            n = int(n)
            rows = (prune_selection(sal[i], n, self.keep_frac,
                                    self.max_kept_tokens)
                    if sal is not None else slice(None, n))
            parts = self.codec.encode(reps[i, rows])
            if kv is not None:
                if self.kv_codec is not None:
                    parts.update(self.kv_codec.encode_group(
                        "layer_k", kv[0][i, rows]))
                    parts.update(self.kv_codec.encode_group(
                        "layer_v", kv[1][i, rows]))
                else:
                    parts["layer_k"] = kv[0][i, rows]
                    parts["layer_v"] = kv[1][i, rows]
            kept = len(rows) if sal is not None else n
            writers[shard].append(parts, kept, orig_tokens=n)
        write_s[0] += time.perf_counter() - t0


def verify_index(index: TermRepIndex, cfg: P.PreTTRConfig, params,
                 docs: Sequence[np.ndarray], sample: int = 16,
                 seed: int = 0) -> int:
    """Re-encode a sample of ``docs`` and compare the stored streams
    byte-for-byte against a fresh ``precompute_docs`` pass (deterministic
    codecs make this exact for fp16 *and* int8).  The sample is encoded in
    the same fixed batch shape the build used (``manifest.encode_batch``) —
    per-row results are position-invariant but XLA output differs at the
    ulp across batch *shapes*.  Returns the number of docs checked; raises
    AssertionError on any mismatch."""
    rng = np.random.default_rng(seed)
    n = len(index)
    ids = np.sort(rng.choice(n, size=min(sample, n), replace=False)) \
        if n else np.zeros((0,), np.int64)
    if not len(ids):
        return 0
    codec = index.codec
    store_dtype = jnp.dtype(np.dtype(codec.encode_dtype))
    vcfg = dataclasses.replace(cfg, store_dtype=store_dtype)
    batch = int(getattr(index, "encode_batch", 0) or len(ids))
    encode = jax.jit(lambda p, d, v: P.precompute_docs(p, vcfg, d, v))
    encode_kv = jax.jit(lambda p, st: P.precompute_doc_kv(p, vcfg, st))
    encode_kv_raw = jax.jit(lambda p, parts: P.precompute_doc_kv(
        p, vcfg, codec.decode(parts)))
    prune = getattr(index, "prune_policy", None)
    salience = (jax.jit(lambda p, st, v: P.doc_salience(p, vcfg, st, v))
                if prune else None)
    orig_lens = np.asarray(index.orig_doc_lengths)
    parts, got_valid = index.gather_raw([int(i) for i in ids],
                                        pad_to=cfg.max_doc_len)
    kv_codec = index.kv_codec
    kv_dtype = None
    if index.has_layer_kv:
        kv_dtype = (np.dtype(kv_codec.encode_dtype) if kv_codec is not None
                    else np.dtype(index.layer_kv["dtype"]))
    for lo in range(0, len(ids), batch):
        chunk = ids[lo: lo + batch]
        tokens, lengths, valid = pack_doc_batch([docs[i] for i in chunk],
                                                cfg.max_doc_len)
        if len(chunk) < batch:           # replay the build's fixed shape
            pad = batch - len(chunk)
            tokens = np.concatenate(
                [tokens, np.zeros((pad, tokens.shape[1]), tokens.dtype)])
            valid = np.concatenate(
                [valid, np.zeros((pad, valid.shape[1]), bool)])
        reps_dev = encode(params, jnp.asarray(tokens), jnp.asarray(valid))
        reps = np.asarray(reps_dev)
        sal = (np.asarray(salience(params, reps_dev, jnp.asarray(valid)))
               if salience is not None else None)
        kv = None
        if index.has_layer_kv:
            if codec.decode_is_identity:
                kv_dev = encode_kv(params, reps_dev)
            else:                    # replay the build's codec round trip
                kv_dev = encode_kv_raw(
                    params, jax.device_put(codec.encode(reps)))
            kv = (np.asarray(kv_dev[0]).astype(kv_dtype),
                  np.asarray(kv_dev[1]).astype(kv_dtype))
        for i, (n_tok, rep) in enumerate(zip(lengths, reps)):
            row = lo + i
            n_tok = int(n_tok)
            if sal is not None:       # replay the build's prune selection
                rows = prune_selection(sal[i], n_tok, prune["keep_frac"],
                                       prune["max_kept_tokens"])
                stored = len(rows)
                assert orig_lens[ids[row]] == n_tok, (
                    f"doc {ids[row]} orig_lengths={orig_lens[ids[row]]} "
                    f"but the doc packs to {n_tok} tokens")
            else:
                rows = slice(None, n_tok)
                stored = n_tok
            want = codec.encode(rep[rows])
            if kv is not None:
                if kv_codec is not None:
                    want.update(kv_codec.encode_group(
                        "layer_k", kv[0][i, rows]))
                    want.update(kv_codec.encode_group(
                        "layer_v", kv[1][i, rows]))
                else:
                    want["layer_k"] = kv[0][i, rows]
                    want["layer_v"] = kv[1][i, rows]
            for name, arr in want.items():
                np.testing.assert_array_equal(
                    parts[name][row, :stored], arr,
                    err_msg=f"doc {ids[row]} stream {name!r} mismatch")
            assert int(got_valid[row].sum()) == stored, \
                f"doc {ids[row]} stored length mismatch"
    return len(ids)
