"""PreTTR: Precomputing Transformer Term Representations (paper §4).

Three phases, one parameter set:

* **Train** — :func:`rank_forward` runs the joint ``[CLS];q;[SEP];d;[SEP]``
  input with the split attention mask active in layers ``0..l`` (query and
  document tokens cannot attend across segments), optionally round-tripping
  the document reps through the compressor at the ``l`` boundary (fine-tune
  stage).  :func:`rank_pairs_loss` is the paper's pairwise softmax loss.
* **Index** — :func:`precompute_docs` pushes documents (alone) through layers
  ``0..l`` and returns the (compressed, fp16) term representations that the
  index stores.  Because of the split mask, these are bit-identical in
  function to what the joint forward would have produced for the doc side.
* **Query** — :func:`encode_query` runs the query through layers ``0..l``
  once (reused for every candidate); :func:`join_and_score` joins the query
  reps with the loaded doc reps, runs layers ``l..n-1`` jointly, and
  finishes with a **CLS-only final layer** (paper §6.3: the ranking score
  reads only [CLS], so the last layer computes a single attention row).
  The join is built around a :class:`JoinState` with two execution paths:
  the **fused** default keeps the query/doc segments as separate arrays —
  attention runs over the split K/V pair via the ``join_attention``
  backend op, and layer ``l`` can consume the index's precomputed doc K/V
  streams (:func:`precompute_doc_kv`, MORES-style) instead of re-projecting
  them per query — while ``fused=False`` is the legacy concat path (the
  equivalence oracle).

Equivalence invariant (tested in tests/test_prettr.py): for any (q, d),
``rank_forward == join_and_score(encode_query, precompute_docs)`` up to
storage-dtype rounding.  This is the property that makes index-time
precomputation *sound*, and it pins down every masking/position detail.

Positions: the query segment is padded to ``max_query_len`` so document
tokens always sit at positions ``max_query_len + i`` — index-time encoding
must use the same positions the joint forward would (the paper pads queries
for the same reason).

Compute backends: every hot path here dispatches through the pluggable
backend layer (``repro.models.backend``) selected by the backbone config —
``attn_impl`` ("plain" | "blocked" | "pallas") covers the split-mask layers
and the CLS-only final layer (which runs the flash-*decode* kernel under
"pallas"), ``compress_impl`` ("plain" | "pallas") covers the d->e->d
bottleneck.  The equivalence invariant above holds under every backend;
off-TPU the pallas kernels fall back to interpret mode automatically.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import compression as C
from repro.dist.context import maybe_shard
from repro.models import backend as B
from repro.models import layers as L
from repro.models import transformer as T


@dataclasses.dataclass(frozen=True)
class PreTTRConfig:
    backbone: T.TransformerConfig
    l: int = 6                       # layers precomputed (paper's sweep 1..11)
    max_query_len: int = 32          # [CLS] + query + [SEP], padded
    max_doc_len: int = 224           # doc + trailing [SEP], padded
    compress_dim: int = 0            # e; 0 disables compression
    store_dtype: Any = jnp.float16   # paper's 16-bit storage trick
    cls_only_last_layer: bool = True

    def __post_init__(self):
        # the backbone must be bidirectional with the split boundary at l
        assert not self.backbone.causal, "PreTTR backbone is an encoder"
        assert self.backbone.split_layers == self.l, \
            "backbone.split_layers must equal PreTTRConfig.l"
        assert 0 <= self.l < self.backbone.n_layers


def make_backbone(n_layers=12, d_model=768, n_heads=12, d_ff=3072,
                  vocab_size=30522, l=6, max_len=256, n_kv_heads=None,
                  **kw) -> T.TransformerConfig:
    """A Vanilla-BERT-style encoder (the paper's base model family).
    ``n_kv_heads`` < ``n_heads`` gives a GQA variant (served by every
    attention backend, incl. the pallas kernels)."""
    return T.TransformerConfig(
        name="prettr_bert", n_layers=n_layers, d_model=d_model,
        n_heads=n_heads, n_kv_heads=n_kv_heads or n_heads, d_ff=d_ff,
        vocab_size=vocab_size,
        causal=False, rope=False, learned_pos=max_len, segment_vocab=2,
        norm="layernorm", gated_mlp=False, activation="gelu", mlp_bias=True,
        qkv_bias=True, split_layers=l, **kw)


def init_prettr(key, cfg: PreTTRConfig):
    k1, k2, k3 = jax.random.split(key, 3)
    bb, bb_ax = T.init_params(k1, cfg.backbone)
    params = {"backbone": bb,
              "score_head": L.dense_init(k2, cfg.backbone.d_model, 1,
                                         cfg.backbone.param_dtype)}
    axes = {"backbone": bb_ax, "score_head": ("embed", None)}
    if cfg.compress_dim:
        params["compressor"], axes["compressor"] = C.init_compressor(
            k3, cfg.backbone.d_model, cfg.compress_dim,
            cfg.backbone.param_dtype)
    return params, axes


# ---------------------------------------------------------------------------
# Shared pieces
# ---------------------------------------------------------------------------


def _score_from_cls(params, cfg: PreTTRConfig, cls_rep):
    """cls_rep: [B, d] -> [B] ranking score (paper Eq. 1, W_combine)."""
    h = L.apply_norm(params["backbone"]["final_norm"], cls_rep,
                     cfg.backbone.norm)
    return (h @ params["score_head"].astype(h.dtype))[..., 0].astype(jnp.float32)


def _cls_only_layer(lp, x, cfg: T.TransformerConfig, *, positions, valid):
    """Final transformer layer computing only the [CLS] (index 0) row of
    attention — paper §6.3: a decode-shaped attention, dispatched through
    the backend registry (the pallas impl is the flash-decode kernel).
    x: [B, S, d] -> cls rep [B, d]."""
    b = x.shape[0]
    dh = cfg.dh
    cd = cfg.compute_dtype
    h = L.apply_norm(lp["ln1"], x, cfg.norm)
    p = lp["attn"]
    q = T.project_q(p, h[:, :1], cfg, positions=positions[:, :1])
    k, v = T.project_kv(p, h, cfg, positions=positions)
    # bidirectional single-row attention over the full sequence
    k_pos = positions
    q_pos = jnp.full((b, 1), jnp.iinfo(jnp.int32).max // 2, jnp.int32)
    out = B.get_impl("decode_attention", cfg.attn_impl)(
        q, k, v, cfg=cfg, scale=1.0 / math.sqrt(dh),
        k_pos=k_pos, q_pos=q_pos, window=-1, k_valid=valid,
        static_window=-1)
    out = out.reshape(b, 1, cfg.n_heads * dh) @ p["wo"].astype(cd)
    x_cls = x[:, :1] + out
    h2 = L.apply_norm(lp["ln2"], x_cls, cfg.norm)
    mlp_p = jax.tree.map(lambda a: a.astype(cd), lp["mlp"])
    x_cls = x_cls + L.mlp(mlp_p, h2, gated=cfg.gated_mlp,
                          activation=cfg.activation)
    return x_cls[:, 0]


def _maybe_roundtrip_docs(params, cfg: PreTTRConfig, x, segs):
    """Fine-tune-time compressor round-trip, applied to doc tokens only."""
    if not cfg.compress_dim:
        return x
    x_hat = C.roundtrip(params["compressor"], x, store_dtype=cfg.store_dtype,
                        compute_dtype=cfg.backbone.compute_dtype,
                        impl=cfg.backbone.compress_impl)
    return jnp.where((segs == 1)[..., None], x_hat, x)


# ---------------------------------------------------------------------------
# Train-time joint forward
# ---------------------------------------------------------------------------


def rank_forward(params, cfg: PreTTRConfig, tokens, segs, valid):
    """Joint [CLS];q;[SEP];d;[SEP] forward with the split mask in layers
    0..l.  tokens/segs/valid: [B, S] with S = max_query_len + max_doc_len.
    Returns scores [B]."""
    bcfg = cfg.backbone
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = T.embed(params["backbone"], bcfg, tokens, positions, segs)
    x, _ = T.run_layer_range(params["backbone"], bcfg, x, 0, cfg.l,
                             positions=positions, segs=segs, valid=valid,
                             seg_boundary=cfg.max_query_len)
    x = _maybe_roundtrip_docs(params, cfg, x, segs)
    last = bcfg.n_layers - (1 if cfg.cls_only_last_layer else 0)
    x, _ = T.run_layer_range(params["backbone"], bcfg, x, cfg.l, last,
                             positions=positions, segs=segs, valid=valid)
    if cfg.cls_only_last_layer:
        lp = jax.tree.map(lambda a: a[-1], params["backbone"]["layers"])
        cls = _cls_only_layer(lp, x, bcfg, positions=positions, valid=valid)
    else:
        cls = x[:, 0]
    return _score_from_cls(params, cfg, cls)


def rank_pairs_loss(params, cfg: PreTTRConfig, pos, neg):
    """Paper §5.3 pairwise softmax loss.  pos/neg: dicts with
    tokens/segs/valid [B, S]."""
    s_pos = rank_forward(params, cfg, pos["tokens"], pos["segs"], pos["valid"])
    s_neg = rank_forward(params, cfg, neg["tokens"], neg["segs"], neg["valid"])
    return jnp.mean(jax.nn.softplus(-(s_pos - s_neg)))


# ---------------------------------------------------------------------------
# Index-time / query-time split execution
# ---------------------------------------------------------------------------


def precompute_docs(params, cfg: PreTTRConfig, doc_tokens, doc_valid):
    """Index-time: [N, Ld] document tokens -> stored reps
    [N, Ld, e or d] in ``store_dtype``.  Documents sit at positions
    ``max_query_len + i`` — identical to their joint-forward positions."""
    bcfg = cfg.backbone
    n, ld = doc_tokens.shape
    positions = jnp.broadcast_to(cfg.max_query_len + jnp.arange(ld), (n, ld))
    segs = jnp.ones((n, ld), jnp.int32)
    x = T.embed(params["backbone"], bcfg, doc_tokens, positions, segs)
    # Split mask makes cross-segment attention impossible below l, so a
    # doc-only input is exactly the doc side of the joint forward.
    x, _ = T.run_layer_range(params["backbone"], bcfg, x, 0, cfg.l,
                             positions=positions, segs=segs, valid=doc_valid)
    if cfg.compress_dim:
        return C.compress(params["compressor"], x, store_dtype=cfg.store_dtype,
                          impl=bcfg.compress_impl)
    return x.astype(cfg.store_dtype)


def encode_query(params, cfg: PreTTRConfig, q_tokens, q_valid):
    """Query-time: [B, Lq] -> query reps [B, Lq, d] through layers 0..l.
    Computed once per query and reused across all candidate documents."""
    bcfg = cfg.backbone
    b, lq = q_tokens.shape
    positions = jnp.broadcast_to(jnp.arange(lq), (b, lq))
    segs = jnp.zeros((b, lq), jnp.int32)
    x = T.embed(params["backbone"], bcfg, q_tokens, positions, segs)
    x, _ = T.run_layer_range(params["backbone"], bcfg, x, 0, cfg.l,
                             positions=positions, segs=segs, valid=q_valid)
    return x


def precompute_doc_kv(params, cfg: PreTTRConfig, doc_store):
    """Index-time: layer-``l`` doc-side K/V from the *stored* reps — the
    join's query-invariant projections (MORES: the doc half of the first
    interaction layer never sees the query, so it can move to index time).

    ``doc_store``: [N, Ld, e|d] exactly as :func:`precompute_docs` returned
    it (the round-trip through the compressor / storage dtype is part of
    the definition: the streams must match what the query-time join would
    recompute from the index bytes).  Returns ``(k, v)`` each
    [N, Ld, n_kv_heads * dh] in ``cfg.store_dtype``.
    """
    bcfg = cfg.backbone
    x_d = _decode_doc_store(params, cfg, doc_store)
    n, ld, _ = x_d.shape
    pos_d = jnp.broadcast_to(cfg.max_query_len + jnp.arange(ld), (n, ld))
    lp = jax.tree.map(lambda a: a[cfg.l], params["backbone"]["layers"])
    h_d = L.apply_norm(lp["ln1"], x_d, bcfg.norm)
    k, v = T.project_kv(lp["attn"], h_d, bcfg, positions=pos_d,
                        rope_base=bcfg.layer_rope_bases()[cfg.l])
    flat = bcfg.n_kv_heads * bcfg.dh
    return (k.reshape(n, ld, flat).astype(cfg.store_dtype),
            v.reshape(n, ld, flat).astype(cfg.store_dtype))


def _decode_doc_store(params, cfg: PreTTRConfig, doc_store):
    """Index bytes -> join-input doc reps [B, Ld, d] in compute dtype."""
    bcfg = cfg.backbone
    if cfg.compress_dim:
        return C.decompress(params["compressor"], doc_store,
                            compute_dtype=bcfg.compute_dtype,
                            impl=bcfg.compress_impl)
    return doc_store.astype(bcfg.compute_dtype)


def doc_salience(params, cfg: PreTTRConfig, doc_store, doc_valid):
    """Index-time token salience for pruning: the attention mass each
    stored doc token *receives* at join layer ``l`` from the other tokens
    of its own document (layer-wise token compression, in the spirit of
    arXiv 2605.20683 — a token no other doc token attends to is unlikely
    to matter to the query either).

    ``doc_store``: [N, Ld, e|d] exactly as :func:`precompute_docs`
    returned it (round-trip included — the salience must rank the tokens
    the join will actually see).  Computes the layer-``l`` doc-side Q/K
    by the same ops the join runs (:func:`repro.models.transformer`'s
    ``project_q``/``project_kv``), softmaxes each valid query row over
    the valid keys, and sums the weight landing on every key position:
    returns [N, Ld] float32, 0 at invalid positions.

    Positionally sound for learned-position backbones only (the join
    layers consume positions exclusively through RoPE, which PreTTR's
    BERT config disables); ``IndexBuilder`` rejects pruning on RoPE
    backbones because dropped rows would shift the rope phases of every
    survivor."""
    bcfg = cfg.backbone
    x_d = _decode_doc_store(params, cfg, doc_store)
    n, ld, _ = x_d.shape
    pos_d = jnp.broadcast_to(cfg.max_query_len + jnp.arange(ld), (n, ld))
    lp = jax.tree.map(lambda a: a[cfg.l], params["backbone"]["layers"])
    h_d = L.apply_norm(lp["ln1"], x_d, bcfg.norm)
    rope_base = bcfg.layer_rope_bases()[cfg.l]
    q = T.project_q(lp["attn"], h_d, bcfg, positions=pos_d,
                    rope_base=rope_base)                    # [N, Ld, H, Dh]
    k, _ = T.project_kv(lp["attn"], h_d, bcfg, positions=pos_d,
                        rope_base=rope_base)                # [N, Ld, Hkv, Dh]
    if bcfg.n_kv_heads != bcfg.n_heads:                     # GQA: widen keys
        k = jnp.repeat(k, bcfg.n_heads // bcfg.n_kv_heads, axis=2)
    q = q.astype(jnp.float32)
    k = k.astype(jnp.float32)
    logits = jnp.einsum("nqhd,nkhd->nhqk", q, k) / jnp.sqrt(
        jnp.float32(bcfg.dh))
    v = jnp.asarray(doc_valid, bool)
    # finite mask (not -inf): an all-pad row would softmax to NaN and
    # poison the row-drop product below
    logits = jnp.where(v[:, None, None, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)                     # [N, H, Lq, Lk]
    w = jnp.where(v[:, None, :, None], w, 0.0)              # drop pad rows
    return (w.sum(axis=2).mean(axis=1) * v).astype(jnp.float32)


@dataclasses.dataclass
class PagedDocKV:
    """Stored layer-``l`` doc K/V living in the device doc cache's
    token-page pools, consumed by the join without ever materializing a
    dense per-batch copy (the pallas impl walks ``page_table`` in its
    index maps; the reference impls gather pages in-jit).

    ``k``/``v``: [P, page, Hkv * Dh] token-major pools (the device doc
    cache's layout; fp16 K/V as their ``uint16`` bits); ``valid``:
    [P, page] int pool (the cache's page 0 is all-zero, so padded
    page-table tails mask themselves); ``page_table``: [B, nP] i32;
    ``k_scale``/``v_scale``: optional [P, page] fp32 per-token dequant
    scale pools when the K/V pools hold raw int8 codec payload."""
    k: Any
    v: Any
    valid: Any
    page_table: Any
    k_scale: Any = None
    v_scale: Any = None


jax.tree_util.register_pytree_node(
    PagedDocKV,
    lambda p: ((p.k, p.v, p.valid, p.page_table, p.k_scale, p.v_scale),
               None),
    lambda _, c: PagedDocKV(*c),
)


@dataclasses.dataclass
class JoinState:
    """Query-time join operands, segment-resident.

    The two segments stay separate arrays end to end on the fused path —
    the ``[B, Lq+Ld, d]`` concatenation the legacy path materializes never
    exists; attention runs over the split K/V pair via the
    ``join_attention`` backend op.  ``doc_k``/``doc_v`` (optional) are the
    index's stored layer-``l`` K/V streams in model layout, letting layer
    ``l`` skip the doc-side K/V projections entirely; with
    ``doc_k_scale``/``doc_v_scale`` they are raw int8 payload plus
    per-token fp32 scales, dequantized inside the join impl (in-register
    for pallas).  ``doc_kv_paged`` replaces the dense pair with a
    :class:`PagedDocKV` pool view.
    """
    x_q: Any                         # [B, Lq, d] query reps (compute dtype)
    q_valid: Any                     # [B, Lq] bool
    x_d: Any                         # [B, Ld, d] decoded doc reps
    d_valid: Any                     # [B, Ld] bool
    doc_k: Any = None                # [B, Ld, Hkv, Dh] stored layer-l K
    doc_v: Any = None                # [B, Ld, Hkv, Dh] stored layer-l V
    doc_k_scale: Any = None          # [B, Ld] f32 (raw-int8 doc_k)
    doc_v_scale: Any = None          # [B, Ld] f32 (raw-int8 doc_v)
    doc_kv_paged: Any = None         # PagedDocKV
    fused: bool = True


def _stored_kv_operand(st: JoinState):
    """The layer-``l`` stored-KV operand of a JoinState in the form the
    split layer functions dispatch on (None / (k, v) / (k, v, ks, vs) /
    PagedDocKV)."""
    if st.doc_kv_paged is not None:
        return st.doc_kv_paged
    if st.doc_k is None:
        return None
    if st.doc_k_scale is not None:
        return (st.doc_k, st.doc_v, st.doc_k_scale, st.doc_v_scale)
    return (st.doc_k, st.doc_v)


def prepare_join(params, cfg: PreTTRConfig, q_reps, q_valid, doc_store,
                 doc_valid, *, doc_kv=None, fused: bool = True) -> JoinState:
    """Decode the index payload and build the :class:`JoinState` that
    :func:`score_join` consumes.  ``doc_kv`` supplies the stored
    layer-``l`` streams (fused path only) in one of three forms:
    ``(k, v)`` raw floats each [B, Ld, n_kv_heads * dh];
    ``(k, v, k_scale, v_scale)`` int8 payload plus [B, Ld] fp32 scales
    (dequantized inside the join impl); or a :class:`PagedDocKV` straight
    from the device doc cache, passed on as the cache holds it."""
    bcfg = cfg.backbone
    x_d = _decode_doc_store(params, cfg, doc_store)
    doc_k = doc_v = doc_k_scale = doc_v_scale = doc_kv_paged = None
    if doc_kv is not None:
        if not fused:
            raise ValueError(
                "stored layer-l doc K/V streams require the fused join "
                "path (the legacy concat path re-projects at layer l)")
        b, ld = x_d.shape[0], x_d.shape[1]
        hkv, dh = bcfg.n_kv_heads, bcfg.dh
        if isinstance(doc_kv, PagedDocKV):
            doc_kv_paged = doc_kv
        elif len(doc_kv) == 4:
            k, v, doc_k_scale, doc_v_scale = doc_kv
            # raw int8 payload: keep the narrow dtype — the join impl
            # dequantizes (in-register on pallas)
            doc_k = k.reshape(b, ld, hkv, dh)
            doc_v = v.reshape(b, ld, hkv, dh)
        else:
            doc_k, doc_v = (a.reshape(b, ld, hkv, dh)
                            .astype(bcfg.compute_dtype) for a in doc_kv)
    if fused:
        windows = bcfg.layer_windows()[cfg.l:]
        if bcfg.causal or any(w > 0 for w in windows) or bcfg.n_experts:
            raise ValueError(
                "the fused join path serves bidirectional, validity-masked "
                "dense join layers only (no causal/window masks, no MoE); "
                "pass fused=False for this architecture")
        if cfg.cls_only_last_layer and (bcfg.rope or bcfg.use_qk_norm):
            # the legacy CLS-only layer predates qk-norm and ropes its
            # query row at the [CLS] position; the split CLS layer shares
            # project_q/project_kv with the rest of the join, which would
            # silently diverge here — fail instead of drifting
            raise ValueError(
                "the fused join's CLS-only final layer does not support "
                "rope/use_qk_norm backbones; pass fused=False (PreTTR's "
                "BERT-style backbones use learned positions)")
    return JoinState(x_q=q_reps.astype(bcfg.compute_dtype), q_valid=q_valid,
                     x_d=x_d, d_valid=doc_valid, doc_k=doc_k, doc_v=doc_v,
                     doc_k_scale=doc_k_scale, doc_v_scale=doc_v_scale,
                     doc_kv_paged=doc_kv_paged, fused=fused)


def _unpack_stored_kv(doc_kv):
    """Unpack a stored-KV operand (``(k, v)`` / ``(k, v, ks, vs)`` /
    :class:`PagedDocKV`) into the operand set the ``join_attention`` impls
    take: ``(kd, vd, kd_scale, vd_scale, paged)``."""
    if isinstance(doc_kv, PagedDocKV):
        return None, None, None, None, doc_kv
    if len(doc_kv) == 4:
        kd, vd, ks, vs = doc_kv
        return kd, vd, ks, vs, None
    kd, vd = doc_kv
    return kd, vd, None, None, None


def _join_layer_split(lp, bcfg: T.TransformerConfig, x_q, x_d, q_valid,
                      d_valid, pos_q, pos_d, rope_base, doc_kv=None):
    """One join layer over the split residual (x_q, x_d) — the per-segment
    twin of ``transformer._layer_step`` for the mask-free join layers.
    Every non-attention op is row-wise, so running it per segment is
    bit-identical to running it on the concatenation; attention dispatches
    the ``join_attention`` backend op over the split K/V pair.  The (tiny,
    query-time-produced) Q blocks are stacked so each layer issues exactly
    one attention call — it is the K/V side, fed from index buffers, that
    is never concatenated."""
    cd = bcfg.compute_dtype
    dh = bcfg.dh
    lq = x_q.shape[1]
    h_q = L.apply_norm(lp["ln1"], x_q, bcfg.norm)
    h_d = L.apply_norm(lp["ln1"], x_d, bcfg.norm)
    p = lp["attn"]
    qq = T.project_q(p, h_q, bcfg, positions=pos_q, rope_base=rope_base)
    qd = T.project_q(p, h_d, bcfg, positions=pos_d, rope_base=rope_base)
    kq, vq = T.project_kv(p, h_q, bcfg, positions=pos_q, rope_base=rope_base)
    if doc_kv is None:
        kd, vd = T.project_kv(p, h_d, bcfg, positions=pos_d,
                              rope_base=rope_base)
        kd_scale = vd_scale = paged = None
    else:                      # layer l: index-stored, projections skipped
        kd, vd, kd_scale, vd_scale, paged = _unpack_stored_kv(doc_kv)
    impl = B.get_impl("join_attention", bcfg.attn_impl)
    out = impl(jnp.concatenate([qq, qd], axis=1), kq, vq, kd, vd, cfg=bcfg,
               scale=1.0 / math.sqrt(dh),
               q_valid=jnp.concatenate([q_valid, d_valid], axis=1),
               kq_valid=q_valid, kd_valid=d_valid,
               kd_scale=kd_scale, vd_scale=vd_scale, paged=paged)

    def _finish(x, out):
        b, s = x.shape[0], x.shape[1]
        attn_out = out.reshape(b, s, bcfg.n_heads * dh) @ p["wo"].astype(cd)
        return T.block_tail(lp, bcfg, x, attn_out)[0]

    return _finish(x_q, out[:, :lq]), _finish(x_d, out[:, lq:])


def _cls_only_layer_split(lp, bcfg: T.TransformerConfig, x_q, x_d, q_valid,
                          d_valid, pos_d, doc_kv=None):
    """Final CLS-only layer (paper §6.3) over the split residual: one
    attention row ([CLS] lives in the query segment) against the split K/V
    pair.  x_q: [B, Lq, d]; x_d: [B, Ld, d] -> cls rep [B, d]."""
    cd = bcfg.compute_dtype
    dh = bcfg.dh
    b, lq, _ = x_q.shape
    h_q = L.apply_norm(lp["ln1"], x_q, bcfg.norm)
    h_d = L.apply_norm(lp["ln1"], x_d, bcfg.norm)
    p = lp["attn"]
    q_pos = jnp.full((b, 1), jnp.iinfo(jnp.int32).max // 2, jnp.int32)
    q = T.project_q(p, h_q[:, :1], bcfg, positions=q_pos)
    pos_q = jnp.broadcast_to(jnp.arange(lq), (b, lq))
    kq, vq = T.project_kv(p, h_q, bcfg, positions=pos_q)
    if doc_kv is None:
        kd, vd = T.project_kv(p, h_d, bcfg, positions=pos_d)
        kd_scale = vd_scale = paged = None
    else:
        kd, vd, kd_scale, vd_scale, paged = _unpack_stored_kv(doc_kv)
    impl = B.get_impl("join_attention", bcfg.attn_impl)
    out = impl(q, kq, vq, kd, vd, cfg=bcfg, scale=1.0 / math.sqrt(dh),
               q_valid=jnp.ones((b, 1), bool), kq_valid=q_valid,
               kd_valid=d_valid,
               kd_scale=kd_scale, vd_scale=vd_scale, paged=paged)
    out = out.reshape(b, 1, bcfg.n_heads * dh) @ p["wo"].astype(cd)
    x_cls = x_q[:, :1] + out
    h2 = L.apply_norm(lp["ln2"], x_cls, bcfg.norm)
    mlp_p = jax.tree.map(lambda a: a.astype(cd), lp["mlp"])
    x_cls = x_cls + L.mlp(mlp_p, h2, gated=bcfg.gated_mlp,
                          activation=bcfg.activation)
    return x_cls[:, 0]


def _score_join_fused(params, cfg: PreTTRConfig, st: JoinState):
    """Fused query-time join: layers ``l..n-1`` over the split residual."""
    bcfg = cfg.backbone
    b, lq, _ = st.x_q.shape
    ld = st.x_d.shape[1]
    pos_q = jnp.broadcast_to(jnp.arange(lq), (b, lq))
    pos_d = jnp.broadcast_to(cfg.max_query_len + jnp.arange(ld), (b, ld))
    bases = bcfg.layer_rope_bases()
    last = bcfg.n_layers - (1 if cfg.cls_only_last_layer else 0)
    x_q, x_d = st.x_q, st.x_d
    layers = params["backbone"]["layers"]
    stored = _stored_kv_operand(st)
    for li in range(cfg.l, last):
        lp = jax.tree.map(lambda a: a[li], layers)
        dkv = stored if li == cfg.l else None
        x_q, x_d = _join_layer_split(lp, bcfg, x_q, x_d, st.q_valid,
                                     st.d_valid, pos_q, pos_d, bases[li],
                                     doc_kv=dkv)
        if bcfg.act_shard == "seq":
            x_q = maybe_shard(x_q, ("batch", "act_seq", None))
            x_d = maybe_shard(x_d, ("batch", "act_seq", None))
        elif bcfg.act_shard == "embed":
            x_q = maybe_shard(x_q, ("batch", None, "embed_tp"))
            x_d = maybe_shard(x_d, ("batch", None, "embed_tp"))
    if cfg.cls_only_last_layer:
        lp = jax.tree.map(lambda a: a[-1], layers)
        dkv = stored if cfg.l == last else None
        cls = _cls_only_layer_split(lp, bcfg, x_q, x_d, st.q_valid,
                                    st.d_valid, pos_d, doc_kv=dkv)
    else:
        cls = x_q[:, 0]
    return _score_from_cls(params, cfg, cls)


def _score_join_concat(params, cfg: PreTTRConfig, st: JoinState):
    """Legacy concat join: materialize [B, Lq+Ld, d] and run the join
    layers over it (the pre-fusion query-time path, kept as the
    equivalence oracle and for architectures the fused path rejects).

    The layers are unrolled (no scan/remat): the join depth ``n - l`` is
    small by design — the paper's entire speedup is serving few layers —
    and the layer-scan machinery's remat grouping perturbs fusion enough
    to cost bit-exactness against the fused path for zero serving-time
    benefit (there is no backward pass to checkpoint for)."""
    bcfg = cfg.backbone
    b, lq, _ = st.x_q.shape
    ld = st.x_d.shape[1]
    x = jnp.concatenate([st.x_q, st.x_d], axis=1)
    positions = jnp.broadcast_to(
        jnp.concatenate([jnp.arange(lq), cfg.max_query_len + jnp.arange(ld)]),
        (b, lq + ld))
    segs = jnp.concatenate([jnp.zeros((b, lq), jnp.int32),
                            jnp.ones((b, ld), jnp.int32)], axis=1)
    valid = jnp.concatenate([st.q_valid, st.d_valid], axis=1)
    last = bcfg.n_layers - (1 if cfg.cls_only_last_layer else 0)
    windows = bcfg.layer_windows()
    bases = bcfg.layer_rope_bases()
    for li in range(cfg.l, last):
        lp = jax.tree.map(lambda a: a[li], params["backbone"]["layers"])
        x, _, _ = T._layer_step(
            lp, x, bcfg, positions=positions, window=windows[li],
            rope_base=bases[li], split_flag=False, segs=segs, valid=valid,
            seg_boundary=-1, static_window=windows[li], static_split=False)
        if bcfg.act_shard == "seq":
            x = maybe_shard(x, ("batch", "act_seq", None))
        elif bcfg.act_shard == "embed":
            x = maybe_shard(x, ("batch", None, "embed_tp"))
    if cfg.cls_only_last_layer:
        lp = jax.tree.map(lambda a: a[-1], params["backbone"]["layers"])
        cls = _cls_only_layer(lp, x, bcfg, positions=positions, valid=valid)
    else:
        cls = x[:, 0]
    return _score_from_cls(params, cfg, cls)


def score_join(params, cfg: PreTTRConfig, st: JoinState):
    return (_score_join_fused if st.fused else _score_join_concat)(
        params, cfg, st)


def join_and_score(params, cfg: PreTTRConfig, q_reps, q_valid, doc_store,
                   doc_valid, *, doc_kv=None, fused: bool = True):
    """Query-time join: q_reps [B, Lq, d] (+valid), doc_store [B, Ld, e|d]
    (loaded from the index) -> scores [B].  Runs layers l..n-1 jointly and
    a CLS-only final layer.

    ``fused=True`` (default — the serving hot path) keeps the two segments
    as separate arrays and attends over the split K/V pair via the
    ``join_attention`` backend op; ``doc_kv`` may supply the index's stored
    layer-``l`` doc K/V streams so layer ``l`` skips all doc-side K/V
    projections — as a dense ``(k, v)`` float pair, a
    ``(k, v, k_scale, v_scale)`` raw-int8 quadruple, or a
    :class:`PagedDocKV` cache-pool view (see :func:`prepare_join`).
    ``fused=False`` is the legacy concat path.  Both paths
    satisfy the equivalence invariant against :func:`rank_forward`; under
    the reference (plain/blocked) backends they are bit-identical to each
    other (tests/test_join_attention.py).
    """
    st = prepare_join(params, cfg, q_reps, q_valid, doc_store, doc_valid,
                      doc_kv=doc_kv, fused=fused)
    return score_join(params, cfg, st)
