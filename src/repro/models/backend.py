"""Pluggable compute backends for the hot paths (attention / decode /
compress / decompress).

The Pallas kernel subsystems (``kernels/split_attention``,
``kernels/decode_attention``, ``kernels/fused_compress``) implement the
paper's fast paths; this module is the seam that lets the model, the PreTTR
core and the serving layer pick between the pure-XLA reference
implementations and the kernels without code changes — one string knob per
``TransformerConfig`` (``attn_impl`` for both attention flavours,
``compress_impl`` for the bottleneck).

Registry
--------
Implementations are registered per *kind* under a name::

    get_impl("attention", "pallas")(q, k, v, cfg=cfg, ...)

Kinds and their call contracts (all arrays in **model layout**):

* ``attention(q, k, v, *, cfg, scale, positions, window, split_flag, segs,
  valid, seg_boundary, static_window, static_split)`` —
  q ``[B, Sq, Hq, D]``; k, v ``[B, Skv, Hkv, D]`` (GQA: ``Hkv <= Hq``).
  Returns ``[B, Sq, Hq, D]``.
* ``decode_attention(q, k, v, *, cfg, scale, q_pos, k_pos, window, k_valid,
  lengths, static_window)`` — q ``[B, 1, Hq, D]``; k, v ``[B, S, Hkv, D]``.
  One query row against a full K/V sequence: the transformer decode step
  and the PreTTR CLS-only final layer (paper §6.3).
* ``join_attention(q, kq, vq, kd, vd, *, cfg, scale, q_valid, kq_valid,
  kd_valid, kd_scale, vd_scale, paged)`` — q ``[B, Sq, Hq, D]``; kq, vq
  ``[B, Lq, Hkv, D]`` (the freshly-encoded query segment); kd, vd
  ``[B, Ld, Hkv, D]`` (index-loaded doc segment).  Attention over the
  *union* of the two K/V segments — PreTTR's query-time join layers
  (``l..n-1``), which are bidirectional and validity-masked only.  The
  reference impls concatenate the segments and reuse the regular attention
  cores (so the fused join path stays bit-exact with the legacy concat
  path); the ``pallas`` impl is the split-KV flash kernel, which never
  materializes the concatenation.  Two optional doc-segment forms:
  ``kd_scale``/``vd_scale`` (``[B, Ld]`` fp32, both or neither) mark
  ``kd``/``vd`` as raw int8 codec payload dequantized on the fly (the
  reference impls widen before the concat, the pallas impl dequantizes
  in-register inside the KV tile loop); ``paged`` (an object with
  ``k``/``v`` ``[P, page, Hkv * D]`` pools, ``page_table`` ``[B, nP]``,
  ``valid`` ``[P, page]``, optional ``k_scale``/``v_scale``
  ``[P, page]`` — ``repro.core.prettr.PagedDocKV``) replaces ``kd``/
  ``vd`` entirely with the device doc cache's token-page pools: the
  reference impls gather the pages into dense rows in-jit, the pallas
  impl walks the page table in its index maps.
* ``compress(params, x, *, store_dtype)`` / ``decompress(params, r, *,
  compute_dtype)`` — the paper's d->e->d bottleneck (§4.2).

Layout adapters
---------------
The Pallas kernels use ``[B, H, S, D]`` and per-row valid *lengths*; the
model uses ``[B, S, H, D]`` and boolean ``valid`` masks.  The ``pallas``
impls transpose at the boundary and forward the full boolean mask; the
kernel ops wrappers derive ``lengths`` (last valid index plus one,
``repro.kernels.masking``) for tile skipping, so non-prefix validity
(PreTTR's padded-query + padded-doc two-prefix pattern) is masked exactly.

Static-mask contract (``pallas`` only)
--------------------------------------
The kernels specialize their masks at trace time, so the ``pallas`` impls
need *static* values: ``static_window``/``static_split`` (the dispatcher in
``transformer._run_layers`` resolves these from the config and raises if a
layer range mixes different windows or split flags) and ``seg_boundary``
(the static token index where segment 0 ends — ``max_query_len`` for the
joint PreTTR forward, ``-1`` for single-segment ranges).  Mask positions
are token indices, which matches every caller in this repo (sequences are
``arange``-positioned wherever causal/window/split masks are active).

Off-TPU the kernel wrappers run in Pallas interpret mode
(``interpret=None`` -> ``repro.kernels.tpu.interpret_mode()``), so every
backend runs — and is tested — on CPU.
"""
from __future__ import annotations

from typing import Callable

import jax.numpy as jnp

from repro.kernels.decode_attention import flash_decode_attention
from repro.kernels.fused_compress import fused_compress, fused_decompress
from repro.kernels.join_attention import (join_flash_attention,
                                          join_flash_attention_paged,
                                          pages_to_dense)
from repro.kernels.split_attention import split_flash_attention
from repro.models import layers as L

KINDS = ("attention", "decode_attention", "join_attention", "compress",
         "decompress")

_REGISTRY: dict[str, dict[str, Callable]] = {k: {} for k in KINDS}


def register(kind: str, name: str):
    """Decorator: register ``fn`` as the ``name`` implementation of
    ``kind``.  Re-registering a name overwrites (tests / downstream
    extensions)."""
    if kind not in _REGISTRY:
        raise ValueError(f"unknown backend kind {kind!r}; kinds: {KINDS}")

    def deco(fn):
        _REGISTRY[kind][name] = fn
        return fn
    return deco


def available(kind: str) -> list[str]:
    if kind not in _REGISTRY:
        raise ValueError(f"unknown backend kind {kind!r}; kinds: {KINDS}")
    return sorted(_REGISTRY[kind])


def get_impl(kind: str, name: str) -> Callable:
    impls = _REGISTRY.get(kind)
    if impls is None:
        raise ValueError(f"unknown backend kind {kind!r}; kinds: {KINDS}")
    fn = impls.get(name)
    if fn is None:
        raise ValueError(
            f"unknown {kind} implementation {name!r}; "
            f"available: {available(kind)}")
    return fn


def impls_for(backend: str) -> tuple[str, str]:
    """Map a backend family name to ``(attn_impl, compress_impl)`` — the
    single place that knows the compressor has no "blocked" flavour, so
    only "pallas" routes it off "plain"."""
    return backend, ("pallas" if backend == "pallas" else "plain")


def transformer_config_of(cfg):
    """The TransformerConfig carrying the backend knobs: ``cfg`` itself, its
    ``backbone`` *field* (PreTTRConfig — a backbone() method, as on
    Bert4RecConfig, is not this case), or None if neither has them."""
    import dataclasses

    bb = getattr(cfg, "backbone", None)
    if dataclasses.is_dataclass(bb) and hasattr(bb, "attn_impl"):
        return bb
    return cfg if hasattr(cfg, "attn_impl") else None


def apply_backend(cfg, backend: str):
    """Copy of ``cfg`` — a TransformerConfig, or any dataclass carrying one
    as a ``backbone`` field (PreTTRConfig) — rerouted through the
    ``backend`` family (attn_impl + compress_impl)."""
    import dataclasses

    attn_impl, compress_impl = impls_for(backend)
    tcfg = transformer_config_of(cfg)
    if tcfg is not None and tcfg is not cfg:
        return dataclasses.replace(cfg, backbone=dataclasses.replace(
            tcfg, attn_impl=attn_impl, compress_impl=compress_impl))
    return dataclasses.replace(cfg, attn_impl=attn_impl,
                               compress_impl=compress_impl)


def validate_config(attn_impl: str, compress_impl: str) -> None:
    """Raise ValueError for unknown impl names (config-construction time,
    so a typo cannot silently fall through to a default branch).  Each knob
    dispatches two kinds (attention+decode, compress+decompress), so both
    registries must know the name — a half-registered extension would
    otherwise fail deep inside a jit trace.  The join_attention impl must
    additionally accept the quantized/paged doc-segment operands
    (``kd_scale``/``vd_scale``/``paged``) — serving hands every impl the
    same operand set, so a third-party impl missing them would fail on the
    first int8 or paged-cache batch."""
    import inspect

    for kind, name in (("attention", attn_impl),
                       ("decode_attention", attn_impl),
                       ("join_attention", attn_impl)):
        if name not in _REGISTRY[kind]:
            raise ValueError(
                f"unknown attn_impl {name!r} (no {kind} registration); "
                f"available: {available(kind)}")
    join_fn = _REGISTRY["join_attention"][attn_impl]
    params = inspect.signature(join_fn).parameters
    has_var_kw = any(p.kind is inspect.Parameter.VAR_KEYWORD
                     for p in params.values())
    missing = [kw for kw in ("kd_scale", "vd_scale", "paged")
               if kw not in params]
    if missing and not has_var_kw:
        raise ValueError(
            f"join_attention impl {attn_impl!r} does not accept the "
            f"quantized/paged doc-segment keywords {missing}; every join "
            f"impl must take kd_scale/vd_scale/paged (or **kwargs)")
    for kind, name in (("compress", compress_impl),
                       ("decompress", compress_impl)):
        if name not in _REGISTRY[kind]:
            raise ValueError(
                f"unknown compress_impl {name!r} (no {kind} registration); "
                f"available: {available(kind)}")


# ---------------------------------------------------------------------------
# attention: full-sequence self-attention (train / prefill / PreTTR layers)
# ---------------------------------------------------------------------------


@register("attention", "plain")
def _attention_plain(q, k, v, *, cfg, scale, positions, window, split_flag,
                     segs, valid, seg_boundary=-1, static_window=None,
                     static_split=None):
    del seg_boundary, static_window, static_split
    mask = L.attention_mask(positions, positions, causal=cfg.causal,
                            window=window, q_seg=segs, k_seg=segs,
                            split_segments=split_flag,
                            q_valid=valid, k_valid=valid)
    return L.plain_attention(q, k, v, mask[:, None], scale=scale)


@register("attention", "blocked")
def _attention_blocked(q, k, v, *, cfg, scale, positions, window, split_flag,
                       segs, valid, seg_boundary=-1, static_window=None,
                       static_split=None):
    del seg_boundary, static_window, static_split
    return L.blocked_attention(
        q, k, v, scale=scale, block_kv=cfg.block_kv,
        q_pos=positions, k_pos=positions, causal=cfg.causal, window=window,
        q_seg=segs, k_seg=segs, split_segments=split_flag, k_valid=valid)


@register("attention", "pallas")
def _attention_pallas(q, k, v, *, cfg, scale, positions, window, split_flag,
                      segs, valid, seg_boundary=-1, static_window=None,
                      static_split=None):
    del scale, positions, window, split_flag, segs  # static contract below
    if static_window is None or static_split is None:
        raise ValueError(
            "attn_impl='pallas' needs static per-range window/split "
            "metadata; this layer range mixes values — use 'blocked' or "
            "run the heterogeneous layers via separate layer_slice ranges")
    boundary = seg_boundary if static_split else -1
    qt = q.transpose(0, 2, 1, 3)                   # [B, S, H, D] -> [B, H, S, D]
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    # the ops wrapper derives per-row lengths (last valid + 1) from k_valid
    out = split_flash_attention(
        qt, kt, vt, None, k_valid=valid, causal=cfg.causal,
        window=int(static_window), seg_boundary=int(boundary))
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# decode_attention: one query row vs a K/V sequence (decode, CLS-only layer)
# ---------------------------------------------------------------------------


@register("decode_attention", "plain")
@register("decode_attention", "blocked")   # no blocked flavour: jnp reference
def _decode_plain(q, k, v, *, cfg, scale, q_pos, k_pos, window, k_valid=None,
                  lengths=None, static_window=None):
    del cfg, lengths, static_window
    return L.decode_attention(q, k, v, scale=scale, k_pos=k_pos, q_pos=q_pos,
                              window=window, k_valid=k_valid)


@register("decode_attention", "pallas")
def _decode_pallas(q, k, v, *, cfg, scale, q_pos, k_pos, window, k_valid=None,
                   lengths=None, static_window=None):
    del cfg, scale, q_pos, k_pos, window
    if static_window is None:
        raise ValueError(
            "attn_impl='pallas' decode needs a static window; this layer "
            "range mixes window sizes — use 'blocked'")
    qt = q.transpose(0, 2, 1, 3)                   # [B, 1, H, D] -> [B, H, 1, D]
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_decode_attention(qt, kt, vt, lengths, k_valid=k_valid,
                                 window=int(static_window))
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# join_attention: split-KV attention over (query segment, doc segment) —
# PreTTR's query-time join layers (bidirectional, validity-masked only)
# ---------------------------------------------------------------------------


def _concat_join_operands(q, kq, vq, kd, vd, kq_valid, kd_valid):
    b = q.shape[0]
    k = jnp.concatenate([kq, kd], axis=1)
    v = jnp.concatenate([vq, vd], axis=1)
    if kq_valid is None:
        kq_valid = jnp.ones((b, kq.shape[1]), bool)
    if kd_valid is None:
        kd_valid = jnp.ones((b, kd.shape[1]), bool)
    k_valid = jnp.concatenate([kq_valid.astype(bool),
                               kd_valid.astype(bool)], axis=1)
    return k, v, k_valid


def _densify_paged(paged, kd_valid, heads):
    """Reference-impl form of the paged doc segment: gather the cache's
    token pages into dense ``[B, Ld, Hkv, D]`` rows (``heads`` is ``(Hkv,
    D)``; inside the caller's jit), sliced to the caller's dense doc
    length so the concat cores see exactly the shapes the slot-cache path
    fed them — which is what keeps paged scores bit-exact vs the slot
    cache on float KV."""
    ld = kd_valid.shape[1] if kd_valid is not None else None

    def rows(pool):
        return pages_to_dense(pool, paged.page_table)[:, :ld]

    kd, vd = (rows(pool) for pool in (paged.k, paged.v))
    kd = kd.reshape(kd.shape[:2] + tuple(heads))
    vd = vd.reshape(kd.shape)
    kd_scale = vd_scale = None
    if paged.k_scale is not None:
        kd_scale, vd_scale = rows(paged.k_scale), rows(paged.v_scale)
    return kd, vd, kd_scale, vd_scale


def _dequant_kv(kd, vd, kd_scale, vd_scale, cfg):
    """Widen raw-int8 doc K/V with per-token fp32 scales — the same
    elementwise math as a standalone codec-decode dispatch followed by
    ``prepare_join``'s compute-dtype cast, so the reference impls stay
    bit-exact with decode-then-attend."""
    kd = (kd.astype(jnp.float32)
          * kd_scale.astype(jnp.float32)[..., None, None]) \
        .astype(cfg.compute_dtype)
    vd = (vd.astype(jnp.float32)
          * vd_scale.astype(jnp.float32)[..., None, None]) \
        .astype(cfg.compute_dtype)
    return kd, vd


def _join_decode_row(q, k, v, k_valid, *, scale):
    """Single-row join (the CLS-only final layer) through the decode core —
    the same reference the legacy path's ``decode_attention`` dispatch
    runs, so fused-vs-concat stays bit-exact for the last layer too."""
    b = q.shape[0]
    q_pos = jnp.full((b, 1), jnp.iinfo(jnp.int32).max // 2, jnp.int32)
    k_pos = jnp.broadcast_to(jnp.arange(k.shape[1]), (b, k.shape[1]))
    return L.decode_attention(q, k, v, scale=scale, k_pos=k_pos, q_pos=q_pos,
                              window=-1, k_valid=k_valid)


@register("join_attention", "plain")
def _join_plain(q, kq, vq, kd, vd, *, cfg, scale, q_valid=None,
                kq_valid=None, kd_valid=None, kd_scale=None, vd_scale=None,
                paged=None):
    # reference semantics == the legacy concat path: concatenate the K/V
    # segments (bitwise-neutral) and run the same plain core on the same
    # shapes, so fused-vs-concat stays bit-exact under this impl
    b, sq = q.shape[0], q.shape[1]
    if paged is not None:
        kd, vd, kd_scale, vd_scale = _densify_paged(paged, kd_valid,
                                                    kq.shape[2:])
        if kd_scale is None:        # float pools: slot-path dtype parity
            kd, vd = kd.astype(cfg.compute_dtype), vd.astype(cfg.compute_dtype)
    if kd_scale is not None:
        kd, vd = _dequant_kv(kd, vd, kd_scale, vd_scale, cfg)
    k, v, k_valid = _concat_join_operands(q, kq, vq, kd, vd,
                                          kq_valid, kd_valid)
    if sq == 1:
        return _join_decode_row(q, k, v, k_valid, scale=scale)
    mask = jnp.broadcast_to(k_valid[:, None, :], (b, sq, k.shape[1]))
    if q_valid is not None:
        mask = mask & q_valid[:, :, None]
    return L.plain_attention(q, k, v, mask[:, None], scale=scale)


@register("join_attention", "blocked")
def _join_blocked(q, kq, vq, kd, vd, *, cfg, scale, q_valid=None,
                  kq_valid=None, kd_valid=None, kd_scale=None, vd_scale=None,
                  paged=None):
    del q_valid                       # parity with the blocked legacy impl
    b, sq = q.shape[0], q.shape[1]
    if paged is not None:
        kd, vd, kd_scale, vd_scale = _densify_paged(paged, kd_valid,
                                                    kq.shape[2:])
        if kd_scale is None:        # float pools: slot-path dtype parity
            kd, vd = kd.astype(cfg.compute_dtype), vd.astype(cfg.compute_dtype)
    if kd_scale is not None:
        kd, vd = _dequant_kv(kd, vd, kd_scale, vd_scale, cfg)
    k, v, k_valid = _concat_join_operands(q, kq, vq, kd, vd,
                                          kq_valid, kd_valid)
    if sq == 1:                       # "blocked" decode == the jnp reference
        return _join_decode_row(q, k, v, k_valid, scale=scale)
    # positions only feed the (disabled) causal/window mask terms
    q_pos = jnp.broadcast_to(jnp.arange(sq), (b, sq))
    k_pos = jnp.broadcast_to(jnp.arange(k.shape[1]), (b, k.shape[1]))
    return L.blocked_attention(
        q, k, v, scale=scale, block_kv=cfg.block_kv, q_pos=q_pos,
        k_pos=k_pos, causal=False, window=-1, k_valid=k_valid)


@register("join_attention", "pallas")
def _join_pallas(q, kq, vq, kd, vd, *, cfg, scale, q_valid=None,
                 kq_valid=None, kd_valid=None, kd_scale=None, vd_scale=None,
                 paged=None):
    del scale, q_valid                # kernel derives scale; rows w/o valid
    qt = q.transpose(0, 2, 1, 3)      # keys behave as in split_attention
    kqt = kq.transpose(0, 2, 1, 3)
    vqt = vq.transpose(0, 2, 1, 3)
    if paged is not None:
        # the kernel's page index map walks the page table — the pools
        # ([P, page, Hkv * D]) go in as the cache holds them and no dense
        # per-batch KV copy is materialized
        out = join_flash_attention_paged(
            qt, kqt, vqt, paged.k, paged.v, paged.page_table, paged.valid,
            kq_valid=kq_valid, kd_scale_pages=paged.k_scale,
            vd_scale_pages=paged.v_scale)
        return out.transpose(0, 2, 1, 3)
    out = join_flash_attention(
        qt, kqt, vqt,
        kd.transpose(0, 2, 1, 3), vd.transpose(0, 2, 1, 3),
        kq_valid=kq_valid, kd_valid=kd_valid,
        kd_scales=kd_scale, vd_scales=vd_scale)
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# compress / decompress: the PreTTR d->e->d bottleneck (paper §4.2)
# ---------------------------------------------------------------------------


@register("compress", "plain")
def _compress_plain(params, x, *, store_dtype=jnp.float16):
    from repro.core.compression import compress_jnp
    return compress_jnp(params, x, store_dtype=store_dtype)


@register("compress", "pallas")
def _compress_pallas(params, x, *, store_dtype=jnp.float16):
    return fused_compress(x, params["w_comp"], params["b_comp"],
                          out_dtype=store_dtype)


@register("decompress", "plain")
def _decompress_plain(params, r, *, compute_dtype=jnp.bfloat16):
    from repro.core.compression import decompress_jnp
    return decompress_jnp(params, r, compute_dtype=compute_dtype)


@register("decompress", "pallas")
def _decompress_pallas(params, r, *, compute_dtype=jnp.bfloat16):
    return fused_decompress(r, params["w_decomp"], params["b_decomp"],
                            params["ln"]["scale"], params["ln"]["bias"],
                            out_dtype=compute_dtype)
