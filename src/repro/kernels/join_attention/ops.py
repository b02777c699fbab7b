"""Public wrappers for split-KV join attention: pad-to-block, pick interpret
mode off-TPU, jit.  Two entry points: the dense kernel (optionally with
raw-int8 doc K/V + per-token scales) and the paged kernel that scores
straight out of the device doc cache's token-page pools."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.join_attention.kernel import (join_attention_pallas,
                                                 join_attention_pallas_paged)
from repro.kernels.masking import last_valid_lengths
from repro.kernels.tpu import as_f16_bits, interpret_mode, sublane


def _q_segment_operands(q, kq, vq, kq_valid, block_q):
    """Pad ``q`` to whole ``bq`` row blocks (at least one native tile of
    its dtype — the CLS row is one row) and the whole-block query-segment
    K/V to a sublane multiple; validity goes in the kernels' ``[B, 1, L]``
    row layout."""
    b, _, sq, _ = q.shape
    lq = kq.shape[2]
    if kq_valid is None:
        kq_valid = jnp.ones((b, lq), jnp.int32)
    bq = min(block_q, max(sublane(q.dtype), sq))
    pad_q = (-sq) % bq
    pad_lq = (-lq) % sublane(kq.dtype)
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_lq:
        kq = jnp.pad(kq, ((0, 0), (0, 0), (0, pad_lq), (0, 0)))
        vq = jnp.pad(vq, ((0, 0), (0, 0), (0, pad_lq), (0, 0)))
    kq_valid = jnp.pad(kq_valid.astype(jnp.int32), ((0, 0), (0, pad_lq)))
    return q, kq, vq, kq_valid[:, None, :], bq


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret"))
def join_flash_attention(q, kq, vq, kd, vd, kq_valid=None, kd_valid=None,
                         kd_scales=None, vd_scales=None, *,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool | None = None):
    """Attention of ``q`` over the union of two K/V segments, never
    concatenated: the query-segment pair (``kq``/``vq`` — PreTTR's freshly
    encoded query tokens, bounded by ``max_query_len``) and the doc-segment
    pair (``kd``/``vd`` — index-loaded term reps / stored layer-``l``
    streams).

    q: [B, Hq, Sq, D] (Sq may be the query segment, the doc segment, or a
    single CLS row); kq, vq: [B, Hkv, Lq, D]; kd, vd: [B, Hkv, Ld, D];
    kq_valid / kd_valid: optional [B, Lq] / [B, Ld] boolean key-validity
    masks (non-prefix layouts supported).  ``kd_scales`` / ``vd_scales``
    (optional, both or neither): [B, Ld] fp32 per-token dequant scales for
    raw-int8 ``kd``/``vd`` — the KV tiles are widened in registers inside
    the kernel's doc-segment loop, bit-exact vs decode-then-attend.
    Bidirectional, validity-masked only — the PreTTR join layers carry no
    causal/window/split structure.  Pads every sequence dim to tile
    multiples; pad tails are masked and sliced off the output.
    Returns [B, Hq, Sq, D].
    """
    if interpret is None:
        interpret = interpret_mode()
    b, sq, ld = q.shape[0], q.shape[2], kd.shape[2]
    if kd_valid is None:
        kd_valid = jnp.ones((b, ld), jnp.int32)
    dlen = last_valid_lengths(kd_valid, ld)
    q, kq, vq, kq_valid, bq = _q_segment_operands(q, kq, vq, kq_valid,
                                                  block_q)
    bk = min(block_k, max(8, ld))
    pad_d = (-ld) % bk
    if pad_d:
        kd = jnp.pad(kd, ((0, 0), (0, 0), (0, pad_d), (0, 0)))
        vd = jnp.pad(vd, ((0, 0), (0, 0), (0, pad_d), (0, 0)))
    kd_valid = jnp.pad(kd_valid.astype(jnp.int32), ((0, 0), (0, pad_d)))
    if kd_scales is not None:
        kd_scales = kd_scales.astype(jnp.float32)
        vd_scales = vd_scales.astype(jnp.float32)
        if pad_d:
            kd_scales = jnp.pad(kd_scales, ((0, 0), (0, pad_d)))
            vd_scales = jnp.pad(vd_scales, ((0, 0), (0, pad_d)))
        kd_scales = kd_scales[..., None]    # [B, Ld, 1] — row-broadcast
        vd_scales = vd_scales[..., None]
    # fp16 doc K/V cross the kernel boundary as ``uint16`` bits (the kernel
    # widens them in registers)
    out = join_attention_pallas(q, kq, vq, as_f16_bits(kd), as_f16_bits(vd),
                                dlen.astype(jnp.int32),
                                kq_valid, kd_valid[:, None, :],
                                block_q=bq, block_k=bk, interpret=interpret,
                                kd_scales=kd_scales, vd_scales=vd_scales)
    return out[:, :, :sq]


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def join_flash_attention_paged(q, kq, vq, kd_pages, vd_pages, page_table,
                               dval_pages, kq_valid=None,
                               kd_scale_pages=None, vd_scale_pages=None, *,
                               block_q: int = 128,
                               interpret: bool | None = None):
    """Paged doc segment: doc K/V stay in the device doc cache's token-page
    pools and the kernel's page index map walks the page table — no
    per-batch dense KV copy is ever materialized, and every pool goes to
    the kernel as it lies in the cache (no relayout, cast or pad).

    q: [B, Hq, Sq, D]; kq, vq: [B, Hkv, Lq, D];
    kd_pages, vd_pages: [P, page, Hkv * D] token-major pools;
    page_table: [B, nP] i32 pool page per (row, doc page), tail entries
    pointing at the cache's all-zero page 0; dval_pages: [P, page]
    token-validity pool (page 0 is all-zero, so padded tails mask
    themselves); kd_scale_pages / vd_scale_pages: optional [P, page] fp32
    scale pools for raw-int8 KV pools.
    Returns [B, Hq, Sq, D]; the doc segment spans nP * page assembled
    positions."""
    if interpret is None:
        interpret = interpret_mode()
    b, sq = q.shape[0], q.shape[2]
    page_table = page_table.astype(jnp.int32)
    # validity and valid length of each assembled row, gathered from the
    # validity pool (a tiny [B, nP * page] gather; the K/V and scale pools
    # go to the kernel whole and are never densified)
    dval_rows = dval_pages[page_table].reshape(b, -1).astype(jnp.int32)
    dlen = last_valid_lengths(dval_rows, dval_rows.shape[1])
    q, kq, vq, kq_valid, bq = _q_segment_operands(q, kq, vq, kq_valid,
                                                  block_q)
    out = join_attention_pallas_paged(
        q, kq, vq, as_f16_bits(kd_pages), as_f16_bits(vd_pages), page_table,
        dlen, kq_valid, dval_rows.reshape(-1, 1, dval_pages.shape[1]),
        block_q=bq, interpret=interpret,
        kd_scale_pages=kd_scale_pages, vd_scale_pages=vd_scale_pages)
    return out[:, :, :sq]
