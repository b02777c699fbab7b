"""Split-KV join attention — Pallas TPU kernel for PreTTR's query-time join.

The query-time join (layers ``l..n-1``) attends a joint sequence whose K/V
come from two *physically separate* sources: the freshly-encoded query
segment (tiny — ``max_query_len`` tokens) and the index-loaded document
segment.  The legacy path concatenates them into one ``[B, Lq+Ld, ...]``
buffer first; this kernel consumes the two K/V operands as-is, so the
doc-side K/V can flow straight from the index's layer-``l`` streams (or
from the per-segment residual) into the MXU without a concat copy.

Layout: the query-segment K/V is one whole block (its length is bounded by
``max_query_len``, far below a KV tile), folded into the online-softmax
state at the first doc tile; the doc segment is tiled normally.  Grid
``(B, Hq, nQ, nKd)`` with the doc-KV axis innermost — softmax state (m, l,
acc) lives in VMEM scratch across doc tiles (the standard sequential-grid
TPU flash pattern, as in ``kernels/split_attention``).  GQA rides the K/V
index maps (head ``h`` reads KV head ``h * Hkv // Hq``).

The join layers are mask-free apart from validity (no causal / window /
split structure — the split mask only exists *below* layer ``l``), so the
only skip predicate is the per-row valid doc length (scalar-prefetched).

Two orthogonal extensions serve the index-fed doc segment:

* **In-register int8 dequantization** (``dequant=True``): ``kd``/``vd``
  arrive as raw int8 codec payload plus per-token fp32 scales; each KV
  tile is widened *in registers* (``int8 -> f32 * scale``) right before
  its dot — the standalone decode dispatch disappears and the doc-side
  HBM read shrinks to the 1-byte payload.  Dequantizing the rows before
  the dot (rather than folding scales into scores/probabilities) keeps
  the kernel bit-exact against decode-then-attend.
* **Paged doc segment** (:func:`join_attention_pallas_paged`): the doc
  K/V live in fixed-size token-page pools ``[P, Hkv, page, D]`` (the
  device doc cache's layout) and a scalar-prefetched page table
  ``[B, nP]`` maps each (row, tile) to its pool page — the doc-segment
  index maps walk the page table, so a batch is scored straight out of
  the cache pools without materializing a per-batch dense copy.  Page
  validity rides a ``[P, 1, page]`` pool the same way.

TPU block shapes: the last two dims of every block are whole ``(rows, D)``
tiles — K/V tiles ``(block_k | page, D)``, validity as ``[B, 1, L]`` rows
with ``(1, block_k)`` blocks (``block_k`` a multiple of 128 or the whole
padded length), per-token scales as ``(block_k, 1)`` columns.  fp16 doc
K/V (raw fp16 index bytes) arrive bitcast to ``uint16`` and are widened in
registers.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tpu import f16_bits_to_f32

NEG_INF = -1e30


def _widen(x):
    """A doc K/V tile in f32: ``uint16`` carries fp16 bit patterns (the
    TPU's Pallas compiler has no fp16), anything else converts."""
    if x.dtype == jnp.uint16:
        return f16_bits_to_f32(x)
    return x.astype(jnp.float32)


def _join_kernel(dlen_ref, *refs, block_k: int, scale: float,
                 dequant: bool):
    q_ref, kq_ref, vq_ref, kd_ref, vd_ref = refs[:5]
    i = 5
    if dequant:
        kds_ref, vds_ref = refs[i:i + 2]
        i += 2
    qval_ref, dval_ref, o_ref, m_scr, l_scr, acc_scr = refs[i:]

    b = pl.program_id(0)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _query_segment():
        # the whole (padded) query-segment KV in one shot: it seeds the
        # online-softmax state instead of a NEG_INF init
        q = q_ref[0, 0].astype(jnp.float32)            # [bq, D]
        kq = kq_ref[0, 0].astype(jnp.float32)          # [Lqp, D]
        vq = vq_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, kq, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(qval_ref[0] > 0, s, NEG_INF)     # [1, Lqp] broadcast
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m)
        m_scr[...] = m
        l_scr[...] = jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = jax.lax.dot_general(
            p, vq, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    k0 = ik * block_k

    @pl.when(dlen_ref[b] > k0)                         # doc tile beyond length
    def _doc_tile():
        q = q_ref[0, 0].astype(jnp.float32)
        kd = _widen(kd_ref[0, 0])                      # [bk | page, D]
        vd = _widen(vd_ref[0, 0])
        if dequant:
            # widen the raw int8 rows in registers: per-token fp32 scales
            # arrive as a [bk, 1] column, broadcasting over D — identical
            # elementwise math to a standalone decode dispatch, so the
            # fused path is bit-exact against decode-then-attend
            kd = kd * kds_ref[0]
            vd = vd * vds_ref[0]
        s = jax.lax.dot_general(q, kd, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = (k_pos < dlen_ref[b]) & (dval_ref[0] > 0)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, vd, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _paged_shim(pt_ref, dlen_ref, *refs, block_k, scale, dequant):
    # paged variant scalar-prefetches (page_table, dlen); the page table is
    # only consumed by the BlockSpec index maps, never by the body
    del pt_ref
    _join_kernel(dlen_ref, *refs, block_k=block_k, scale=scale,
                 dequant=dequant)


def join_attention_pallas(q, kq, vq, kd, vd, dlen, kq_valid, kd_valid, *,
                          block_q: int, block_k: int, interpret: bool,
                          kd_scales=None, vd_scales=None):
    """q: [B, Hq, Sq, D]; kq, vq: [B, Hkv, Lq, D]; kd, vd: [B, Hkv, Ld, D];
    dlen: [B] i32 (doc-segment tile-skip bound, covering every valid doc
    index); kq_valid: [B, 1, Lq] i32; kd_valid: [B, 1, Ld] i32.  Sq/Ld
    must be multiples of block_q/block_k and Lq a sublane multiple (ops.py
    pads).

    ``kd_scales``/``vd_scales`` (optional, both or neither): per-token fp32
    dequant scales [B, Ld, 1] for raw-int8 ``kd``/``vd`` — the KV tiles are
    widened in registers inside the doc-segment loop."""
    b, hq, sq, d = q.shape
    hkv, lq = kq.shape[1], kq.shape[2]
    ld = kd.shape[2]
    assert sq % block_q == 0 and ld % block_k == 0
    dequant = kd_scales is not None
    n_rep = hq // hkv
    scale = 1.0 / math.sqrt(d)

    kern = functools.partial(_join_kernel, block_k=block_k, scale=scale,
                             dequant=dequant)
    grid = (b, hq, sq // block_q, ld // block_k)
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d),
                     lambda b, h, iq, ik, L: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, lq, d),
                     lambda b, h, iq, ik, L: (b, h // n_rep, 0, 0)),
        pl.BlockSpec((1, 1, lq, d),
                     lambda b, h, iq, ik, L: (b, h // n_rep, 0, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda b, h, iq, ik, L: (b, h // n_rep, ik, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda b, h, iq, ik, L: (b, h // n_rep, ik, 0)),
    ]
    operands = [q, kq, vq, kd, vd]
    if dequant:
        in_specs += [
            pl.BlockSpec((1, block_k, 1), lambda b, h, iq, ik, L: (b, ik, 0)),
            pl.BlockSpec((1, block_k, 1), lambda b, h, iq, ik, L: (b, ik, 0)),
        ]
        operands += [kd_scales, vd_scales]
    in_specs += [
        pl.BlockSpec((1, 1, lq), lambda b, h, iq, ik, L: (b, 0, 0)),
        pl.BlockSpec((1, 1, block_k), lambda b, h, iq, ik, L: (b, 0, ik)),
    ]
    operands += [kq_valid, kd_valid]
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, block_q, d),
                                   lambda b, h, iq, ik, L: (b, h, iq, 0)),
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        interpret=interpret,
    )(dlen, *operands)


def join_attention_pallas_paged(q, kq, vq, kd_pages, vd_pages, page_table,
                                dlen, kq_valid, dval_pages, *,
                                block_q: int, interpret: bool,
                                kd_scale_pages=None, vd_scale_pages=None):
    """Paged doc segment: the doc K/V stay in the device cache's page pools
    and the doc-segment index maps walk the scalar-prefetched page table.

    q: [B, Hq, Sq, D]; kq, vq: [B, Hkv, Lq, D];
    kd_pages, vd_pages: [P, Hkv, page, D] token-page pools;
    page_table: [B, nP] i32 pool page per (row, doc tile) — tail entries
    point at the cache's all-zero page and are masked by ``dlen``;
    dlen: [B] i32 valid length of the assembled doc row;
    kq_valid: [B, 1, Lq] i32; dval_pages: [P, 1, page] i32 page-resident
    validity pool;
    kd_scale_pages / vd_scale_pages: optional [P, page, 1] fp32 per-token
    dequant scale pools for raw-int8 KV pools.

    The doc tile size is the page size (a sublane multiple — the cache
    rounds it up); Sq must be a multiple of block_q (ops.py pads).
    Returns [B, Hq, Sq, D] with the doc segment of length nP * page."""
    b, hq, sq, d = q.shape
    hkv, lq = kq.shape[1], kq.shape[2]
    page = kd_pages.shape[2]
    n_pages = page_table.shape[1]
    assert sq % block_q == 0
    dequant = kd_scale_pages is not None
    n_rep = hq // hkv
    scale = 1.0 / math.sqrt(d)

    kern = functools.partial(_paged_shim, block_k=page, scale=scale,
                             dequant=dequant)
    grid = (b, hq, sq // block_q, n_pages)
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d),
                     lambda b, h, iq, ik, pt, L: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, lq, d),
                     lambda b, h, iq, ik, pt, L: (b, h // n_rep, 0, 0)),
        pl.BlockSpec((1, 1, lq, d),
                     lambda b, h, iq, ik, pt, L: (b, h // n_rep, 0, 0)),
        # the page-table walk: tile ik of row b reads pool page pt[b, ik]
        pl.BlockSpec((1, 1, page, d),
                     lambda b, h, iq, ik, pt, L: (pt[b, ik], h // n_rep, 0, 0)),
        pl.BlockSpec((1, 1, page, d),
                     lambda b, h, iq, ik, pt, L: (pt[b, ik], h // n_rep, 0, 0)),
    ]
    operands = [q, kq, vq, kd_pages, vd_pages]
    if dequant:
        in_specs += [
            pl.BlockSpec((1, page, 1),
                         lambda b, h, iq, ik, pt, L: (pt[b, ik], 0, 0)),
            pl.BlockSpec((1, page, 1),
                         lambda b, h, iq, ik, pt, L: (pt[b, ik], 0, 0)),
        ]
        operands += [kd_scale_pages, vd_scale_pages]
    in_specs += [
        pl.BlockSpec((1, 1, lq), lambda b, h, iq, ik, pt, L: (b, 0, 0)),
        pl.BlockSpec((1, 1, page),
                     lambda b, h, iq, ik, pt, L: (pt[b, ik], 0, 0)),
    ]
    operands += [kq_valid, dval_pages]
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, block_q, d),
                                   lambda b, h, iq, ik, pt, L: (b, h, iq, 0)),
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        interpret=interpret,
    )(page_table, dlen, *operands)
