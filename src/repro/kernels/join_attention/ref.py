"""Pure-jnp oracles for split-KV join attention, including the
separate-dispatch decode reference for the int8 path and the
densify-then-attend reference for the paged path."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def join_attention_ref(q, kq, vq, kd, vd, kq_valid=None, kd_valid=None):
    """q: [B, Hq, Sq, D]; kq, vq: [B, Hkv, Lq, D]; kd, vd: [B, Hkv, Ld, D];
    kq_valid / kd_valid: optional [B, Lq] / [B, Ld] booleans.
    Returns [B, Hq, Sq, D] — softmax over the union of both segments."""
    b, hq, sq, d = q.shape
    hkv, lq = kq.shape[1], kq.shape[2]
    ld = kd.shape[2]
    n_rep = hq // hkv
    k = jnp.repeat(jnp.concatenate([kq, kd], axis=2), n_rep, axis=1)
    v = jnp.repeat(jnp.concatenate([vq, vd], axis=2), n_rep, axis=1)
    if kq_valid is None:
        kq_valid = jnp.ones((b, lq), bool)
    if kd_valid is None:
        kd_valid = jnp.ones((b, ld), bool)
    valid = jnp.concatenate([kq_valid.astype(bool), kd_valid.astype(bool)],
                            axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(d)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)) \
        .astype(q.dtype)


def dequantize_kv(x_q, scales):
    """Separate-dispatch decode reference: widen raw-int8 K or V rows with
    per-token fp32 scales.  x_q: [B, Hkv, Ld, D] int8; scales: [B, Ld] f32.
    Same elementwise math as the in-kernel dequant."""
    return x_q.astype(jnp.float32) * scales.astype(jnp.float32)[:, None, :, None]


def join_attention_ref_quant(q, kq, vq, kd_q, vd_q, kd_scales, vd_scales,
                             kq_valid=None, kd_valid=None):
    """Decode-then-attend oracle for the int8 doc segment: dequantize the
    raw K/V with per-token scales (the separate-dispatch reference), then
    run the fp32 oracle."""
    return join_attention_ref(q, kq, vq,
                              dequantize_kv(kd_q, kd_scales),
                              dequantize_kv(vd_q, vd_scales),
                              kq_valid=kq_valid, kd_valid=kd_valid)


def pages_to_dense(pages, page_table):
    """Densify token-page pools via a page table.
    pages: [P, page, ...]; page_table: [B, nP] i32.
    Returns [B, nP * page, ...] in assembled row order."""
    g = pages[page_table]
    return g.reshape((g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:])


def kv_pages_to_dense(pages, page_table):
    """Densify K/V pools in the paged kernel's head-major layout.
    pages: [P, Hkv, page, D]; page_table: [B, nP] i32.
    Returns token-major [B, nP * page, Hkv, D]."""
    g = jnp.moveaxis(pages[page_table], 2, 3)    # [B, nP, page, Hkv, D]
    return g.reshape((g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:])


def join_attention_ref_paged(q, kq, vq, kd_pages, vd_pages, page_table,
                             dval_pages, kq_valid=None,
                             kd_scale_pages=None, vd_scale_pages=None):
    """Densify-then-attend oracle for the paged doc segment: gather pages
    into dense [B, Hkv, Ld, D] rows, optionally dequantize, then run the
    fp32 oracle.  Pool layouts match the paged kernel
    ([P, Hkv, page, D] KV, [P, page] validity, [P, page, 1] scales)."""
    kd = jnp.moveaxis(kv_pages_to_dense(kd_pages, page_table), 2, 1)
    vd = jnp.moveaxis(kv_pages_to_dense(vd_pages, page_table), 2, 1)
    kd_valid = pages_to_dense(dval_pages, page_table)
    if kd_scale_pages is not None:
        kd_scales = pages_to_dense(kd_scale_pages, page_table)[..., 0]
        vd_scales = pages_to_dense(vd_scale_pages, page_table)[..., 0]
        return join_attention_ref_quant(q, kq, vq, kd, vd, kd_scales,
                                        vd_scales, kq_valid=kq_valid,
                                        kd_valid=kd_valid)
    return join_attention_ref(q, kq, vq, kd, vd, kq_valid=kq_valid,
                              kd_valid=kd_valid)
