"""Flash attention with the PreTTR split mask — Pallas TPU kernel.

TPU adaptation of the paper's train-time masked attention (DESIGN.md §3).
With the PreTTR input layout ``[CLS];q;[SEP](pad to Q);d;[SEP](pad)`` the
split mask is *block structured*: the segment boundary is the static token
index ``seg_boundary``, so for 128-aligned boundaries entire (q-block,
kv-block) tiles are cross-segment and are skipped via ``pl.when`` — the MXU
never issues for them.  The same skip predicate serves causal and
sliding-window masks (LM archs reuse this kernel).

Grid: ``(B, Hq, nQ, nK)`` — the KV axis iterates innermost so the online
softmax state (m, l, acc) lives in VMEM scratch across KV tiles (the
standard sequential-grid TPU flash pattern).  GQA is handled in the K/V
index maps (head ``h`` reads KV head ``h * Hkv // Hq``) — no repeated KV is
materialized.  TPU block shapes: K/V tiles ``(block_k, D)``, validity as
``[B, 1, Skv]`` rows with ``(1, block_k)`` blocks (``block_k`` a multiple
of 128 or the whole padded length).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(lengths_ref, *refs, block_q: int, block_k: int,
                 causal: bool, window: int, seg_boundary: int, scale: float,
                 dequant: bool):
    q_ref, k_ref, v_ref = refs[:3]
    i = 3
    if dequant:
        ks_ref, vs_ref = refs[i:i + 2]
        i += 2
    valid_ref, o_ref, m_scr, l_scr, acc_scr = refs[i:]
    b = pl.program_id(0)
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q0 = iq * block_q
    k0 = ik * block_k

    # ---- block-level skip predicate (static mask structure) ----
    needed = lengths_ref[b] > k0                       # beyond valid length
    if causal:
        needed &= k0 <= q0 + block_q - 1               # strictly-future tile
    if window > 0:
        needed &= (q0 - (k0 + block_k - 1)) < window   # out-of-window tile
    if seg_boundary >= 0:
        q_lo_seg = q0 >= seg_boundary                  # whole tile same side?
        q_hi_seg = (q0 + block_q - 1) >= seg_boundary
        k_lo_seg = k0 >= seg_boundary
        k_hi_seg = (k0 + block_k - 1) >= seg_boundary
        q_uniform = q_lo_seg == q_hi_seg
        k_uniform = k_lo_seg == k_hi_seg
        cross = q_uniform & k_uniform & (q_lo_seg != k_lo_seg)
        needed &= ~cross

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)            # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        if dequant:
            # raw int8 K/V widened in registers: per-token fp32 scales as a
            # [bk, 1] column broadcasting over D — bit-exact against a
            # standalone decode dispatch followed by this kernel
            k = k * ks_ref[0]
            v = v * vs_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = (k_pos < lengths_ref[b]) & (valid_ref[0] > 0)
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= (q_pos - k_pos) < window
        if seg_boundary >= 0:
            mask &= (q_pos >= seg_boundary) == (k_pos >= seg_boundary)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, lengths, k_valid, *, causal: bool,
                           window: int, seg_boundary: int, block_q: int,
                           block_k: int, interpret: bool,
                           k_scales=None, v_scales=None):
    """q: [B, Hq, Sq, D]; k, v: [B, Hkv, Skv, D]; lengths: [B] i32;
    k_valid: [B, 1, Skv] i32 (0 = masked — supports non-prefix validity, e.g.
    PreTTR's padded-query + padded-doc two-prefix pattern; ``lengths`` stays
    the tile-skip bound and must cover every valid index).
    ``k_scales``/``v_scales`` (optional, both or neither): [B, Skv, 1] fp32
    per-token dequant scales for raw-int8 ``k``/``v``, widened in registers
    inside the tiled KV loop.
    Sq/Skv must be multiples of block_q/block_k (ops.py pads)."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    assert sq % block_q == 0 and skv % block_k == 0
    dequant = k_scales is not None
    n_rep = hq // hkv
    scale = 1.0 / math.sqrt(d)

    kern = functools.partial(
        _attn_kernel, block_q=block_q, block_k=block_k, causal=causal,
        window=window, seg_boundary=seg_boundary, scale=scale,
        dequant=dequant)

    grid = (b, hq, sq // block_q, skv // block_k)
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d),
                     lambda b, h, iq, ik, L: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda b, h, iq, ik, L: (b, h // n_rep, ik, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda b, h, iq, ik, L: (b, h // n_rep, ik, 0)),
    ]
    operands = [q, k, v]
    if dequant:
        in_specs += [
            pl.BlockSpec((1, block_k, 1), lambda b, h, iq, ik, L: (b, ik, 0)),
            pl.BlockSpec((1, block_k, 1), lambda b, h, iq, ik, L: (b, ik, 0)),
        ]
        operands += [k_scales, v_scales]
    in_specs += [
        pl.BlockSpec((1, 1, block_k), lambda b, h, iq, ik, L: (b, 0, ik)),
    ]
    operands += [k_valid]
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
    )(lengths, *operands)
