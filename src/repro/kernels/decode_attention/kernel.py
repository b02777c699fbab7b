"""GQA flash-decode kernel — one query position against a long KV cache.

Doubles as the paper's **CLS-only final layer** (§6.3): scoring reads only
the [CLS] attention row, which is exactly a decode-shaped attention.  The
GQA group (``R = Hq/Hkv`` query heads sharing a KV head) forms the MXU row
dimension, so a single tile computes all of a KV-head's query rows: q is
laid out ``[B, Hkv, R, D]``.

Grid ``(B, Hkv, nK)`` with the KV axis innermost; online-softmax state in
VMEM scratch across KV tiles.  Sliding-window archs (Gemma3 local layers)
mask ``k_pos <= qpos - window``.  TPU block shapes: validity rides
``[B, 1, S]`` rows in ``(1, block_k)`` blocks (``block_k`` a multiple of
128 or the whole padded length).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(lengths_ref, q_ref, k_ref, v_ref, valid_ref, o_ref,
                   m_scr, l_scr, acc_scr, *,
                   block_k: int, window: int, scale: float):
    b = pl.program_id(0)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k0 = ik * block_k
    length = lengths_ref[b]
    q_pos = length - 1

    needed = k0 < length
    if window > 0:
        needed &= (k0 + block_k - 1) > (q_pos - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [R, D]
        k = k_ref[0, 0].astype(jnp.float32)            # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = (k_pos < length) & (valid_ref[0] > 0)
        if window > 0:
            mask &= (q_pos - k_pos) < window
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
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)) \
            .astype(o_ref.dtype)


def flash_decode_pallas(q, k, v, lengths, k_valid, *, window: int,
                        block_k: int, interpret: bool):
    """q: [B, Hkv, R, D]; k, v: [B, Hkv, S, D]; lengths: [B]; k_valid:
    [B, 1, S] i32 (0 = masked — non-prefix validity for the CLS-only layer;
    ``lengths`` stays the tile-skip bound covering every valid index)."""
    b, hkv, r, d = q.shape
    s = k.shape[2]
    assert s % block_k == 0
    scale = 1.0 / math.sqrt(d)
    kern = functools.partial(_decode_kernel, block_k=block_k, window=window,
                             scale=scale)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, s // block_k),
            in_specs=[
                pl.BlockSpec((1, 1, r, d), lambda b, h, ik, L: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda b, h, ik, L: (b, h, ik, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda b, h, ik, L: (b, h, ik, 0)),
                pl.BlockSpec((1, 1, block_k),
                             lambda b, h, ik, L: (b, 0, ik)),
            ],
            out_specs=pl.BlockSpec((1, 1, r, d),
                                   lambda b, h, ik, L: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((r, 1), jnp.float32),
                pltpu.VMEM((r, 1), jnp.float32),
                pltpu.VMEM((r, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, r, d), q.dtype),
        interpret=interpret,
    )(lengths, q, k, v, k_valid)
