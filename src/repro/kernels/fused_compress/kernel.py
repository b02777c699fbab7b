"""PreTTR compressor kernels (paper §4.2) — fused single-pass tiles.

* ``compress``: GELU(x @ W_comp + b) with the fp16 downcast fused — token
  tiles stream HBM->VMEM once, W_comp (d x e <= 768x384) stays VMEM-resident
  across the grid.
* ``decompress``: the serving hot path (Table 5's "Decompress" column):
  fp16 stored reps are upcast, expanded (e -> d), bias-added and
  LayerNorm'd in one VMEM round trip — three ops the reference executes as
  separate HBM passes.

Grid: 1-D over token tiles (rows 128-aligned for the MXU).

The TPU's Pallas compiler has no ``float16``: fp16 reps cross the kernel
boundary as ``uint16`` bit patterns (ops.py bitcasts), narrowed and widened
in registers by ``repro.kernels.tpu`` — exact, so the store stays the
paper's fp16 bytes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tpu import f16_bits_to_f32, f32_to_f16_bits


def _load_f32(ref):
    x = ref[...]
    if x.dtype == jnp.uint16:                          # fp16 bit patterns
        return f16_bits_to_f32(x)
    return x.astype(jnp.float32)


def _store(ref, x):
    if ref.dtype == jnp.uint16:                        # fp16 bit patterns
        ref[...] = f32_to_f16_bits(x).astype(jnp.uint16)
    else:
        ref[...] = x.astype(ref.dtype)


def _compress_kernel(x_ref, w_ref, b_ref, o_ref):
    x = _load_f32(x_ref)
    h = jax.lax.dot_general(x, w_ref[...].astype(jnp.float32),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    _store(o_ref, jax.nn.gelu(h + b_ref[...].astype(jnp.float32)))


def _decompress_kernel(r_ref, w_ref, b_ref, g_ref, beta_ref, o_ref, *,
                       eps: float):
    r = _load_f32(r_ref)                               # fp16 -> f32 upcast
    h = jax.lax.dot_general(r, w_ref[...].astype(jnp.float32),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    h = h + b_ref[...].astype(jnp.float32)
    mu = jnp.mean(h, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(h - mu), axis=1, keepdims=True)
    h = (h - mu) * jax.lax.rsqrt(var + eps)
    _store(o_ref, h * g_ref[...].astype(jnp.float32)
           + beta_ref[...].astype(jnp.float32))


def compress_pallas(x, w, b, *, out_dtype, block_t: int, interpret: bool):
    """x: [T, d] -> [T, e] in out_dtype (``uint16`` = fp16 bits)."""
    t, d = x.shape
    e = w.shape[1]
    assert t % block_t == 0
    return pl.pallas_call(
        _compress_kernel,
        grid=(t // block_t,),
        in_specs=[
            pl.BlockSpec((block_t, d), lambda i: (i, 0)),
            pl.BlockSpec((d, e), lambda i: (0, 0)),
            pl.BlockSpec((e,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_t, e), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, e), out_dtype),
        interpret=interpret,
    )(x, w, b)


def decompress_pallas(r, w, b, gamma, beta, *, out_dtype, block_t: int,
                      interpret: bool, eps: float = 1e-6):
    """r: [T, e] (``uint16`` = fp16 bits) -> [T, d] LayerNorm'd, in
    out_dtype (``uint16`` = fp16 bits)."""
    t, e = r.shape
    d = w.shape[1]
    assert t % block_t == 0
    kern = functools.partial(_decompress_kernel, eps=eps)
    return pl.pallas_call(
        kern,
        grid=(t // block_t,),
        in_specs=[
            pl.BlockSpec((block_t, e), lambda i: (i, 0)),
            pl.BlockSpec((e, d), lambda i: (0, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_t, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, d), out_dtype),
        interpret=interpret,
    )(r, w, b, gamma, beta)
