"""Public wrappers: flatten leading dims, pad token tiles, pick interpret,
carry fp16 across the kernel boundary as ``uint16`` bits."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.fused_compress.kernel import compress_pallas, decompress_pallas
from repro.kernels.tpu import interpret_mode


def _to_kernel(x):
    return (jax.lax.bitcast_convert_type(x, jnp.uint16)
            if x.dtype == jnp.float16 else x)


def _kernel_dtype(dtype):
    return jnp.uint16 if jnp.dtype(dtype) == jnp.float16 else dtype


def _from_kernel(y, dtype):
    return (jax.lax.bitcast_convert_type(y, jnp.float16)
            if jnp.dtype(dtype) == jnp.float16 else y)


@functools.partial(jax.jit, static_argnames=("out_dtype", "block_t", "interpret"))
def fused_compress(x, w, b, *, out_dtype=jnp.float16, block_t: int = 256,
                   interpret: bool | None = None):
    """x: [..., d] -> [..., e] (GELU bottleneck, fp16 store)."""
    if interpret is None:
        interpret = interpret_mode()
    lead = x.shape[:-1]
    d = x.shape[-1]
    t = 1
    for s in lead:
        t *= s
    xf = x.reshape(t, d)
    bt = min(block_t, max(8, t))
    pad = (-t) % bt
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    out = compress_pallas(_to_kernel(xf), w, b,
                          out_dtype=_kernel_dtype(out_dtype), block_t=bt,
                          interpret=interpret)
    return _from_kernel(out[:t], out_dtype).reshape(*lead, w.shape[1])


@functools.partial(jax.jit, static_argnames=("out_dtype", "block_t", "interpret"))
def fused_decompress(r, w, b, gamma, beta, *, out_dtype=jnp.bfloat16,
                     block_t: int = 256, interpret: bool | None = None):
    """r: [..., e] fp16 -> [..., d] (upcast + expand + LayerNorm, one pass)."""
    if interpret is None:
        interpret = interpret_mode()
    lead = r.shape[:-1]
    e = r.shape[-1]
    t = 1
    for s in lead:
        t *= s
    rf = r.reshape(t, e)
    bt = min(block_t, max(8, t))
    pad = (-t) % bt
    if pad:
        rf = jnp.pad(rf, ((0, pad), (0, 0)))
    out = decompress_pallas(_to_kernel(rf), w, b, gamma, beta,
                            out_dtype=_kernel_dtype(out_dtype), block_t=bt,
                            interpret=interpret)
    return _from_kernel(out[:t], out_dtype).reshape(*lead, w.shape[1])
