"""What the kernel wrappers need to know about the chip they compile for.

* :func:`interpret_mode` — the one place that decides between compiled
  Mosaic kernels (a TPU is the default device) and the Pallas interpreter
  (anything else: the CPU tests).
* :func:`sublane` — the second-minor block multiple of one native tile.
* :func:`f16_bits_to_f32` / :func:`f32_to_f16_bits` — the TPU's Pallas
  compiler cannot load, store or convert ``float16`` (the paper's storage
  dtype), so fp16 streams cross the kernel boundary bitcast to ``uint16``
  and are widened / narrowed in registers with integer ops.  Both are
  exact: the narrowing rounds to nearest-even like XLA's ``convert``.
* :func:`as_f16_bits` / :func:`from_f16_bits` — that bitcast and its
  inverse, for arrays that are kept as bits (the doc cache's pools: on
  the TPU a bitcast of a whole pool is a copy).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def interpret_mode() -> bool:
    """True unless the default device is a TPU: off the chip the kernels
    run in Pallas interpret mode (slow, numerically the same program)."""
    return jax.default_backend() != "tpu"


def sublane(dtype) -> int:
    """Rows of one native ``(sublane, 128)`` tile of ``dtype``: 8 for
    32-bit, 16 for 16-bit, 32 for 8-bit types.  Block second-minor dims
    that are not the whole array must be multiples of it."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def as_f16_bits(x):
    """fp16 as its ``uint16`` bit patterns; other dtypes as they are."""
    return (jax.lax.bitcast_convert_type(x, jnp.uint16)
            if x.dtype == jnp.float16 else x)


def from_f16_bits(x):
    """``uint16`` bit patterns back to fp16; other dtypes as they are."""
    return (jax.lax.bitcast_convert_type(x, jnp.float16)
            if x.dtype == jnp.uint16 else x)


def f16_bits_to_f32(h):
    """Widen fp16 bit patterns (any integer dtype, low 16 bits) to f32."""
    h = h.astype(jnp.int32) & 0xFFFF
    sign = h >> 15
    exp = (h >> 10) & 0x1F
    mant = h & 0x3FF
    # normal numbers and inf/nan: re-bias the exponent, shift the mantissa
    exp32 = jnp.where(exp == 0x1F, 0xFF, exp + (127 - 15))
    bits = (sign << 31) | (exp32 << 23) | (mant << 13)
    normal = jax.lax.bitcast_convert_type(bits, jnp.float32)
    # zero and subnormals: mant * 2^-24, exact in f32
    small = mant.astype(jnp.float32) * jnp.float32(2.0 ** -24)
    small = jnp.where(sign == 1, -small, small)
    return jnp.where(exp == 0, small, normal)


def f32_to_f16_bits(x):
    """Narrow f32 to fp16 bit patterns (int32 holding the low 16 bits),
    rounding to nearest-even; overflow gives inf, nan stays nan."""
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    sign = (u >> 16) & 0x8000
    a = u & 0x7FFFFFFF
    # |x| >= 65536: inf, or a quiet nan
    big = jnp.where(a > 0x7F800000, 0x7E00, 0x7C00)
    # result subnormal or zero (|x| < 2^-14): adding 0.5 aligns the ten
    # mantissa bits at the bottom and the FPU's own rounding is the RNE
    # (0.5 is the float whose bits are ``magic``)
    magic = ((127 - 15) + (23 - 10) + 1) << 23
    af = jax.lax.bitcast_convert_type(a, jnp.float32)
    sub = jax.lax.bitcast_convert_type(af + jnp.float32(0.5),
                                       jnp.int32) - magic
    # normal: re-bias, round half to even on the 13 dropped bits
    odd = (a >> 13) & 1
    norm = (a + (((15 - 127) << 23) + 0xFFF) + odd) >> 13
    out = jnp.where(a >= (127 + 16) << 23, big,
                    jnp.where(a < (113 << 23), sub, norm))
    return out | sign
