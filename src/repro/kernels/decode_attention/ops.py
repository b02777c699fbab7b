"""Public wrapper for flash decode: standard [B, Hq, 1, D] layout in/out."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import flash_decode_pallas
from repro.kernels.masking import last_valid_lengths
from repro.kernels.tpu import interpret_mode


@functools.partial(jax.jit, static_argnames=("window", "block_k", "interpret"))
def flash_decode_attention(q, k, v, lengths=None, k_valid=None, *,
                           window: int = -1,
                           block_k: int = 256, interpret: bool | None = None):
    """q: [B, Hq, 1, D]; k, v: [B, Hkv, S, D]; lengths: [B] (query position =
    lengths-1); k_valid: optional [B, S] boolean mask for non-prefix
    validity (PreTTR's CLS-only final layer) — when given, ``lengths``
    defaults to one past the last valid index per row.
    Returns [B, Hq, 1, D]."""
    if interpret is None:
        interpret = interpret_mode()
    b, hq, _, d = q.shape
    hkv, s = k.shape[1], k.shape[2]
    n_rep = hq // hkv
    if lengths is None:
        lengths = (jnp.full((b,), s, jnp.int32) if k_valid is None
                   else last_valid_lengths(k_valid, s))
    if k_valid is None:
        k_valid = jnp.ones((b, s), jnp.int32)
    bk = min(block_k, s)
    pad = (-s) % bk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    k_valid = jnp.pad(k_valid.astype(jnp.int32), ((0, 0), (0, pad)))
    qg = q[:, :, 0].reshape(b, hkv, n_rep, d)
    out = flash_decode_pallas(qg, k, v, lengths.astype(jnp.int32),
                              k_valid[:, None, :],
                              window=window, block_k=bk, interpret=interpret)
    return out.reshape(b, hq, 1, d)
