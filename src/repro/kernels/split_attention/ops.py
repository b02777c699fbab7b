"""Public wrapper: pad-to-block, pick interpret mode off-TPU, jit."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.masking import last_valid_lengths
from repro.kernels.split_attention.kernel import flash_attention_pallas
from repro.kernels.tpu import interpret_mode, sublane


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "seg_boundary", "block_q", "block_k", "interpret"))
def split_flash_attention(q, k, v, lengths=None, k_valid=None,
                          k_scales=None, v_scales=None, *,
                          causal: bool = False,
                          window: int = -1, seg_boundary: int = -1,
                          block_q: int = 128, block_k: int = 128,
                          interpret: bool | None = None):
    """Flash attention with PreTTR split / causal / sliding-window masks.

    q: [B, Hq, Sq, D]; k, v: [B, Hkv, Skv, D]; lengths: [B] valid KV length
    (defaults to Skv); k_valid: optional [B, Skv] boolean mask for
    non-prefix validity (the model's padded-segment layouts) — when given,
    ``lengths`` defaults to one past the last valid index per row.
    ``k_scales``/``v_scales`` (optional, both or neither): [B, Skv] fp32
    per-token dequant scales for raw-int8 ``k``/``v`` — dequantization
    happens in registers inside the kernel's KV-tile loop, bit-exact vs
    decode-then-attend.  Pads sequence dims to block multiples; the pad
    tail is masked and sliced off the output.
    """
    if interpret is None:
        interpret = interpret_mode()
    b, hq, sq, d = q.shape
    skv = k.shape[2]
    if lengths is None:
        lengths = (jnp.full((b,), skv, jnp.int32) if k_valid is None
                   else last_valid_lengths(k_valid, skv))
    if k_valid is None:
        k_valid = jnp.ones((b, skv), jnp.int32)
    bq = min(block_q, max(sublane(q.dtype), sq))
    bk = min(block_k, max(8, skv))
    pad_q = (-sq) % bq
    pad_k = (-skv) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    k_valid = jnp.pad(k_valid.astype(jnp.int32), ((0, 0), (0, pad_k)))
    if k_scales is not None:
        k_scales = k_scales.astype(jnp.float32)
        v_scales = v_scales.astype(jnp.float32)
        if pad_k:
            k_scales = jnp.pad(k_scales, ((0, 0), (0, pad_k)))
            v_scales = jnp.pad(v_scales, ((0, 0), (0, pad_k)))
        k_scales = k_scales[..., None]      # [B, Skv, 1] — row-broadcast
        v_scales = v_scales[..., None]
    out = flash_attention_pallas(q, k, v, lengths.astype(jnp.int32),
                                 k_valid[:, None, :],
                                 causal=causal, window=window,
                                 seg_boundary=seg_boundary,
                                 block_q=bq, block_k=bk, interpret=interpret,
                                 k_scales=k_scales, v_scales=v_scales)
    return out[:, :, :sq]
