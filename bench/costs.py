"""What the algorithm requires, in operations and bytes, and what the chip
can do: the yardstick that roofline and utilization metrics divide by.

Counts are of the work the ranker needs for a candidate's *real* tokens
(query tokens with [CLS]/[SEP], document tokens with [SEP]) and of the
stored dtypes, never of what an implementation pads or copies.  A matmul
of ``[m, k] x [k, n]`` counts ``2 m k n`` operations.

Per candidate row with ``lq`` query and ``ld`` document tokens
(``t = lq + ld``), width ``d``, MLP width ``f``, split layer ``l`` of
``n`` layers, compressed width ``e``:

* decompress (only when a join layer reads the document reps):
  ``2 ld e d``;
* each full join layer ``l..n-2``: Q and O projections ``2 * 2 t d^2``,
  K/V projections ``2 * 2 r d^2`` where ``r = lq`` on the layer whose
  document K/V the index stores and ``t`` otherwise, MLP ``2 * 2 t d f``,
  attention scores and values ``2 * 2 t t d``;
* the CLS-only last layer: Q and O for one row ``2 * 2 d^2``, K/V
  projections ``2 * 2 r d^2`` (``r`` as above when ``l = n-1``), attention
  ``2 * 2 t d``, MLP for one row ``2 * 2 d f``, and the score ``2 d``.

The join-attention kernel's part of that is the attention term of each
layer; its bytes are one read of Q, K and V and one write of the output:
bfloat16 for computed tensors, the index's stored dtype (plus a float32
scale per token for int8) for stored document K/V.
"""
from __future__ import annotations

import json
from pathlib import Path

BF16 = 2
KV_BYTES = {"fp16": 2, "bf16": 2, "int8": 1}
SCALE_BYTES = {"int8": 4}


def peaks(kind: str, path: Path | None = None) -> dict:
    """The chip's peaks by ``device_kind``; a device missing from the
    table is an error, never a default."""
    path = path or Path(__file__).with_name("peaks.json")
    table = json.loads(path.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[kind]


def _shape(cfg: dict):
    return (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_hidden_layers"], cfg["split_layer"],
            cfg["compress_dim"], bool(cfg["index"]["store_layer_kv"]))


def score_step_flops(cfg: dict, lq: int, ld: int) -> int:
    """Operations the scoring step requires for one candidate row."""
    d, f, n, l, e, stored = _shape(cfg)
    t = lq + ld
    ops = 0
    if e and (l < n - 1 or not stored):
        ops += 2 * ld * e * d
    for li in range(l, n - 1):
        r = lq if (stored and li == l) else t
        ops += 4 * t * d * d + 4 * r * d * d + 4 * t * d * f + 4 * t * t * d
    r = lq if (stored and l == n - 1) else t
    ops += 4 * d * d + 4 * r * d * d + 4 * t * d + 4 * d * f + 2 * d
    return ops


def join_attention_work(cfg: dict, lq: int, ld: int) -> dict:
    """Operations and bytes of every join-attention kernel call of one
    candidate row's scoring step, split into the full join layers
    (``full``) and the CLS-only row (``cls``): ``{part: (ops, bytes)}``."""
    d, _, n, l, _, stored = _shape(cfg)
    codec = cfg["index"].get("kv_codec") or cfg["index"]["codec"]
    t = lq + ld

    def kv_bytes(li):
        if stored and li == l:
            return 2 * ld * d * KV_BYTES[codec] + 2 * ld * SCALE_BYTES.get(
                codec, 0)
        return 2 * ld * d * BF16

    full_ops = full_bytes = 0
    for li in range(l, n - 1):
        full_ops += 4 * t * t * d
        full_bytes += (t * d * BF16 + 2 * lq * d * BF16 + kv_bytes(li)
                       + t * d * BF16)
    cls_ops = 4 * t * d
    cls_bytes = d * BF16 + 2 * lq * d * BF16 + kv_bytes(n - 1) + d * BF16
    return {"full": (full_ops, full_bytes), "cls": (cls_ops, cls_bytes)}


def least_time(parts, peak_flops: float, peak_bw: float):
    """Least time for a set of ``(ops, bytes)`` totals, one per kind of
    call, each bound by the larger of its compute and memory time ->
    ``(seconds, bounding term)``; the term is the one that bounds the
    larger share of the time."""
    total, by = 0.0, {"compute": 0.0, "memory": 0.0}
    for ops, nbytes in parts:
        tc, tm = ops / peak_flops, nbytes / peak_bw
        total += max(tc, tm)
        by["compute" if tc >= tm else "memory"] += max(tc, tm)
    return total, max(by, key=by.get)
