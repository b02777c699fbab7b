"""Kernels (``kernels/join_attention``, dense and paged): the least time
the window's join-attention work needs on this chip (the larger of
required operations over the bf16 peak and required bytes over HBM
bandwidth, per kind of call: ``costs.join_attention_work``), over the
kernels' summed device time in the trace.  The bounding term is printed
on standard error.

The kernels are the Mosaic custom calls that the jitted wrappers
``join_flash_attention`` (dense) and ``join_flash_attention_paged`` of
``kernels/join_attention/ops.py`` lower to; on the chip's op line they are
named ``join_flash_attention.<n>`` / ``join_flash_attention_paged.<n>``."""
import sys

import costs
import trace_reduce

KERNELS = r"^join_flash_attention(_paged)?(\.\d+)?$"


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    t = trace_reduce.matched_s(ctx.trace, KERNELS)
    if t <= 0:
        return None
    full_ops = full_b = cls_ops = cls_b = 0
    for r in ctx.requests:
        if r.done_s is None:
            continue
        for ld in r.d_lens:
            w = costs.join_attention_work(ctx.config, r.q_len, ld)
            full_ops += w["full"][0]
            full_b += w["full"][1]
            cls_ops += w["cls"][0]
            cls_b += w["cls"][1]
    least, bound = costs.least_time(
        [(full_ops, full_b), (cls_ops, cls_b)],
        ctx.peaks["bf16_flops_per_s"], ctx.peaks["hbm_bytes_per_s"])
    print(f"[bench] join_attention: least time {least!r} s ({bound}-bound) "
          f"in {t!r} s of kernel time", file=sys.stderr)
    return 100.0 * least / t
