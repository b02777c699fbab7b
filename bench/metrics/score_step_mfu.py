"""Scoring step: the operations the window's candidates require in it
(``costs.score_step_flops``: join layers ``l..n-2`` over query and
document tokens and the CLS-only layer, real tokens only), over the device
time of the scoring programs in the trace, over the chip's bf16 peak.

The scoring programs are the jitted ``_raw_score`` (dense miss path) and
``_pool_score`` (paged doc cache) of ``serving/service.py``; their runs
are the ``XLA Modules`` events named ``jit__raw_score`` /
``jit__pool_score``."""
import costs
import trace_reduce

MODULES = r"jit__(raw|pool)_score"


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    t = trace_reduce.matched_s(ctx.trace, MODULES, line="modules")
    if t <= 0:
        return None
    ops = sum(costs.score_step_flops(ctx.config, r.q_len, ld)
              for r in ctx.requests if r.done_s is not None
              for ld in r.d_lens)
    return 100.0 * ops / t / ctx.peaks["bf16_flops_per_s"]
