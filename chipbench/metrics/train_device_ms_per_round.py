"""Device milliseconds of the cohort-training program (``jit_cohort_flat``,
``repro.core.client``) per aggregation in the traced slice."""
from benchlib import traces


def read(ctx):
    s = traces.module_s(ctx.trace, "jit_cohort_flat", ctx.lo, ctx.hi)
    if s <= 0 or not ctx.aggregations:
        return None
    return 1e3 * s / ctx.aggregations
