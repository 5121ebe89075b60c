"""Share of the HBM roofline reached by the aggregation kernel
(``kernels/staleness_agg``): the bytes an aggregation needs, every
aggregated row of W float32 parameters read once and the W-wide result
written once, over the peak HBM bandwidth, divided by the kernel's device
time in the traced slice. The count is of the work needed, whatever
reads it, so a path that stops reading unused rows raises it."""
from benchlib import traces

#: the Pallas kernel's custom call in the trace: ``staleness_agg.<n>``
KERNEL = r"^staleness_agg(\.\d+)?$"


def read(ctx):
    s = traces.op_s(ctx.trace, KERNEL, ctx.lo, ctx.hi)
    if s <= 0:
        return None
    need = 4 * ctx.n_params * (ctx.rows_aggregated + ctx.aggregations)
    return 100.0 * need / ctx.peak["hbm_bytes_per_s"] / s
