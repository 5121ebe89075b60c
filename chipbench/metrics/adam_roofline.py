"""Share of the HBM roofline reached by the fused Adam kernel
(``kernels/fused_adam``): the bytes it moves in the traced slice
(``fused_adam.hbm_bytes``, 4 reads and 3 writes of the block-padded fp32
parameter vector, for every lane-step the cohort program runs, masked ones
included: ``train.adam_bytes``, ``repro.obs`` session tally), over the
peak HBM bandwidth, divided by the kernel's device time."""
from benchlib import traces

#: the Pallas kernel's custom call in the trace: ``fused_adam.<n>``
KERNEL = r"^fused_adam(\.\d+)?$"


def read(ctx):
    try:
        from repro import obs
    except ImportError:        # a program without the counters
        return None
    moved = obs.session().get("train.adam_bytes", 0)
    s = traces.op_s(ctx.trace, KERNEL, ctx.lo, ctx.hi)
    if not moved or s <= 0:
        return None
    return 100.0 * moved / ctx.peak["hbm_bytes_per_s"] / s
