"""Model FLOP/s utilisation of the whole FL round: the forward and
backward FLOPs one sample requires (``reference/<arch>.py``), times the
samples the slice's invocations trained (each client's real steps times
the batch; padded lanes and masked steps do not count), over the traced
window's seconds times the chips times the bf16 peak."""


def read(ctx):
    if ctx.window_s <= 0 or not ctx.samples:
        return None
    flops = ctx.flops_per_sample * ctx.samples
    return 100.0 * flops / (ctx.window_s * ctx.chips
                            * ctx.peak["bf16_flops_per_s"])
