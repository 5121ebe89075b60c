"""Host milliseconds per aggregation in the traced slice in which the host
was not blocked on the device: the window less the union of the program's
``fl.sync.<site>`` spans (``repro.obs.sync``), over the aggregations. It is
the most that overlapping the host's work with the device's can save."""
from benchlib import traces

PREFIX = "fl.sync."


def read(ctx):
    syncs = [e for e in traces.clip(ctx.trace.host, ctx.lo, ctx.hi)
             if e.name.startswith(PREFIX)]
    if not syncs or not ctx.aggregations:
        return None
    free_ns = (ctx.hi - ctx.lo) - traces.union_ns(syncs)
    return free_ns / 1e6 / ctx.aggregations
