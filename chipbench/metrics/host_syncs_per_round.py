"""Blocking device-to-host reads per aggregation in the traced slice: the
program's ``fl.sync.<site>`` host spans (``repro.obs.sync``) in the window,
over the aggregations."""
from benchlib import traces

PREFIX = "fl.sync."


def read(ctx):
    syncs = [e for e in traces.clip(ctx.trace.host, ctx.lo, ctx.hi)
             if e.name.startswith(PREFIX)]
    if not syncs or not ctx.aggregations:
        return None
    return len(syncs) / ctx.aggregations
