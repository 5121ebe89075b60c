"""Protocol events the scheduler dispatched per aggregation in the traced
slice (``Scheduler.n_events``): the host pump's work per round."""


def read(ctx):
    if not ctx.aggregations:
        return None
    return ctx.events / ctx.aggregations
