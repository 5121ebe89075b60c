"""Seconds JAX spent tracing, lowering and compiling during set-up,
persistent-cache reads included (JAX monitoring events)."""


def read(ctx):
    return ctx.compile_setup["seconds"]
