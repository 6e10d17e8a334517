"""Seconds XLA and Mosaic spent compiling, or fetching compiled programs
from the persistent cache, during set-up (jax's own duration events)."""


def reduce(params, ctx):
    return float(ctx.compile_s)
