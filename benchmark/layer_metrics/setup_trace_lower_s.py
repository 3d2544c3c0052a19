"""Seconds in which jax traced a function or lowered it to a module
before the window: the union of the `jax.trace` and `jax.lower` intervals
(an inner `jax.jit`'s trace lies inside the outer one's and counts once) of
the process that owns the chips. What a block traced once a layer, or a
rung a layer, lengthens. `None` where the program does not listen to jax."""

from benchmark import setup_spans


def read(ctx):
    return setup_spans.read(ctx, "setup_trace_lower_s")
