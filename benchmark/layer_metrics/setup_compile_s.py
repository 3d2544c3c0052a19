"""Seconds in which jax compiled a program or loaded it from the
persistent cache before the window: the union of the `jax.compile`
intervals of the process that owns the chips (Mosaic compiling a kernel
again on every load is in here). `None` where the program does not listen
to jax."""

from benchmark import setup_spans


def read(ctx):
    return setup_spans.read(ctx, "setup_compile_s")
