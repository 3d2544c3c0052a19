"""Seconds from the OS starting the process that owns the chips to its
`ray_tpu.init()`: the start of `process.boot` to the start of
`runtime.init`. The interpreter, the imports, jax claiming the chip, and
whatever the entry point does before it starts the runtime. `None` where
the program records neither span (an older commit, or no `/proc`)."""

from benchmark import setup_spans


def read(ctx):
    return setup_spans.read(ctx, "setup_before_init_s")
