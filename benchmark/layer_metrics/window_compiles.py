"""How many `jax.compile` spans begin inside the window: programs built
or loaded under traffic. 0 is expected in every cell; the reference's own
compiles come after the window's last program span and are not counted.
`None` where the program does not listen to jax."""

from benchmark import setup_spans


def read(ctx):
    return setup_spans.read(ctx, "window_compiles")
