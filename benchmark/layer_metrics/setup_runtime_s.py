"""Seconds the runtime took to come up before the window: `runtime.init`
and, in a serve cell, `serve.start` (the controller and the ingress), less
the `jax.trace`, `jax.lower` and `jax.compile` intervals of the process
inside them, whatever their thread (on a TPU host the scheduler's
round-trip probe builds the process's first program meanwhile), which
have metrics of their own: the parts of `setup_s` share no second.
`None` without `runtime.init`."""

from benchmark import setup_spans


def read(ctx):
    return setup_spans.read(ctx, "setup_runtime_s")
