"""The part of `setup_compile_s` that the cache did not serve: the union
of the `jax.compile` intervals before the window whose `cache_hit` is 0.
Near nothing on a warm start (programs under jax's one-second floor for
caching), the bulk of a cold one, and all of a program jax will not cache.
`None` where the program does not listen to jax."""

from benchmark import setup_spans


def read(ctx):
    return setup_spans.read(ctx, "setup_compile_miss_s")
