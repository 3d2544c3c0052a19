"""Where the chip-owning process keeps JAX's persistent compile cache.

A cold TPU process compiles for tens of seconds (the train cell's
``setup_s`` reads 23 s warm and 65 s cold: ledger, PR 24); the cache
directory is part of the cache key's lookup, so it must not move
between runs. Called where
a process first touches JAX for the device: ``ray_tpu.init()``'s TPU
detection, ``chip_smoke.py`` and the benchmark's ``require_chips``.

Because every chip-owning process passes here before it compiles, this
is also where the span recorder starts to listen to jax: each program
jax traces, lowers and compiles (or loads from the cache) from then on
leaves a ``jax.trace``, a ``jax.lower`` and a ``jax.compile`` span in
``ray_tpu.util.tracing``'s ring (docs/tracing.md).
"""

from __future__ import annotations

import os
import threading
import time

from ray_tpu.util import tracing

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# jax's event -> the span it becomes (jax/_src/dispatch.py)
_SPAN_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_listening = False
_listen_lock = threading.Lock()
# whether the compile event open on this thread was served by the cache
_thread = threading.local()


def _on_event(event: str, **_kwargs) -> None:
    if event == _CACHE_HIT:
        _thread.cache_hit = 1


def _on_duration(event: str, duration: float, **kwargs) -> None:
    """A closed trace, lower or compile event of jax, as a span that
    ends now. jax names the traced function ``f`` and its module
    ``jit(f)``: the wrapper goes, so that the three spans of one
    program's build share one id."""
    name = _SPAN_OF.get(event)
    if name is None:
        return
    now = time.perf_counter_ns()
    fun = str(kwargs.get("fun_name", ""))
    if fun.startswith("jit(") and fun.endswith(")"):
        fun = fun[4:-1]
    counts = {}
    if name == "jax.compile":
        counts["cache_hit"] = getattr(_thread, "cache_hit", 0)
        _thread.cache_hit = 0
    tracing.record(name, now - int(duration * 1e9), now, fun, **counts)


def _listen() -> None:
    """Once a process (a forked child inherits jax's listeners with
    the flag)."""
    global _listening
    with _listen_lock:
        if _listening:
            return
        from jax import monitoring
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True


def configure_compile_cache() -> str:
    """Returns the cache directory in effect. With
    ``JAX_COMPILATION_CACHE_DIR`` set, jax has already read it and
    nothing is set in code; otherwise the cache lives at the fixed
    path ``<checkout>/.jax_cache`` (git-ignored). Either way jax's
    trace, lower and compile events become spans from here on."""
    _listen()
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
