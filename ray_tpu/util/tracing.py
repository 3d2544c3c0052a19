"""Tracing: program spans, the task-event timeline, jax.profiler capture.

Reference: ``python/ray/util/tracing/tracing_helper.py`` (opt-in spans
around submit/execute) and ``ray timeline`` [UNVERIFIED — mount empty,
SURVEY.md §0]. TPU-native twist (SURVEY §5 row 1): the deep trace is
the XLA/device trace — ``start_trace``/``stop_trace`` wrap
``jax.profiler`` in the process that owns the chips, and every task and
every program span runs inside a ``TraceAnnotation``, so device ops in
the profile lie under the task and the span that launched them.

Three layers, cheap to expensive:

- **Program spans** (always on, ``event_log_enabled``): the flight
  recorder. ``span(name)`` / ``record(name, start_ns, end_ns)`` at the
  layer boundaries of the serve request path and the train session
  (docs/tracing.md has the table) append to one bounded ring a process,
  and so do the process's start (``process.boot``), the runtime's
  (``runtime.init``, ``serve.start``) and every program jax traces,
  lowers and compiles (``jax.trace``, ``jax.lower``, ``jax.compile``:
  ``_private/compile_cache.py`` listens).
  The clock is ``time.perf_counter_ns()`` — CLOCK_MONOTONIC on Linux,
  so one clock for every process of a host and spans of the proxy,
  the driver and a replica need no offsets. All spans of one serve
  request carry one id, the hex id of the actor task that carries it.
  ``collect()`` gathers the rings of the live process workers
  (``serve.shutdown()`` and ``ray_tpu.shutdown()`` call it), and
  ``spans()`` reads what this process recorded and gathered, also
  after shutdown.
- **Task timeline** (always on): per-task RUNNING→FINISHED spans with
  worker-measured ``exec_ms`` (result serialization syncs pending
  device work, so array-returning TPU tasks' exec_ms includes device
  compute). ``timeline()`` exports tasks and spans as one Chrome-trace
  JSON.
- **Device profile** (opt-in, heavyweight): ``start_trace(logdir)`` →
  run the workload → ``stop_trace()``; open the logdir with
  TensorBoard/XProf or the generated ``.trace.json.gz`` in Perfetto.
  Task names and ``ray_tpu:<span>`` annotations appear above the XLA
  ops, on the device trace's own clock.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Dict, List, NamedTuple, Optional, Tuple

from ray_tpu._private.config import get_config

__all__ = ["Span", "NO_SPAN", "span", "record", "annotate", "request_of",
           "current_request", "spans", "collect", "clear", "start_trace",
           "stop_trace", "timeline", "task_events"]

RING_SPANS = 65536
ANNOTATION_PREFIX = "ray_tpu:"


class Span(NamedTuple):
    """One closed span. ``parent`` is the name of the span that was
    open on the same thread (or asyncio task) when this one began;
    ``counts`` are small integers measured at the boundary, or None."""

    name: str
    start_ns: int
    end_ns: int
    request: Optional[str]
    parent: Optional[str]
    pid: int
    thread: int
    counts: Optional[Dict[str, int]]


# this process's ring, and what collect() gathered from other processes;
# both hold plain tuples in Span's order (a NamedTuple costs a span() a
# third more), and spans() makes Spans of them
_ring: deque = deque(maxlen=RING_SPANS)
_gathered: deque = deque(maxlen=4 * RING_SPANS)
# pid -> (time.time_ns(), time.perf_counter_ns()) taken together, for
# the wall-clock export; the pair of a process on another host differs
_anchors: Dict[int, Tuple[int, int]] = {}
_pid = os.getpid()
# the innermost open span's name: a contextvar is per thread and, under
# asyncio, per task
_open: contextvars.ContextVar = contextvars.ContextVar(
    "rtpu_open_span", default=None)
# this process's ``process.boot`` as (start_ns, end_ns), until the ring's
# first reader puts it there
_boot: Optional[Tuple[int, int]] = None


def _process_start_ns() -> Optional[int]:
    """When the OS started this process, on ``perf_counter_ns()``'s
    clock, or None where ``/proc`` does not say. Field 22 of
    ``/proc/self/stat`` counts clock ticks since boot (10 ms fine);
    CLOCK_MONOTONIC starts at boot too but stands still while the host
    sleeps, which CLOCK_BOOTTIME less CLOCK_MONOTONIC corrects."""
    try:
        with open("/proc/self/stat") as f:
            # the command's name, in brackets, may hold spaces
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        since_boot = ticks * 1_000_000_000 // os.sysconf("SC_CLK_TCK")
        slept = (time.clock_gettime_ns(time.CLOCK_BOOTTIME)
                 - time.clock_gettime_ns(time.CLOCK_MONOTONIC))
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return since_boot - slept


def _note_process() -> None:
    """This process's clock pair and the two ends of its
    ``process.boot`` span: from the OS starting it to the recorder's
    import (in a forked child, to the fork's return)."""
    global _pid, _boot
    _pid = os.getpid()
    now = time.perf_counter_ns()
    _anchors[_pid] = (time.time_ns(), now)
    started = _process_start_ns()
    _boot = (started, now) if started is not None and started <= now \
        else None


def _after_fork() -> None:
    _ring.clear()
    _gathered.clear()
    _anchors.clear()
    _note_process()


_note_process()
os.register_at_fork(after_in_child=_after_fork)


_trace_annotation = None    # jax.profiler.TraceAnnotation, once jax is loaded


def annotate(name: str):
    """The mirror into a device profile: a
    ``jax.profiler.TraceAnnotation`` where jax is already loaded in
    this process (next to free when no profile is being taken), else a
    no-op context — never the one to import jax."""
    global _trace_annotation
    if _trace_annotation is None:
        if "jax" not in sys.modules:
            return contextlib.nullcontext()
        from jax.profiler import TraceAnnotation
        _trace_annotation = TraceAnnotation
    return _trace_annotation(name)


class _OpenSpan:
    __slots__ = ("name", "request", "counts", "_start", "_parent",
                 "_token", "_mirror")

    def __init__(self, name, request, counts):
        self.name = name
        self.request = request
        self.counts = counts

    def note(self, request: Optional[str] = None, **counts) -> None:
        """Name the request, or add counts, before the span closes (a
        span may begin before the call that carries its request has an
        id)."""
        if request is not None:
            self.request = request
        if counts:
            self.counts.update(counts)

    def __enter__(self):
        self._parent = _open.get()
        self._token = _open.set(self.name)
        self._mirror = None
        if _trace_annotation is not None or "jax" in sys.modules:
            self._mirror = annotate(ANNOTATION_PREFIX + self.name)
            self._mirror.__enter__()
        self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        if self._mirror is not None:
            self._mirror.__exit__(*exc)
        _open.reset(self._token)
        _ring.append((self.name, self._start, end, self.request,
                      self._parent, _pid, threading.get_ident(),
                      self.counts or None))
        return False


class _NoSpan:
    """What ``span()`` returns with the recorder off: one shared
    object, nothing measured, nothing stored."""

    __slots__ = ()

    def note(self, request=None, **counts) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NO_SPAN = _NoSpan()


def enabled() -> bool:
    """The recorder shares the task-event ring's flag."""
    return get_config().event_log_enabled


def span(name: str, request: Optional[str] = None, **counts):
    """Context manager round one layer's part of a request or a step."""
    if not get_config().event_log_enabled:
        return NO_SPAN
    return _OpenSpan(name, request, counts)


def record(name: str, start_ns: int, end_ns: int,
           request: Optional[str] = None, **counts) -> None:
    """A span whose two ends lie in different callbacks or threads:
    the caller read ``time.perf_counter_ns()`` at both."""
    if not get_config().event_log_enabled:
        return
    _ring.append((name, start_ns, end_ns, request, _open.get(), _pid,
                  threading.get_ident(), counts or None))


def _record_boot() -> None:
    """``process.boot`` goes into the ring when the ring is first read
    (``spans()``, ``drain()``) and not at import: the flag is then
    whatever ``init(_system_config=...)`` or the environment made it,
    and importing this module builds no ``Config``."""
    global _boot
    if _boot is not None and get_config().event_log_enabled:
        boot, _boot = _boot, None
        if boot is not None:        # another thread's reader took it
            _ring.append(("process.boot", boot[0], boot[1], None, None,
                          _pid, threading.get_ident(), None))


def request_of(ref) -> str:
    """The id every span of one serve request carries: the hex id of
    the actor task whose reply ``ref`` is (the replica reads the same
    id from its task context). A promise ref of the driver-side batched
    plane is no task's return and stands for itself."""
    oid = ref.id()
    return oid.hex() if oid.is_put() else oid.task_id().hex()


def current_request() -> Optional[str]:
    """The same id, from inside the actor task that carries the
    request (what ``get_runtime_context().get_task_id()`` reads)."""
    from ray_tpu._private.worker_process import _CURRENT_TASK
    task_id = _CURRENT_TASK.get("task_id")
    return task_id.hex() if task_id else None


def spans() -> List[Span]:
    """Every span this process holds: its own ring and what
    ``collect()`` gathered, oldest first. Readable after shutdown."""
    _record_boot()
    return sorted((Span(*row) for row in list(_gathered) + list(_ring)),
                  key=lambda s: s.start_ns)


def clear() -> None:
    global _boot
    _ring.clear()
    _gathered.clear()
    _boot = None


def drain() -> tuple:
    """This process's ring, emptied, as the ``("spans", ...)`` reply a
    process worker sends: a second collection finds only newer spans."""
    _record_boot()
    out = []
    try:
        while True:
            out.append(_ring.popleft())
    except IndexError:
        pass
    return ("spans", _pid, _anchors[_pid], out)


def absorb(reply: tuple) -> None:
    """The other half of ``drain()``, on the collecting side."""
    _op, pid, anchor, rows = reply
    _anchors[pid] = tuple(anchor)
    _gathered.extend(tuple(row) for row in rows)


def collect(timeout: float = 2.0, worker=None) -> List[Span]:
    """Gather the rings of every live process worker (each is emptied
    into this process), then return ``spans()``. Without a runtime, or
    with the recorder off, it is ``spans()``."""
    if worker is None:
        from ray_tpu._private.worker import try_global_worker
        worker = try_global_worker()
    gather = getattr(worker, "gather_worker_spans", None)
    if gather is not None and enabled():
        for reply in gather(timeout):
            absorb(reply)
    return spans()


# -- device profile ---------------------------------------------------------

def start_trace(logdir: str) -> None:
    """Begin a jax.profiler capture in THIS process (the TPU owner —
    in-process tasks, actors and their spans are captured; process
    workers on CPU annotate their own local traces only)."""
    import jax
    jax.profiler.start_trace(logdir)


def stop_trace() -> None:
    """End the capture begun by ``start_trace``."""
    import jax
    jax.profiler.stop_trace()


# -- export -----------------------------------------------------------------

def task_events() -> List[dict]:
    """Raw task state-transition events (includes per-task exec_ms)."""
    from ray_tpu._private import events
    return events.raw_events()


def _span_events() -> List[dict]:
    own = _anchors[_pid]
    out = []
    for s in spans():
        wall0, perf0 = _anchors.get(s.pid, own)
        args = dict(s.counts or {})
        if s.request is not None:
            args["request"] = s.request
        if s.parent is not None:
            args["parent"] = s.parent
        out.append({"name": s.name, "cat": "span", "ph": "X",
                    "ts": (s.start_ns - perf0 + wall0) / 1e3,
                    "dur": (s.end_ns - s.start_ns) / 1e3,
                    "pid": s.pid, "tid": s.thread, "args": args})
    return out


def timeline(path: Optional[str] = None) -> List[dict]:
    """Chrome-trace events for completed tasks and recorded spans, on
    the wall clock; written to ``path`` (JSON) when given — load in
    chrome://tracing or Perfetto."""
    from ray_tpu._private import events
    trace_events = events.get_task_events() + _span_events()
    if path is not None:
        with open(path, "w") as f:
            json.dump(trace_events, f)
    return trace_events
