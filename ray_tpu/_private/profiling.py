"""Host-side profiling: on-demand stack dumps + per-process RSS.

Reference analog: ``python/ray/dashboard/modules/reporter/`` — the
py-spy stack-dump and memory endpoints served per node [UNVERIFIED —
mount empty, SURVEY.md §0]. Here the raylet serves the role directly:
a ``dump_stacks`` RPC returns live Python stacks for the raylet
process and every one of its process workers, and worker RSS rides the
heartbeat stats into the per-node Prometheus series and the dashboard
nodes table.
"""

from __future__ import annotations

import sys
import threading
import time
import traceback
from typing import Dict, List, Optional


def dump_all_stacks() -> str:
    """Live stacks of every thread in THIS process (pure-Python; no
    file descriptors, unlike faulthandler — safe to ship over RPC)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    parts: List[str] = []
    for tid, frame in sorted(sys._current_frames().items()):
        parts.append(f"--- thread {names.get(tid, '?')} (id={tid}) ---")
        parts.append("".join(traceback.format_stack(frame)))
    return "\n".join(parts)


def process_rss_bytes(pid: Optional[int] = None) -> int:
    """Resident set size of ``pid`` (default: this process) from
    /proc; 0 when unreadable (non-linux, dead pid)."""
    try:
        with open(f"/proc/{pid or 'self'}/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        pass
    return 0


# Serializes concurrent requests: the per-worker reply slots
# (_stack_evt/_stack_reply, _spans_evt/_spans_reply) are shared state,
# and two overlapping requesters would orphan each other's events.
_REQUEST_LOCK = threading.Lock()
# Makes slot RESET (requester) and slot DELIVERY (worker IO thread)
# atomic against each other: a late reply from a previous timed-out
# request must not interleave with the next request's reset (which
# could report a responsive worker as unresponsive).
_SLOT_LOCK = threading.Lock()


def gather_pool_stacks(worker_pool, timeout: float = 3.0
                       ) -> Dict[str, str]:
    """Live stacks from a pool's registered, live process workers
    (shared by the driver API and the raylet's dump_stacks RPC)."""
    with worker_pool._lock:
        workers = [w for w in worker_pool._all.values()
                   if getattr(w, "conn", None) is not None and w.alive]
    return request_worker_stacks(workers, timeout=timeout)


def request_worker_stacks(workers, timeout: float = 3.0
                          ) -> Dict[str, str]:
    """Request live stacks from process workers and gather their
    ("stacks", text) replies (routed back by the worker IO thread into
    ``deliver_stack_reply``). The request is SIGUSR1 when a pid is
    known — a worker busy executing a task never reads its pipe, and
    mid-task is exactly when stacks matter — falling back to the pipe
    message otherwise. Workers that do not answer within the deadline
    are reported as such rather than omitted."""
    import os
    import signal

    def ask(w) -> None:
        pid = getattr(getattr(w, "proc", None), "pid", None)
        if pid is not None:
            os.kill(pid, signal.SIGUSR1)
        else:
            w.send(("dump_stacks",))

    out: Dict[str, str] = {}
    for w, text in _request_replies(workers, "_stack", ask, timeout):
        out[f"worker:{w.worker_id.hex()[:12]}"] = (
            text if text is not None else "<no reply within deadline>")
    return out


def gather_pool_spans(worker_pool, timeout: float = 2.0) -> List[tuple]:
    """The ("spans", ...) replies of a pool's live process workers:
    each empties its span ring into the reply
    (``ray_tpu.util.tracing.drain``). The request is a pipe message
    that the worker's intake thread answers, also in mid-task; a
    worker that does not answer in time is left out."""
    with worker_pool._lock:
        workers = [w for w in worker_pool._all.values()
                   if getattr(w, "conn", None) is not None and w.alive]
    replies = _request_replies(
        workers, "_spans", lambda w: w.send(("dump_spans",)), timeout)
    return [reply for _w, reply in replies if reply is not None]


def _request_replies(workers, slot: str, ask, timeout: float) -> list:
    """One request to each worker, one reply slot a worker
    (``<slot>_evt`` / ``<slot>_reply``) that ``deliver_reply`` fills
    from the reply routers; returns ``[(worker, reply or None)]``."""
    with _REQUEST_LOCK:
        asked = []
        for w in workers:
            with _SLOT_LOCK:
                setattr(w, slot + "_evt", threading.Event())
                setattr(w, slot + "_reply", None)
            try:
                ask(w)
                asked.append(w)
            except Exception:
                pass    # worker died mid-request: report the rest
        deadline = time.monotonic() + timeout
        out = []
        for w in asked:
            getattr(w, slot + "_evt").wait(
                max(0.0, deadline - time.monotonic()))
            out.append((w, getattr(w, slot + "_reply")))
        return out


def deliver_reply(worker, slot: str, reply) -> None:
    """Reply half of ``_request_replies`` (called from the reply
    routers). Atomic against slot reset — a straggler reply either
    lands fully before the next request's reset (and is discarded by
    it) or fully after (a fresh-enough reply the fresh one then
    overwrites)."""
    with _SLOT_LOCK:
        setattr(worker, slot + "_reply", reply)
        evt = getattr(worker, slot + "_evt", None)
        if evt is not None:
            evt.set()


def deliver_stack_reply(worker, text: str) -> None:
    deliver_reply(worker, "_stack", text)


def deliver_spans_reply(worker, reply: tuple) -> None:
    deliver_reply(worker, "_spans", reply)


def worker_rss_map(worker_pool) -> Dict[str, int]:
    """worker-hex -> RSS bytes for a pool's live process workers."""
    out: Dict[str, int] = {}
    with worker_pool._lock:
        workers = list(worker_pool._all.values())
    for w in workers:
        proc = getattr(w, "proc", None)
        if proc is not None and w.alive:
            rss = process_rss_bytes(proc.pid)
            if rss:
                out[w.worker_id.hex()[:12]] = rss
    return out
