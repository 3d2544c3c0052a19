"""From the program's own spans to numbers. No jax here.

The program records spans at its layer boundaries in one ring a process
(`ray_tpu.util.tracing`; the table is in `docs/tracing.md`), on
`time.perf_counter_ns()`, which every process of a host shares. A span
is `(name, start_ns, end_ns, request, parent, pid, thread, counts)`; all
spans of one serve request carry one id. This file is what the readers
under `layer_metrics/` share: the spans of the measured window grouped
by request, a span's self time, the two waits that are gaps between
spans, and the offset that lays the program's clock on a device trace's.
A program without the recorder (an older commit) yields `None`
everywhere, and so do its readers.
"""

from __future__ import annotations

from collections import namedtuple
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmark import trace

Span = namedtuple(
    "Span", "name start_ns end_ns request parent pid thread counts")

ROOT = "serve.request"
PARSE = "serve.ingress.parse"
ASSIGN = "serve.router.assign"
REPLICA = "serve.replica.request"
ADMISSION = "serve.replica.admission"
INVOKE = "serve.replica.invoke"
REPLY = "serve.ingress.reply"
GET = "serve.ingress.get"
WRITE = "serve.ingress.write"
REPORT = "train.report"

MAX_ALIGN_ERROR_S = 200e-6


def recorded() -> Optional[List[Span]]:
    """Every span the program holds after shutdown, oldest first, or
    `None` where the program has no recorder."""
    try:
        from ray_tpu.util import tracing
    except ImportError:
        return None
    read = getattr(tracing, "spans", None)
    if read is None:
        return None
    return [Span(*s) for s in read()]


def ms(span: Span) -> float:
    return (span.end_ns - span.start_ns) / 1e6


def self_ms(span: Span, spans: Sequence[Span]) -> float:
    """The span less the spans it is the parent of: those of its
    thread that name it as parent and lie inside it."""
    inner = sum(ms(s) for s in spans
                if s.parent == span.name and s.pid == span.pid
                and s.thread == span.thread and s is not span
                and span.start_ns <= s.start_ns and s.end_ns <= span.end_ns)
    return ms(span) - inner


def by_request(spans: Sequence[Span]) -> Dict[str, Dict[str, Span]]:
    """request id -> span name -> the request's span of that name."""
    out: Dict[str, Dict[str, Span]] = {}
    for s in spans:
        if s.request is not None:
            out.setdefault(s.request, {}).setdefault(s.name, s)
    return out


def window_requests(spans: Optional[Sequence[Span]], window_s: float,
                    offered: int) -> List[Dict[str, Span]]:
    """The requests of the measured window, in the order they came in:
    those with a root span that began within `window_s` of the last
    root's beginning, and of them the last `offered` (what came
    before is the warm-up)."""
    if not spans:
        return []
    rooted = sorted((r for r in by_request(spans).values() if ROOT in r),
                    key=lambda r: r[ROOT].start_ns)
    if not rooted:
        return []
    since = rooted[-1][ROOT].start_ns - window_s * 1e9
    kept = [r for r in rooted if r[ROOT].start_ns >= since]
    return kept[-offered:] if offered > 0 else []


def serve_window(ctx: dict) -> List[Dict[str, Span]]:
    """`window_requests` for a serve cell's reader."""
    facts = ctx["facts"]
    return window_requests(recorded(), facts["window_s"],
                           len(facts["late_ms"]))


# -- one request's numbers, each `None` where a span it needs is missing ----

def ingress_ms(r: Dict[str, Span]) -> Optional[float]:
    """The ingress's own time: parse, and from the reply seen ready to
    its last byte at the socket (fetch, render and send)."""
    if PARSE not in r or REPLY not in r:
        return None
    return ms(r[PARSE]) + ms(r[REPLY])


def router_ms(r: Dict[str, Span]) -> Optional[float]:
    return ms(r[ASSIGN]) if ASSIGN in r else None


def replica_wait_ms(r: Dict[str, Span]) -> Optional[float]:
    """Router's submit to the replica's entry (the wire, the actor's
    mailbox, the wait behind an earlier forward), and the admission
    semaphore where the deployment has one."""
    if ASSIGN not in r or REPLICA not in r:
        return None
    gap = (r[REPLICA].start_ns - r[ASSIGN].end_ns) / 1e6
    return gap + (ms(r[ADMISSION]) if ADMISSION in r else 0.0)


def reply_wait_ms(r: Dict[str, Span]) -> Optional[float]:
    """Replica's return to the ingress seeing the reply ready: result
    store, notification, the poll thread's wake-up."""
    if REPLICA not in r or REPLY not in r:
        return None
    return (r[REPLY].start_ns - r[REPLICA].end_ns) / 1e6


def queue_depth(r: Dict[str, Span]) -> Optional[float]:
    counts = r[REPLICA].counts if REPLICA in r else None
    return None if not counts or "ongoing" not in counts else \
        float(counts["ongoing"])


def percentile(requests: Sequence[Dict[str, Span]], number,
               q: float) -> Optional[float]:
    """The q-th percentile of `number(request)` over the requests that
    have it; `None` where none has."""
    values = [v for v in map(number, requests) if v is not None]
    return float(np.percentile(values, q)) if values else None


# -- the program's clock on a device trace's ---------------------------------

def align(trace_starts_s: Sequence[float], program_starts_s: Sequence[float],
          max_error_s: float = MAX_ALIGN_ERROR_S
          ) -> Optional[Tuple[float, float]]:
    """`(offset, error)` with trace time = program time + offset, both
    in seconds, from two lists of the same events' beginnings, one a
    run of consecutive events of the other (a profile covers part of
    a run). The shorter list is slid along the longer; at the shift
    where the differences agree best their median is the offset and
    the widest deviation from it the error. `None` with under three
    events, or an error over `max_error_s`: the lists are then not the
    same events, or one clock's reading of them wanders."""
    a = np.sort(np.asarray(trace_starts_s, np.float64))
    b = np.sort(np.asarray(program_starts_s, np.float64))
    if min(len(a), len(b)) < 3:
        return None
    short, long_, sign = (a, b, 1.0) if len(a) <= len(b) else (b, a, -1.0)
    windows = np.lib.stride_tricks.sliding_window_view(long_, len(short))
    # trace - program at every shift, one row a shift
    diffs = sign * (short[None, :] - windows)
    best = diffs[np.argmin(np.ptp(diffs, axis=1))]
    offset = float(np.median(best))
    error = float(np.max(np.abs(best - offset)))
    return (offset, error) if error <= max_error_s else None


def trace_offset(ctx: dict, spans: Optional[Sequence[Span]]
                 ) -> Optional[Tuple[float, float]]:
    """The serve cell's anchor: the benchmark's `replica_call` spans in
    the profile and the program's `serve.replica.invoke` spans are the
    same calls in the same order, each `replica_call` beginning a few
    microseconds inside its `invoke`."""
    if not spans:
        return None
    calls = [start for name, start, _dur in ctx["trace"]["spans"]
             if name == "replica_call"]
    invokes = [s.start_ns / 1e9 for s in spans if s.name == INVOKE]
    return align(calls, invokes)


def overlap_seconds(a: Sequence[Sequence[float]],
                    b: Sequence[Sequence[float]]) -> float:
    """Seconds that lie in both of two sorted lists of disjoint
    [start, end) intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_with_work_pct(ctx: dict, spans: Optional[Sequence[Span]]
                       ) -> Optional[float]:
    """Share of the traced window in which the first device ran nothing
    and at least one `serve.request` was open: the part of the device's
    idle share that is the host's doing, not the traffic's."""
    found = trace_offset(ctx, spans)
    if found is None:
        return None
    offset = found[0]
    t = ctx["trace"]
    window = tuple(t["window"])
    ops = t["inside"][min(t["inside"])]
    busy = trace.merged(trace.clipped(ops, window))
    roots = [(s.start_ns / 1e9 + offset, (s.end_ns - s.start_ns) / 1e9)
             for s in spans if s.name == ROOT]
    open_ = trace.merged(trace.clipped(
        [(ROOT, start, dur) for start, dur in roots], window))
    with_work = sum(e - s for s, e in open_)
    idle = with_work - overlap_seconds(open_, busy)
    return 100.0 * idle / (window[1] - window[0])


# -- the train session --------------------------------------------------------

def report_ms_p50(spans: Optional[Sequence[Span]]) -> Optional[float]:
    """Median `train.report`. A run's last report carries the outcome
    and is one among some 180: it cannot move the median."""
    values = [ms(s) for s in spans or () if s.name == REPORT]
    return float(np.percentile(values, 50)) if values else None
