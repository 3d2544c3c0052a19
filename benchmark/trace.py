"""From a list of trace events to numbers. No jax here: `xplane.py` turns a
profile into the events, the tests hand-make them.

An event is `(name, start_s, duration_s)`. `device_ops` maps a device's
index to its operations; `spans` are the benchmark's own host spans
(`bench:<what>`), on the same clock.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]

MIN_GAP_S = 20e-6


def merged(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Union of [start, end) intervals, sorted."""
    out: List[List[float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def clipped(events: Sequence[Event], window: Tuple[float, float]) -> list:
    """[start, end) of each event, cut to the window; empty ones dropped."""
    lo, hi = window
    cut = ((max(s, lo), min(s + d, hi)) for _n, s, d in events)
    return [(s, e) for s, e in cut if e > s]


def busy_seconds(events: Sequence[Event],
                 window: Tuple[float, float]) -> float:
    return sum(e - s for s, e in merged(clipped(events, window)))


def self_seconds(events: Sequence[Event]) -> Dict[str, float]:
    """Seconds by operation name, each operation less the operations
    nested inside it (a loop's line holds its body's too)."""
    totals: Dict[str, float] = {}
    stack: List[list] = []          # [name, end, self_seconds]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _end, own = stack.pop()
            totals[name] = totals.get(name, 0.0) + max(own, 0.0)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return totals


def mean_over_devices(device_ops: Dict[int, Sequence[Event]], fn) -> float:
    return sum(fn(ops) for ops in device_ops.values()) / len(device_ops)


def named_seconds(device_ops: Dict[int, Sequence[Event]],
                  fragments: Sequence[str]) -> float:
    """Self seconds of the operations whose name holds one of the
    fragments, averaged over the devices."""
    def one(ops):
        return sum(sec for name, sec in self_seconds(ops).items()
                   if any(f in name for f in fragments))
    return mean_over_devices(device_ops, one)


def named_calls(device_ops: Dict[int, Sequence[Event]],
                fragments: Sequence[str]) -> float:
    """How many operations carry one of the fragments in their name,
    averaged over the devices."""
    return mean_over_devices(device_ops, lambda ops: sum(
        1 for name, _s, _d in ops if any(f in name for f in fragments)))


def top_operations(device_ops: Dict[int, Sequence[Event]],
                   n: int = 10) -> List[list]:
    totals: Dict[str, float] = {}
    for ops in device_ops.values():
        for name, sec in self_seconds(ops).items():
            totals[name] = totals.get(name, 0.0) + sec / len(device_ops)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, sec] for name, sec in ranked]


def span_at(spans: Sequence[Event], t: float) -> str:
    """The innermost benchmark span open at time t."""
    best: Optional[Event] = None
    for span in spans:
        if span[1] <= t < span[1] + span[2] and (
                best is None or span[2] < best[2]):
            best = span
    return best[0] if best else "no_benchmark_span_open"


def idle_gaps(events: Sequence[Event], spans: Sequence[Event],
              window: Tuple[float, float], n: int = 10) -> List[list]:
    """Idle seconds of one device inside the window, by the benchmark
    span open at the middle of each gap; gaps under 20 us lumped
    together."""
    lo, hi = window
    edges = [[lo, lo]] + merged(clipped(events, window)) + [[hi, hi]]
    totals: Dict[str, float] = {}
    for (_s, end), (start, _e) in zip(edges, edges[1:]):
        gap = start - end
        if gap <= 0:
            continue
        name = span_at(spans, end + gap / 2) if gap >= MIN_GAP_S else \
            "gaps_under_20_us"
        totals[name] = totals.get(name, 0.0) + gap
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, sec] for name, sec in ranked]


def window_of(spans: Sequence[Event], name: str) -> Tuple[float, float]:
    for span in spans:
        if span[0] == name:
            return span[1], span[1] + span[2]
    raise LookupError(f"no span {name!r} in the trace")


def count_spans(spans: Sequence[Event], name: str,
                window: Tuple[float, float]) -> int:
    """Spans of that name that lie wholly inside the window."""
    lo, hi = window
    return sum(1 for n, s, d in spans
               if n == name and s >= lo and s + d <= hi)


def summary(device_ops: Dict[int, Sequence[Event]], spans: Sequence[Event],
            window_span: str) -> dict:
    """What the result's `device` and `breakdown` carry."""
    window = window_of(spans, window_span)
    busy = mean_over_devices(device_ops, lambda o: busy_seconds(o, window))
    inside = {dev: [e for e in ops if window[0] <= e[1] < window[1]]
              for dev, ops in device_ops.items()}
    first = device_ops[min(device_ops)]
    return {"busy_s": busy, "window_s": window[1] - window[0],
            "window": window, "inside": inside, "spans": list(spans),
            "breakdown": {"device_ops": top_operations(inside),
                          "idle_gaps": idle_gaps(first, spans, window)}}
