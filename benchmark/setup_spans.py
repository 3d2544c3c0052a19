"""`setup_s` from the inside: what the six `setup` readers share. No jax here.

Since PR 37 the program's recorder (`ray_tpu.util.tracing`, read through
`program_spans.recorded()`) also holds the spans of a process's start:
`process.boot` (the OS starting the process -> the recorder's import),
`runtime.init`, `serve.start`, and one `jax.trace`, `jax.lower` and
`jax.compile` for each program jax builds or loads from its cache, whose
`request` field is the function's name and whose `jax.compile` counts
`cache_hit`. All are read in the process that made `runtime.init`: the one
that owns the chips.

"Before the window" is ending before the window's first program span, and
"inside" beginning between that and the end of its last one. A serve
cell's are `program_spans.window_requests`'s first and last
`serve.request`. The train cell's are its first `train.report` and its
last but one: the run's last report carries the outcome and is made after
the reference has run, whose own compiles so count nowhere. The driver
reports after a step, so the train window's first step lies before its
first report: a program built in that one step reads as set-up here, and
the four parts can then pass `setup_s` (PERF.md section 7).

A program without these spans (an older commit) reads `None` everywhere.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from benchmark import program_spans as ps
from benchmark import trace

BOOT = "process.boot"
INIT = "runtime.init"
START = "serve.start"
TRACE = "jax.trace"
LOWER = "jax.lower"
COMPILE = "jax.compile"


def window_ns(facts: dict, spans: Sequence[ps.Span]
              ) -> Optional[Tuple[int, int]]:
    """The window's first program span's start and its last one's end.
    The spans say which kind of cell it is: a ring with `serve.request`
    roots is a serve cell's, one with `train.report`s a train cell's."""
    if any(s.name == ps.ROOT for s in spans):
        roots = [r[ps.ROOT] for r in ps.window_requests(
            spans, facts["window_s"], len(facts["late_ms"]))]
        if not roots:
            return None
        return roots[0].start_ns, max(r.end_ns for r in roots)
    reports = sorted((s for s in spans if s.name == ps.REPORT),
                     key=lambda s: s.start_ns)
    if len(reports) < 2:
        return None
    return reports[0].start_ns, reports[-2].end_ns


def union(spans: Sequence[ps.Span]) -> List[List[float]]:
    """The spans' intervals in seconds, merged: nested `jax.trace`
    events (an inner `jax.jit`'s lies inside the outer one's) count
    once."""
    return trace.merged((s.start_ns / 1e9, s.end_ns / 1e9) for s in spans)


def seconds(intervals: Sequence[Sequence[float]]) -> float:
    return sum(end - start for start, end in intervals)


def split(facts: dict, spans: Optional[Sequence[ps.Span]]
          ) -> Dict[str, Optional[float]]:
    """The six readings by metric name, each `None` where the spans it
    needs are not there."""
    out: Dict[str, Optional[float]] = dict.fromkeys((
        "setup_before_init_s", "setup_runtime_s", "setup_trace_lower_s",
        "setup_compile_s", "setup_compile_miss_s", "window_compiles"))
    inits = [s for s in spans or () if s.name == INIT]
    if not inits:
        return out
    init = inits[0]
    mine = [s for s in spans if s.pid == init.pid]
    built = [s for s in mine if s.name in (TRACE, LOWER, COMPILE)]
    boots = [s for s in mine if s.name == BOOT]
    if boots:
        out["setup_before_init_s"] = (init.start_ns
                                      - boots[0].start_ns) / 1e9
    window = window_ns(facts, spans)
    before = [s for s in built if window is None or s.end_ns <= window[0]]
    # less every `jax.*` interval of the process, whatever its thread (on
    # a TPU host the scheduler's round-trip probe makes the process's
    # first program on a thread of its own meanwhile): the four parts
    # then share no second of the timeline
    runtime = union(s for s in mine if s.name in (INIT, START)
                    and (window is None or s.end_ns <= window[0]))
    out["setup_runtime_s"] = seconds(runtime) - ps.overlap_seconds(
        runtime, union(before))
    compiles = [s for s in built if s.name == COMPILE]
    if window is None or not compiles:
        return out
    compiled = [s for s in compiles if s.end_ns <= window[0]]
    out["setup_trace_lower_s"] = seconds(union(
        s for s in before if s.name in (TRACE, LOWER)))
    out["setup_compile_s"] = seconds(union(compiled))
    out["setup_compile_miss_s"] = seconds(union(
        s for s in compiled if not (s.counts or {}).get("cache_hit")))
    out["window_compiles"] = float(sum(
        window[0] <= s.start_ns <= window[1] for s in compiles))
    return out


def read(ctx: dict, metric: str) -> Optional[float]:
    """What `layer_metrics/<metric>.py` returns."""
    return split(ctx["facts"], ps.recorded())[metric]
