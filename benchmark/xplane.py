"""Takes a window's profile with `jax.profiler` and reads the
`.xplane.pb` into the events that `trace.py` reduces. Only the process
that holds the chip can trace it.
"""

from __future__ import annotations

import contextlib
import glob
import json
import os
import shutil
import tempfile
from typing import Dict, List, Tuple

from benchmark.trace import Event

SPAN_PREFIX = "bench:"
_DEVICE_PLANE = "/device:"
_OPS_LINE = "XLA Ops"


def kernel_signatures() -> dict:
    """(operands, result is a tuple) -> kernel, from `kernels/*.json`.
    The program's Pallas calls carry no name into the profile (all are
    `tpu_custom_call`), so a kernel is known by its call signature."""
    out = {}
    here = os.path.join(os.path.dirname(os.path.abspath(__file__)), "kernels")
    for path in sorted(glob.glob(os.path.join(here, "*.json"))):
        with open(path) as f:
            k = json.load(f)
        out[(k["operands"], k["tuple_result"])] = k["kernel"]
    return out


def op_name(hlo: str, kernels: dict) -> str:
    """The profile names an operation by its whole HLO line.
    `%fusion.243 = ...` -> `fusion.243`; a Pallas kernel -> its name
    under `kernels/`, the same for every layer's call."""
    head, _, rest = hlo.partition(" = ")
    if 'custom_call_target="tpu_custom_call"' in rest:
        result, _, call = rest.partition(" custom-call(")
        operands = call.split("), custom_call_target")[0].count(" %")
        kernel = kernels.get((operands, result.startswith("(")))
        if kernel:
            return kernel
    return head.lstrip("%")


def span(name: str):
    """A host span on the device trace's clock; costs nothing to speak
    of when no trace is being taken."""
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


@contextlib.contextmanager
def profiled(window_span: str):
    """Profiles the body, under a span of that name; yields a dict that
    holds `device_ops` and `spans` once the body has ended. The profile
    itself is deleted."""
    import jax
    out: dict = {}
    directory = tempfile.mkdtemp(prefix="bench_trace_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # the spans are TraceAnnotations
    try:
        jax.profiler.start_trace(directory, profiler_options=options)
        try:
            with span(window_span):
                yield out
        finally:
            jax.profiler.stop_trace()
        out["device_ops"], out["spans"] = read(directory)
    finally:
        shutil.rmtree(directory, ignore_errors=True)


def read(directory: str) -> Tuple[Dict[int, List[Event]], List[Event]]:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {directory}, "
                           f"found {paths}")
    device_ops: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    kernels = kernel_signatures()
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith(_DEVICE_PLANE):
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    index = int(plane.name.rsplit(":", 1)[1])
                    device_ops[index] = [
                        (op_name(e.name, kernels), e.start_ns * 1e-9,
                         e.duration_ns * 1e-9) for e in line.events]
        else:
            for line in plane.lines:
                spans.extend(
                    (e.name[len(SPAN_PREFIX):], e.start_ns * 1e-9,
                     e.duration_ns * 1e-9)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    if not any(device_ops.values()):
        raise RuntimeError("the profile holds no device operation: "
                           "nothing ran on the chip inside the window")
    return device_ops, spans
