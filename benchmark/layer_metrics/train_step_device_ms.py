"""Device busy time a step, from the trace, averaged over the chips."""

from benchmark import trace


def read(ctx):
    t = ctx["trace"]
    steps = trace.count_spans(t["spans"], "step", t["window"])
    return 1e3 * t["busy_s"] / steps if steps else None
