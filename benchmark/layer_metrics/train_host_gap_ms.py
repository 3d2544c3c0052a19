"""Wall time a step less device busy time a step: what trainer,
session, report and the TPU lane add round the program."""

from benchmark import trace


def read(ctx):
    t = ctx["trace"]
    steps = trace.count_spans(t["spans"], "step", t["window"])
    return 1e3 * (t["window_s"] - t["busy_s"]) / steps if steps else None
