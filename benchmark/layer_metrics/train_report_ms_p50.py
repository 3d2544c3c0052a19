"""Median `train.report`: the stop-token and resize checks and the
report's pickle, fsync and rename, between two steps."""

from benchmark import program_spans as ps


def read(ctx):
    return ps.report_ms_p50(ps.recorded())
