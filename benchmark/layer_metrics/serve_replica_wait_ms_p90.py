"""90th percentile of the wait between the router's submit and the
replica's entry: the wait behind an earlier forward."""

from benchmark import program_spans as ps


def read(ctx):
    return ps.percentile(ps.serve_window(ctx), ps.replica_wait_ms, 90)
