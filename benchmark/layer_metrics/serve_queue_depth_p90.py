"""90th percentile of the requests a request found in the replica on
entry (`ongoing` on `serve.replica.request`): the queue length."""

from benchmark import program_spans as ps


def read(ctx):
    return ps.percentile(ps.serve_window(ctx), ps.queue_depth, 90)
