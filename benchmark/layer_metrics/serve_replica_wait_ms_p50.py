"""Median wait between the router's submit and the replica's entry (the
wire, the actor's mailbox, an earlier forward), admission included."""

from benchmark import program_spans as ps


def read(ctx):
    return ps.percentile(ps.serve_window(ctx), ps.replica_wait_ms, 50)
