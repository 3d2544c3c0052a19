"""Median wait between the replica's return and the ingress seeing the
reply ready: result store, notification, the poll thread's wake-up."""

from benchmark import program_spans as ps


def read(ctx):
    return ps.percentile(ps.serve_window(ctx), ps.reply_wait_ms, 50)
