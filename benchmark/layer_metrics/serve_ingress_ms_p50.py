"""Median over the window's requests of the ingress's own time: the
program's `serve.ingress.parse`, and `serve.ingress.reply` from the reply
seen ready to its last byte at the socket."""

from benchmark import program_spans as ps


def read(ctx):
    return ps.percentile(ps.serve_window(ctx), ps.ingress_ms, 50)
