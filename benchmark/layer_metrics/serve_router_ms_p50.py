"""Median `serve.router.assign`: entry to the replica call submitted."""

from benchmark import program_spans as ps


def read(ctx):
    return ps.percentile(ps.serve_window(ctx), ps.router_ms, 50)
