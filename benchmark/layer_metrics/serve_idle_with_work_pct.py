"""Share of the traced window in which the device ran nothing while a
request was open in the ingress: the host's part of the idle share."""

from benchmark import program_spans as ps


def read(ctx):
    return ps.idle_with_work_pct(ctx, ps.recorded())
