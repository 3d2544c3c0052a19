"""Median time from when a request was due to when the deployment's
method was entered: generator, ingress, router, object plane and the
wait behind earlier forwards."""

import numpy as np


def read(ctx):
    values = ctx["facts"]["inbound_ms"]
    return float(np.percentile(values, 50)) if len(values) else None
