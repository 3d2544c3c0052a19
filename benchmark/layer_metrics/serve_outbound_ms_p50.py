"""Median time from the deployment's method returning to the client
having read the response: reply, object plane, ingress."""

import numpy as np


def read(ctx):
    values = ctx["facts"]["outbound_ms"]
    return float(np.percentile(values, 50)) if len(values) else None
