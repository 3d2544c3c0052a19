"""95th percentile of how late the generator sent a request after it
was due: a starved generator must not read as a fast server."""

import numpy as np


def read(ctx):
    values = ctx["facts"]["late_ms"]
    return float(np.percentile(values, 95)) if len(values) else None
