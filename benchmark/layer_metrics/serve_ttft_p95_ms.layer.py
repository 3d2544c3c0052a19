"""95th percentile of time to first token over all requests due in the
window, due time to response read. Among the layer metrics because over
two sets of six 25 s runs it spread by 23 % and 6 % of its median (PERF.md
2 and 6), too wide for a bound."""

import numpy as np


def read(ctx):
    values = ctx["facts"]["ttft_ms"]
    return float(np.percentile(values, 95)) if len(values) else None
