"""The program's `model.moe.route` records (one a forward; counts
`layers`, `rows_total`, `rows_held`, `load_max`, `load_mean`) of the
measured window. A program without the record yields `None`."""

from __future__ import annotations

from typing import Optional

import numpy as np

from benchmark import program_spans as ps

ROUTE = "model.moe.route"


def window_counts(ctx: dict) -> list:
    """The counts of the window's forwards: the last `offered` records
    (what came before is set-up and warm-up)."""
    offered = len(ctx["facts"]["late_ms"])
    records = [s.counts for s in ps.recorded() or ()
               if s.name == ROUTE and s.counts]
    return records[-offered:] if offered > 0 else []


def median(ctx: dict, number) -> Optional[float]:
    values = [v for v in map(number, window_counts(ctx)) if v is not None]
    return float(np.median(values)) if values else None
