"""The busiest held expert's rows over the mean held expert's, from the
program's `model.moe.route` records: the median over the window's
forwards. 1 is an even load."""

from benchmark import moe_route


def read(ctx):
    return moe_route.median(ctx, lambda c: c["load_max"] / c["load_mean"]
                            if c["load_mean"] else None)
