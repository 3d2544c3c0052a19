"""Share of the (token, expert) rows that fell to the experts held
here, from the program's `model.moe.route` records: the median over the
window's forwards. An even router gives held / published experts."""

from benchmark import moe_route


def read(ctx):
    return moe_route.median(ctx, lambda c: 100.0 * c["rows_held"]
                            / c["rows_total"] if c["rows_total"] else None)
