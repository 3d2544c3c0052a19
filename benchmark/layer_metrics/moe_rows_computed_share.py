"""Share of the (token, expert) rows that the routed layers' gather,
activation and combine passed over, from the program's `model.moe.route`
records: the median over the window's forwards. 100 is the static worst
case in every layer; a program that chooses its row count on the device
reads its rung over the rows. A program whose record lacks the count
yields `None`."""

from benchmark import moe_route


def read(ctx):
    return moe_route.median(
        ctx, lambda c: 100.0 * c["rows_computed"] / c["rows_total"]
        if c.get("rows_computed") is not None and c["rows_total"] else None)
