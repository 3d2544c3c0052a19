"""The lightning kernel's share of its roofline over the forwards of
the traced window: the least time the chip could take for the
recurrence of every lightning layer at the forward's padded length
(`costs_sala.lightning_cost`: bound by reading q, k, v and writing o
once) over the device time of the operations the program names
`lightning_attn`."""

from benchmark import costs_sala


def read(ctx):
    return costs_sala.roofline_share(ctx, "lightning_attn", "lightning",
                                     costs_sala.lightning_cost)
