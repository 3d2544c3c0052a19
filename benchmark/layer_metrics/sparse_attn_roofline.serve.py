"""The sparse attention kernel's share of its roofline over the
forwards of the traced window: the least time the chip could take for
the pairs every sparse layer's queries selected at the forward's padded
length (`costs_sala.sparse_attn_cost`: bound by compute; the blocks a
kernel fetches beyond the chosen ones earn nothing) over the device
time of the operations the program names `sparse_attn`."""

from benchmark import costs_sala


def read(ctx):
    return costs_sala.roofline_share(ctx, "sparse_attn", "sparse",
                                     costs_sala.sparse_attn_cost)
