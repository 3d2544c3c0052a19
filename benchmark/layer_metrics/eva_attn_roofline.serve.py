"""The EVA attention kernel's share of its roofline over the forwards
of the traced window: the least time the chip could take for the pairs
inside windows and the query-summary pairs of every layer at the
forward's padded length (`costs_eva.eva_attn_cost`: bound by compute;
the half of a diagonal tile and the summaries of a tile that no query
may see earn nothing) over the device time of the operations the
program names `eva_attn`."""

from benchmark import costs_eva


def read(ctx):
    return costs_eva.roofline_share(ctx, costs_eva.ATTN,
                                    costs_eva.eva_attn_cost)
