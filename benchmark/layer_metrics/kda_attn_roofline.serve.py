"""The delta-rule kernel's share of its roofline over the forwards of
the traced window: the least time the chip could take for the
recurrence of every kda layer at the forward's padded length
(`costs_kimi.kda_cost`: the larger of its operations at the peak and q,
k, v, the decays and the steps read and o written once at the
bandwidth) over the device time of the operations the program names
`kda_attn`."""

from benchmark import costs_kimi


def read(ctx):
    return costs_kimi.roofline_share(ctx, costs_kimi.KDA_KERNEL, "kda")
