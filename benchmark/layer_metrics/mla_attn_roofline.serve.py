"""The flash forward's share of its roofline in a `kimi_linear` cell's
forwards of the traced window: the least time the chip could take for
the causal pairs of every mla layer at the forward's padded length,
scored over nope + rope lanes and weighing v lanes
(`costs_kimi.mla_cost`), over the device time of the `flash_fwd`
kernel."""

from benchmark import costs_kimi


def read(ctx):
    return costs_kimi.roofline_share(ctx, costs_kimi.MLA_KERNEL, "mla")
