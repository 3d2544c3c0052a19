"""The grouped matmul's share of its roofline over the forwards of the
traced window: the least time the chip could take for the rows the held
experts were given and the weights of those that had a row
(`costs_layers.gmm_cost`; whichever of compute and bandwidth bounds
each forward) over the kernel's device time."""

from benchmark import costs, costs_layers, traced_forwards

KERNEL = "moe_gmm"


def read(ctx):
    forwards = traced_forwards.whole_forwards(ctx)
    if not forwards:
        return None
    config = ctx["job"]["config"]
    least = seconds = 0.0
    for f in forwards:
        seconds += traced_forwards.kernel_seconds(f, KERNEL)
        cost = costs_layers.gmm_cost(config, f["facts"]["rows_held"],
                                     f["facts"]["experts_hit"])
        least += costs.roofline_seconds(cost, ctx["peaks"],
                                        ctx["chips"])["seconds"]
    return 100.0 * least / seconds if seconds else None
