"""The flash forward kernel's share of its roofline over the forwards of
the traced window: the least time the chip could take for each layer's
call at the forward's padded length (window layers by their visible
pairs; `costs_layers.flash_cost`) over the kernel's device time."""

from benchmark import costs, costs_layers, traced_forwards

KERNEL = "flash_fwd"


def read(ctx):
    forwards = traced_forwards.whole_forwards(ctx)
    if not forwards:
        return None
    config = ctx["job"]["config"]
    least = seconds = 0.0
    for f in forwards:
        seconds += traced_forwards.kernel_seconds(f, KERNEL)
        least += sum(
            costs.roofline_seconds(cost, ctx["peaks"], ctx["chips"])["seconds"]
            for cost in costs_layers.flash_cost_of_forward(
                config, f["facts"]["padded"]))
    return 100.0 * least / seconds if seconds else None
