"""The flash forward kernel's share of its roofline: the least time the
chip could take for the calls the trace holds (FLOPs and bytes reckoned
from shapes by `costs.flash_cost`) over the kernel's device time. It is
compute-bound at these shapes. Under remat the kernel runs twice a layer
a step; every call counts, with its own work."""

from benchmark import costs, trace

KERNEL = ("flash_fwd",)


def read(ctx):
    t, job = ctx["trace"], ctx["job"]
    seconds = trace.named_seconds(t["inside"], KERNEL)
    calls = trace.named_calls(t["inside"], KERNEL)
    if not seconds:
        return None
    cost = costs.flash_cost(job["config"], job["traffic"]["batch"],
                            job["traffic"]["seq"], backward=False)
    least = costs.roofline_seconds(cost, ctx["peaks"], ctx["chips"])
    return 100.0 * calls * least["seconds"] / seconds
