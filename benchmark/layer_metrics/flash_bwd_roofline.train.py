"""The two flash backward kernels' (dq; dk and dv) share of their
roofline: the least time for the backward passes the trace holds (five
matmuls over half the score matrix each, `costs.flash_cost`) over both
kernels' device time. Each kernel recomputes the scores, which the
algorithm needs once: that shows here as a share under 100."""

from benchmark import costs, trace

KERNEL_DQ = ("flash_bwd_dq",)
KERNEL_DKV = ("flash_bwd_dkv",)


def read(ctx):
    t, job = ctx["trace"], ctx["job"]
    seconds = trace.named_seconds(t["inside"], KERNEL_DQ + KERNEL_DKV)
    passes = trace.named_calls(t["inside"], KERNEL_DQ)
    if not seconds or not passes:
        return None
    cost = costs.flash_cost(job["config"], job["traffic"]["batch"],
                            job["traffic"]["seq"], backward=True)
    least = costs.roofline_seconds(cost, ctx["peaks"], ctx["chips"])
    return 100.0 * passes * least["seconds"] / seconds
