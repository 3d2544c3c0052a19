"""The grouped matmul's share of the device's busy time in the traced
window."""

from benchmark import trace

KERNEL = ("moe_gmm",)


def read(ctx):
    t = ctx["trace"]
    seconds = trace.named_seconds(t["inside"], KERNEL)
    if not seconds or not t["busy_s"]:
        return None
    return 100.0 * seconds / t["busy_s"]
