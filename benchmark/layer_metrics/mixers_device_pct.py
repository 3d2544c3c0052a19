"""The mixers' share of the forwards' device time in the traced
window: the device seconds of the operations the program names
`lightning_attn`, `sparse_attn` and `sparse_select` over the seconds
the device was busy inside the same forwards."""

from benchmark import trace, traced_forwards

KERNELS = ("lightning_attn", "sparse_attn", "sparse_select")


def read(ctx):
    forwards = traced_forwards.whole_forwards(ctx)
    if not forwards:
        return None
    mixers = busy = 0.0
    for f in forwards:
        mixers += sum(traced_forwards.kernel_seconds(f, k) for k in KERNELS)
        if f["ops"]:
            busy += trace.busy_seconds(f["ops"], (
                min(s for _n, s, _d in f["ops"]),
                max(s + d for _n, s, d in f["ops"])))
    return 100.0 * mixers / busy if mixers and busy else None
