"""The two mixers' share of the forwards' device time in the traced
window: the device seconds of the operations the profile names
`kda_attn` and `flash_fwd` over the seconds the device was busy inside
the same forwards."""

from benchmark import costs_kimi, trace, traced_forwards

KERNELS = (costs_kimi.KDA_KERNEL, costs_kimi.MLA_KERNEL)


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
