"""The EVA mixer's share of the forwards' device time in the traced
window: the device seconds of the operations the program names
`eva_attn` and `eva_summaries` over the seconds the device was busy
inside the same forwards."""

from benchmark import costs_eva, trace, traced_forwards

KERNELS = (costs_eva.ATTN, costs_eva.SUMMARIES)


def read(ctx):
    forwards = traced_forwards.whole_forwards(ctx)
    if not forwards:
        return None
    mixer = busy = 0.0
    for f in forwards:
        mixer += sum(traced_forwards.kernel_seconds(f, k) for k in KERNELS)
        if f["ops"]:
            busy += trace.busy_seconds(f["ops"], (
                min(s for _n, s, _d in f["ops"]),
                max(s + d for _n, s, d in f["ops"])))
    return 100.0 * mixer / busy if mixer and busy else None
