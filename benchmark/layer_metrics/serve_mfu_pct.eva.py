"""The served forward passes' share of the chip's bf16 peak over the
whole window, for an `evabyte` configuration: the FLOPs of each prompt
answered, at its real (unpadded) length (`costs_eva.forward_flops`:
matrices, the summaries' pooling, the pairs inside windows and the
query-summary pairs, the head at one position)."""

from benchmark import costs_eva


def read(ctx):
    facts = ctx["facts"]
    forwards = facts.get("forwards")
    if not forwards:
        return None
    config = ctx["job"]["config"]
    flops = sum(costs_eva.forward_flops(config, f["real"])
                for f in forwards if f["id"] >= 0)
    return 100.0 * flops / (facts["window_s"] * ctx["chips"]
                            * ctx["peaks"]["flops_per_s"])
