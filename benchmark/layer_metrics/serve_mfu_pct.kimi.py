"""The served forward passes' share of the chip's bf16 peak over the
whole window, for a `kimi_linear` configuration: this chip's share of
the FLOPs of each prompt answered, at its real (unpadded) length
(`costs_kimi.forward_flops`: matrices, the latent attention's causal
pairs, the delta rule's recurrence, the held experts' rows scaled from
the padded forward's count to the real tokens, the head at one
position)."""

from benchmark import costs_kimi


def read(ctx):
    facts = ctx["facts"]
    forwards = facts.get("forwards")
    if not forwards:
        return None
    config = ctx["job"]["config"]
    flops = sum(costs_kimi.forward_flops(
        config, f["real"], f["rows_held"] * f["real"] // f["padded"])
        for f in forwards if f["id"] >= 0)
    return 100.0 * flops / (facts["window_s"] * ctx["chips"]
                            * ctx["peaks"]["flops_per_s"])
