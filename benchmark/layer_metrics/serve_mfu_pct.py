"""The served forward passes' share of the chip's bf16 peak over the
whole window: 2 FLOPs for each matrix parameter and each real (unpadded)
prompt token answered."""

from benchmark import costs


def read(ctx):
    facts = ctx["facts"]
    flops = costs.forward_flops_per_token(ctx["job"]["config"]) * \
        facts["prompt_tokens_answered"]
    return 100.0 * flops / (facts["window_s"] * ctx["chips"]
                            * ctx["peaks"]["flops_per_s"])
