"""Model FLOP/s utilization of the traced steps: tokens a second times
the FLOPs a token needs forward and backward (no recomputation, causal
attention at half), over the chips' bf16 peak."""

from benchmark import costs, trace


def read(ctx):
    t, job = ctx["trace"], ctx["job"]
    steps = trace.count_spans(t["spans"], "step", t["window"])
    if not steps:
        return None
    tokens_per_s = steps * ctx["facts"]["tokens_per_step"] / t["window_s"]
    flops = costs.train_flops_per_token(job["config"], job["traffic"]["seq"])
    return 100.0 * tokens_per_s * flops / (
        ctx["chips"] * ctx["peaks"]["flops_per_s"])
