"""Operations and bytes of a `kimi_linear` configuration's two mixers
and of one forward, from shapes, **for this chip's share only** and as
**the least work the mathematics asks, whatever implements it**: a
delta-rule kernel that works in chunks is credited with the recurrence
token by token, the latent attention with its causal pairs at the
widths it scores and weighs, the routed experts with the rows the held
ones were given. A share over 100 % is a fault of the count.
"""

from __future__ import annotations

from benchmark import costs, costs_layers, traced_forwards

BYTES = 2       # bfloat16, the type the configuration states
DECAY_BYTES = 4     # the log-decays and the steps are float32

KDA_KERNEL, MLA_KERNEL = "kda_attn", "flash_fwd"


def layer_kinds(c: dict) -> list:
    """[("kda" or "mla", routed)] for each layer."""
    linear = c["linear_attn_config"]
    return [("mla" if i + 1 in linear["full_attn_layers"] else "kda",
             i >= c["first_k_dense_replace"])
            for i in range(c["num_hidden_layers"])]


def _kda_wide(c: dict) -> int:
    linear = c["linear_attn_config"]
    return linear["num_heads"] * linear["head_dim"]


def kda_params(c: dict) -> int:
    """Wq, Wk, Wv, Wo; three convolutions; the decay through the head
    size with its bias and a rate a head; the step; the output gate
    through the head size with its bias; the output norm."""
    d, wide = c["hidden_size"], _kda_wide(c)
    linear = c["linear_attn_config"]
    n, h = linear["num_heads"], linear["head_dim"]
    return (4 * d * wide + 3 * wide * linear["short_conv_kernel_size"]
            + (d * h + h * wide + wide + n) + d * n
            + (d * h + h * wide + wide) + h)


def mla_params(c: dict) -> int:
    """Wq at heads x (nope + rope), the latent projection, its norm,
    the decompression to heads x (nope + v), Wo."""
    d, n = c["hidden_size"], c["num_attention_heads"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    rank = c["kv_lora_rank"]
    return (d * n * (nope + rope) + d * (rank + rope) + rank
            + rank * n * (nope + v) + n * v * d)


def mixer_params(c: dict, kind: str) -> int:
    return kda_params(c) if kind == "kda" else mla_params(c)


def layer_matmul_params(c: dict, kind: str, routed: bool) -> int:
    """Parameters every token of a layer is multiplied with (the routed
    experts are counted by their rows, apart)."""
    if not routed:
        return mixer_params(c, kind) + costs_layers.dense_mlp_params(c)
    return (mixer_params(c, kind)
            + c["hidden_size"] * costs_layers.router_width(c)
            + c["num_shared_experts"] * costs_layers.expert_params(c))


def total_params(c: dict) -> int:
    """Everything this chip holds: both norms of the hidden size a
    layer, the router's bias, the held experts, the final norm,
    embedding and head."""
    d = c["hidden_size"]
    total = 2 * d * c["vocab_size"] + d
    for kind, routed in layer_kinds(c):
        total += layer_matmul_params(c, kind, routed) + 2 * d
        if routed:
            total += (costs_layers.router_width(c)
                      + c["num_experts"] * costs_layers.expert_params(c))
    return total


def kda_cost(c: dict, seq: int) -> dict:
    """One kda layer's recurrence over one sequence, token by token: a
    head's state decayed (H x H), read by k, moved by the outer product
    and read by q (2 H x H each); q, k, v read and o written once in
    bfloat16, the log-decays and the steps read once in float32."""
    linear = c["linear_attn_config"]
    n, h = linear["num_heads"], linear["head_dim"]
    return {"flops": n * seq * 7 * h * h,
            "bytes": seq * n * (h * (4 * BYTES + DECAY_BYTES) + DECAY_BYTES)}


def mla_cost(c: dict, seq: int) -> dict:
    """One mla layer's attention over one sequence: the causal pairs at
    every head, scored over nope + rope lanes and weighing v lanes; q
    and k read once at their width, v read and o written at theirs."""
    n = c["num_attention_heads"]
    score, value = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
                    c["v_head_dim"])
    return {"flops": costs_layers.visible_pairs(seq, None) * n * 2
            * (score + value),
            "bytes": BYTES * seq * n * 2 * (score + value)}


COSTS = {"kda": kda_cost, "mla": mla_cost}


def roofline_share(ctx: dict, kernel: str, kind: str):
    """A mixer kernel's share of its roofline over the forwards of the
    traced window: the least time the chip could take for the `kind`
    layers' cost at each forward's padded length over the device time
    of the operations the profile names `kernel` (None: nothing to
    read)."""
    forwards = traced_forwards.whole_forwards(ctx)
    if not forwards:
        return None
    config = ctx["job"]["config"]
    layers = [k for k, _r in layer_kinds(config)].count(kind)
    least = seconds = 0.0
    for f in forwards:
        seconds += traced_forwards.kernel_seconds(f, kernel)
        least += layers * costs.roofline_seconds(
            COSTS[kind](config, f["facts"]["padded"]), ctx["peaks"],
            ctx["chips"])["seconds"]
    return 100.0 * least / seconds if seconds else None


def forward_flops(c: dict, seq: int, rows_held: int) -> float:
    """A prefill of `seq` tokens that answers with the last position's
    logits: every layer's matrices at every token, each mixer's least
    work, the held experts' rows, the head at one position."""
    flops = 2.0 * c["hidden_size"] * c["vocab_size"]
    for kind, routed in layer_kinds(c):
        flops += 2.0 * seq * layer_matmul_params(c, kind, routed)
        flops += COSTS[kind](c, seq)["flops"]
    return flops + costs_layers.gmm_cost(c, rows_held, 0)["flops"]
