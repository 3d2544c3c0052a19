"""Operations and bytes from a configuration's **layer pattern**
(`layer_types`, `num_dense_layers`, experts held of those published):
what `costs.py` reckons for one dense block repeated, here for window
and full attention, dense and routed layers side by side, and **for
this chip's share only**: the visible attention pairs, the rows the
held experts were given as counted, the weights of held experts that
had a row. Count what the chip does, never more: a share over 100 % is
a fault of the count.
"""

from __future__ import annotations

BYTES = 2       # bfloat16, the type the configuration states


def layer_kinds(c: dict) -> list:
    """[(window or None, routed)] for each layer."""
    return [(c["sliding_window"] if kind == "sliding_attention" else None,
             i >= c["num_dense_layers"])
            for i, kind in enumerate(c["layer_types"])]


def visible_pairs(seq: int, window) -> int:
    """(query, key) pairs causal attention computes over one sequence:
    query i sees keys i-window+1 .. i, all of 0 .. i where no window or
    a window the sequence does not outgrow."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_params(c: dict) -> int:
    """q, gate and o at heads x head_dim, k and v at the KV heads."""
    d, h = c["hidden_size"], c["head_dim"]
    wide, kv = c["num_attention_heads"] * h, c["num_key_value_heads"] * h
    return 3 * d * wide + 2 * d * kv


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def dense_mlp_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def router_width(c: dict) -> int:
    return c["num_experts"] * c.get("expert_parallel", {"size": 1})["size"]


def layer_matmul_params(c: dict, routed: bool) -> int:
    """Parameters every token of a layer is multiplied with (the routed
    experts are counted by their rows, apart)."""
    if not routed:
        return attention_params(c) + dense_mlp_params(c)
    return (attention_params(c) + c["hidden_size"] * router_width(c)
            + c["num_shared_experts"] * expert_params(c))


def total_params(c: dict) -> int:
    """Everything this chip holds, norms and bias included."""
    d, h = c["hidden_size"], c["head_dim"]
    total = 2 * d * c["vocab_size"] + d
    for _window, routed in layer_kinds(c):
        total += layer_matmul_params(c, routed) + 4 * d + 2 * h
        if routed:
            total += router_width(c) + c["num_experts"] * expert_params(c)
    return total


def flash_cost(c: dict, seq: int, window) -> dict:
    """One layer's flash forward over one sequence: two matmuls over the
    visible pairs at every query head; reads q, k, v and writes o, k
    and v at their KV heads."""
    h = c["head_dim"]
    wide, kv = c["num_attention_heads"] * h, c["num_key_value_heads"] * h
    return {"flops": 2 * 2 * visible_pairs(seq, window) * wide,
            "bytes": BYTES * seq * (2 * wide + 2 * kv)}


def flash_cost_of_forward(c: dict, seq: int) -> list:
    """The cost of each layer's flash call in one forward."""
    return [flash_cost(c, seq, window) for window, _r in layer_kinds(c)]


def gmm_cost(c: dict, rows_held: int, experts_hit: int) -> dict:
    """The routed experts of one forward, all routed layers together:
    `rows_held` (token, expert) rows through gate, up and down of their
    expert; the three matrices of each held expert that had a row
    streamed once; each row read once and its result written once."""
    d = c["hidden_size"]
    return {"flops": 2 * rows_held * expert_params(c),
            "bytes": BYTES * (experts_hit * expert_params(c)
                              + 2 * rows_held * d)}


def forward_flops(c: dict, seq: int, rows_held: int) -> float:
    """A prefill of `seq` tokens that answers with the last position's
    logits: every layer's matrices at every token, the attention's
    visible pairs, the held experts' rows, the head at one position."""
    flops = 2.0 * c["hidden_size"] * c["vocab_size"]
    for window, routed in layer_kinds(c):
        flops += 2.0 * seq * layer_matmul_params(c, routed)
        flops += flash_cost(c, seq, window)["flops"]
    return flops + gmm_cost(c, rows_held, 0)["flops"]
