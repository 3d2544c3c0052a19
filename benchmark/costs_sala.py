"""Operations and bytes of a `minicpm_sala` configuration's two
mixers and of one forward, from shapes: **the least work the
mathematics asks, whatever implements it**. A sparse kernel that
fetches the union of several queries' blocks is credited with the
chosen pairs only; a lightning kernel that works in chunks is credited
with the recurrence. A share over 100 % is a fault of the count.
"""

from __future__ import annotations

import numpy as np

from benchmark import costs, traced_forwards

BYTES = 2       # bfloat16, the type the configuration states

def layer_kinds(c: dict) -> list:
    """"sparse" or "lightning" for each layer."""
    return [{"minicpm4": "sparse", "lightning-attn": "lightning"}[kind]
            for kind in c["mixer_types"]]


def layer_matmul_params(c: dict, kind: str) -> int:
    """q, gate and o at heads x head_dim, k and v at the kind's KV
    heads, the SwiGLU's three matrices."""
    d, h = c["hidden_size"], c["head_dim"]
    kv = c["lightning_nkv"] if kind == "lightning" else \
        c["num_key_value_heads"]
    return (d * h * (3 * c["num_attention_heads"] + 2 * kv)
            + 3 * d * c["intermediate_size"])


def total_params(c: dict) -> int:
    """Everything this chip holds, norms included: two norms of the
    hidden size and two of the head size a layer, a lightning layer's
    output norm, the final norm, embedding and head."""
    d, h = c["hidden_size"], c["head_dim"]
    total = 2 * d * c["vocab_size"] + d
    for kind in layer_kinds(c):
        total += layer_matmul_params(c, kind) + 2 * d + 2 * h
        if kind == "lightning":
            total += c["num_attention_heads"] * h
    return total


def selected_pairs(c: dict, seq: int) -> int:
    """(query, key) pairs of one head over one sequence: every key up
    to the query while it has no more than `topk` blocks before it (and
    at any length up to `dense_len`), then `topk - 1` whole blocks and
    its own up to itself."""
    s = c["sparse_config"]
    if seq <= s["dense_len"]:
        return seq * (seq + 1) // 2
    block, top = s["block_size"], s["topk"]
    t = np.arange(seq, dtype=np.int64)
    return int(np.where(t // block < top, t + 1,
                        (top - 1) * block + t % block + 1).sum())


def visible_compressed(c: dict, seq: int) -> int:
    """(query, compressed key) pairs the choice scores over one
    sequence: none up to `dense_len`."""
    s = c["sparse_config"]
    if seq <= s["dense_len"]:
        return 0
    t = np.arange(seq, dtype=np.int64)
    return int(np.maximum(
        0, (t - s["kernel_size"] + 1) // s["kernel_stride"] + 1).sum())


def sparse_attn_cost(c: dict, seq: int) -> dict:
    """One sparse layer's attention over one sequence: two matmuls over
    the selected pairs at every query head; q read and o written once
    at the query heads, k and v read once at the KV heads."""
    h, n = c["head_dim"], c["num_attention_heads"]
    return {"flops": 2 * 2 * h * n * selected_pairs(c, seq),
            "bytes": BYTES * seq * h * (2 * n + 2 * c["num_key_value_heads"])}


def lightning_cost(c: dict, seq: int) -> dict:
    """One lightning layer's mixer over one sequence in its recurrent
    form: the state's update and its read, 2 x 2 x H x H a token and
    head; q, k, v read and o written once."""
    h, n = c["lightning_head_dim"], c["lightning_nh"]
    return {"flops": n * seq * 4 * h * h, "bytes": BYTES * 4 * seq * n * h}


def roofline_share(ctx: dict, kernel: str, kind: str, cost) -> float:
    """A mixer kernel's share of its roofline over the forwards of the
    traced window: the least time the chip could take for `cost(config,
    padded length)` in every layer of `kind` over the device time of
    the operations the program names `kernel` (None: nothing to read)."""
    forwards = traced_forwards.whole_forwards(ctx)
    if not forwards:
        return None
    config = ctx["job"]["config"]
    layers = layer_kinds(config).count(kind)
    least = seconds = 0.0
    for f in forwards:
        seconds += traced_forwards.kernel_seconds(f, kernel)
        least += layers * costs.roofline_seconds(
            cost(config, f["facts"]["padded"]), ctx["peaks"],
            ctx["chips"])["seconds"]
    return 100.0 * least / seconds if seconds else None


def forward_flops(c: dict, seq: int) -> float:
    """A prefill of `seq` tokens that answers with the last position's
    logits: every layer's matrices at every token, each mixer's least
    work, the choice's scores over the visible compressed keys, the
    head at one position."""
    h, n = c["head_dim"], c["num_attention_heads"]
    flops = 2.0 * c["hidden_size"] * c["vocab_size"]
    for kind in layer_kinds(c):
        flops += 2.0 * seq * layer_matmul_params(c, kind)
        if kind == "lightning":
            flops += lightning_cost(c, seq)["flops"]
        else:
            flops += sparse_attn_cost(c, seq)["flops"]
            flops += 2.0 * h * n * visible_compressed(c, seq)
    return flops
