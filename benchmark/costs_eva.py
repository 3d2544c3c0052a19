"""Operations and bytes of an `evabyte` configuration's attention and
of one forward, from shapes: **the least work the mathematics asks,
whatever implements it**. A kernel that computes a whole tile across
the diagonal, or a tile of summaries that only some of its queries may
see, is credited with the pairs the equations name. A share over 100 %
is a fault of the count.
"""

from __future__ import annotations

import numpy as np

from benchmark import costs, traced_forwards

BYTES = 2       # bfloat16, the type the configuration states
ATTN, SUMMARIES = "eva_attn", "eva_summaries"   # the program's launches


def head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def layer_matmul_params(c: dict) -> int:
    """q, k, v and o at heads x head size, the SwiGLU's three matrices."""
    d = c["hidden_size"]
    return (4 * d * c["num_attention_heads"] * head_dim(c)
            + 3 * d * c["intermediate_size"])


def total_params(c: dict) -> int:
    """Everything this chip holds: a layer's matrices, its two norms
    and its two learned vectors a head; embedding, the head's
    `num_pred_heads` vocabularies and the final norm."""
    d, v = c["hidden_size"], c["vocab_size"]
    layer = (layer_matmul_params(c) + 2 * d
             + 2 * c["num_attention_heads"] * head_dim(c))
    return (c["num_hidden_layers"] * layer + d * v
            + d * v * c["num_pred_heads"] + d)


def pairs(c: dict, seq: int) -> dict:
    """Of one head over one sequence: `local`, the (query, key) pairs
    inside windows (a query and the keys up to it in its window);
    `far`, the (query, summary) pairs (a query and every chunk of the
    windows before its own)."""
    w, chunk = c["window_size"], c["chunk_size"]
    t = np.arange(seq, dtype=np.int64)
    return {"local": int((t % w + 1).sum()),
            "far": int((t // w * (w // chunk)).sum())}


def summarised(c: dict, seq: int) -> int:
    """Positions that some query of the sequence sees summarised: the
    windows before the last position's."""
    w = c["window_size"]
    return (seq - 1) // w * w if seq else 0


def eva_attn_cost(c: dict, seq: int) -> dict:
    """One layer's attention over one sequence: two matmuls over the
    local and the far pairs at every head; q, k and v read, o written
    and both summaries read once."""
    h, n = head_dim(c), c["num_attention_heads"]
    p = pairs(c, seq)
    return {"flops": 2 * 2 * h * n * (p["local"] + p["far"]),
            "bytes": BYTES * n * h * (4 * seq + 2 * (seq // c["chunk_size"]))}


def pooling_flops(c: dict, seq: int) -> int:
    """One layer's summaries: a position's score against `phi`, its
    weighted value and its share of the mean key, 2 H each, at every
    head and every position that is seen summarised."""
    return 3 * 2 * head_dim(c) * c["num_attention_heads"] * summarised(c, seq)


def forward_flops(c: dict, seq: int) -> float:
    """A prefill of `seq` bytes that answers with the last position's
    logits: every layer's matrices at every byte, the summaries'
    pooling, the local and far pairs, the head at one position."""
    layer = (2.0 * seq * layer_matmul_params(c) + pooling_flops(c, seq)
             + eva_attn_cost(c, seq)["flops"])
    return (c["num_hidden_layers"] * layer + 2.0 * c["hidden_size"]
            * c["vocab_size"] * c["num_pred_heads"])


def roofline_share(ctx: dict, kernel: str, cost) -> float:
    """A kernel's share of its roofline over the forwards of the traced
    window: the least time the chip could take for `cost(config, padded
    length)` in every layer over the device time of the operations the
    program names `kernel` (None: nothing to read)."""
    forwards = traced_forwards.whole_forwards(ctx)
    if not forwards:
        return None
    config = ctx["job"]["config"]
    least = seconds = 0.0
    for f in forwards:
        seconds += traced_forwards.kernel_seconds(f, kernel)
        least += config["num_hidden_layers"] * costs.roofline_seconds(
            cost(config, f["facts"]["padded"]), ctx["peaks"],
            ctx["chips"])["seconds"]
    return 100.0 * least / seconds if seconds else None
