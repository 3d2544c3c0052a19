"""The yardstick's arithmetic: chip peaks, and the operations and bytes
that the algorithm needs, reckoned from shapes. Recomputed operations
(remat, a backward kernel's second pass over the scores) do not count.
"""

from __future__ import annotations

# Published peaks of one chip, keyed by `device_kind` as jax reports it.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 16 GB HBM2e at 819 GB/s). A kind that is not here is an error.
CHIP_PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "memory_bytes": 16e9},
}


def chip_peaks(device_kind: str) -> dict:
    if device_kind not in CHIP_PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}: add it to CHIP_PEAKS with its "
                       f"source, do not guess")
    return CHIP_PEAKS[device_kind]


def layer_params(c: dict) -> int:
    """Parameters of one decoder layer (GQA attention, SwiGLU, two
    RMSNorm scales) from a configuration's HF keys."""
    d = c["hidden_size"]
    head = d // c["num_attention_heads"]
    kv = c["num_key_value_heads"] * head
    attention = d * d + 2 * d * kv + d * d          # wq, wk, wv, wo
    mlp = 3 * d * c["intermediate_size"]            # gate, up, down
    return attention + mlp + 2 * d


def matmul_params(c: dict) -> int:
    """Parameters that every token is multiplied with: the layers'
    matrices and the output head (the embedding is a lookup)."""
    d = c["hidden_size"]
    return (c["num_hidden_layers"] * (layer_params(c) - 2 * d)
            + d * c["vocab_size"])


def total_params(c: dict) -> int:
    d = c["hidden_size"]
    return (c["num_hidden_layers"] * layer_params(c)
            + 2 * d * c["vocab_size"] + d)


def attention_flops_per_token(c: dict, seq: int) -> float:
    """Forward FLOPs a token's causal attention needs, summed over the
    layers: QK^T and PV, 2 FLOPs a multiply-add, each query seeing half
    the sequence on average."""
    return c["num_hidden_layers"] * 2 * 2 * c["hidden_size"] * seq / 2


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward and backward (twice the forward), no recomputation."""
    return 3 * (2 * matmul_params(c) + attention_flops_per_token(c, seq))


def forward_flops_per_token(c: dict) -> float:
    """A short prompt's forward pass: the matrices alone."""
    return 2.0 * matmul_params(c)


def flash_cost(c: dict, batch: int, seq: int, backward: bool) -> dict:
    """FLOPs and HBM bytes one layer's causal flash attention needs over
    `batch` sequences. Forward: two matmuls over half the score matrix;
    reads q, k, v and writes o. Backward: five matmuls (scores once, dP,
    dV, dK, dQ); reads q, k, v, o, dO and writes dq, dk, dv. k and v
    count at their KV heads. bf16 throughout."""
    d = c["hidden_size"]
    head = d // c["num_attention_heads"]
    kv = c["num_key_value_heads"] * head
    per_matmul = 2 * batch * seq * seq * d / 2
    q_bytes, kv_bytes = 2 * batch * seq * d, 2 * batch * seq * kv
    if backward:
        return {"flops": 5 * per_matmul,
                "bytes": 4 * q_bytes + 4 * kv_bytes}
    return {"flops": 2 * per_matmul, "bytes": 2 * q_bytes + 2 * kv_bytes}


def roofline_seconds(cost: dict, peaks: dict, chips: int = 1) -> dict:
    """The least time `chips` chips could take, and which bound sets it."""
    compute = cost["flops"] / (chips * peaks["flops_per_s"])
    memory = cost["bytes"] / (chips * peaks["bytes_per_s"])
    return {"seconds": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
