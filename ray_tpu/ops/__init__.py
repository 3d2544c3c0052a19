"""TPU ops: fused attention kernels, sequence-parallel attention,
routed experts with their grouped matmul."""

from ray_tpu.ops.flash_attention import flash_attention, mha_reference
from ray_tpu.ops.moe import gmm, make_moe_fn, routed_experts
from ray_tpu.ops.ring_attention import (
    make_attention_fn,
    ring_attention_shard,
    ulysses_attention_shard,
)

__all__ = [
    "flash_attention", "mha_reference", "make_attention_fn",
    "gmm", "make_moe_fn", "routed_experts",
    "ring_attention_shard", "ulysses_attention_shard",
]
