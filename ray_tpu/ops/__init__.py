"""TPU ops: fused attention kernels, sequence-parallel attention,
routed experts with their grouped matmul, chunked linear attention with
decay, block-sparse attention with a per-query choice of blocks,
windowed attention over exact keys and chunk summaries (EVA), the
gated delta rule with a decay for every key channel (KDA)."""

from ray_tpu.ops.eva_attention import eva_attention, eva_reference
from ray_tpu.ops.flash_attention import flash_attention, mha_reference
from ray_tpu.ops.kda_attention import kda_attention, kda_reference
from ray_tpu.ops.lightning_attention import (
    lightning_attention,
    lightning_reference,
)
from ray_tpu.ops.moe import gmm, make_moe_fn, routed_experts
from ray_tpu.ops.ring_attention import (
    make_attention_fn,
    ring_attention_shard,
    ulysses_attention_shard,
)
from ray_tpu.ops.sparse_attention import (
    selected_attention,
    sparse_reference,
)

__all__ = [
    "flash_attention", "mha_reference", "make_attention_fn",
    "gmm", "make_moe_fn", "routed_experts",
    "lightning_attention", "lightning_reference",
    "selected_attention", "sparse_reference",
    "eva_attention", "eva_reference",
    "kda_attention", "kda_reference",
    "ring_attention_shard", "ulysses_attention_shard",
]
