"""Flagship model: decoder-only transformer, TPU-first.

Pure-functional jax (no flax): params are a pytree of arrays; the
sharding layout is a parallel pytree of ``PartitionSpec``s produced by
``param_specs`` so the same code runs dp/fsdp/tp/sp layouts by changing
only the mesh. Design notes:

- compute in bfloat16, params/optimizer in float32 (MXU-friendly);
- static shapes everywhere; no data-dependent Python control flow;
- per-block rematerialisation via ``jax.checkpoint`` (HBM for FLOPs);
- GQA (grouped KV heads), RoPE, RMSNorm, SwiGLU — the contemporary
  decoder block;
- attention runs through ``ray_tpu.ops.attention`` which dispatches to
  the ring-attention path when the mesh has a nontrivial ``sp`` axis.

The reference (royf/ray) contains no model code of its own — models
enter via torch inside Ray Train/Serve/RLlib workers [SURVEY.md §2.5];
this module is the TPU-native equivalent of that role: the model the
framework's train/tune/serve/bench layers exercise.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32_000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 4          # GQA: kv heads <= heads
    d_ff: int = 1408             # SwiGLU hidden
    max_seq_len: int = 2048
    rope_theta: float = 10_000.0
    dtype: Any = jnp.bfloat16    # compute dtype
    remat: bool = True
    # Pallas flash attention (ops/flash_attention.py): fused blockwise
    # kernel, no S×S in HBM — the TPU path both benchmark cells take
    # (what it costs a step and how far it stands from its roofline:
    # PERF.md §5). Off by default: CPU tests run the interpret path,
    # which is slower than dense XLA.
    use_flash: bool = False
    use_moe: bool = False
    n_experts: int = 8
    expert_top_k: int = 2

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


# --------------------------------------------------------------------------
# Parameters
# --------------------------------------------------------------------------

def _dense_init(key, shape, in_axis=0):
    fan_in = shape[in_axis] if isinstance(in_axis, int) else \
        int(np.prod([shape[a] for a in in_axis]))
    return jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)


def init_params(key: jax.Array, cfg: TransformerConfig) -> Dict:
    keys = jax.random.split(key, cfg.n_layers + 2)
    hd = cfg.head_dim
    params: Dict[str, Any] = {
        "embed": jax.random.normal(keys[0],
                                   (cfg.vocab_size, cfg.d_model),
                                   jnp.float32) * 0.02,
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
        "blocks": [],
    }
    for i in range(cfg.n_layers):
        bk = jax.random.split(keys[i + 1], 8)
        block = {
            "attn_norm": jnp.ones((cfg.d_model,), jnp.float32),
            "wq": _dense_init(bk[0], (cfg.d_model, cfg.n_heads, hd)),
            "wk": _dense_init(bk[1], (cfg.d_model, cfg.n_kv_heads, hd)),
            "wv": _dense_init(bk[2], (cfg.d_model, cfg.n_kv_heads, hd)),
            "wo": _dense_init(bk[3], (cfg.n_heads, hd, cfg.d_model),
                              in_axis=(0, 1)),
            "mlp_norm": jnp.ones((cfg.d_model,), jnp.float32),
        }
        if cfg.use_moe:
            ek = jax.random.split(bk[4], 4)
            block["router"] = _dense_init(ek[0], (cfg.d_model, cfg.n_experts))
            block["wi"] = _dense_init(
                ek[1], (cfg.n_experts, cfg.d_model, cfg.d_ff), in_axis=1)
            block["wg"] = _dense_init(
                ek[2], (cfg.n_experts, cfg.d_model, cfg.d_ff), in_axis=1)
            block["wo_mlp"] = _dense_init(
                ek[3], (cfg.n_experts, cfg.d_ff, cfg.d_model), in_axis=1)
        else:
            block["wi"] = _dense_init(bk[4], (cfg.d_model, cfg.d_ff))
            block["wg"] = _dense_init(bk[5], (cfg.d_model, cfg.d_ff))
            block["wo_mlp"] = _dense_init(bk[6], (cfg.d_ff, cfg.d_model))
        params["blocks"].append(block)
    params["unembed"] = _dense_init(keys[-1], (cfg.d_model, cfg.vocab_size))
    return params


def param_specs(cfg: TransformerConfig) -> Dict:
    """PartitionSpec tree matching init_params.

    Layout: megatron-style tp on head/ff dims, fsdp on the d_model dim
    (ZeRO-3); norms replicated. MoE experts shard over ep=(tp) combined
    with per-expert ff sharding kept replicated for simplicity v1.
    """
    block: Dict[str, Any] = {
        "attn_norm": P(None),
        "wq": P("fsdp", "tp", None),
        "wk": P("fsdp", "tp", None),
        "wv": P("fsdp", "tp", None),
        "wo": P("tp", None, "fsdp"),
        "mlp_norm": P(None),
    }
    if cfg.use_moe:
        block.update({
            "router": P("fsdp", None),
            "wi": P("tp", "fsdp", None),
            "wg": P("tp", "fsdp", None),
            "wo_mlp": P("tp", None, "fsdp"),
        })
    else:
        block.update({
            "wi": P("fsdp", "tp"),
            "wg": P("fsdp", "tp"),
            "wo_mlp": P("tp", "fsdp"),
        })
    return {
        "embed": P("tp", "fsdp"),
        "final_norm": P(None),
        "blocks": [dict(block) for _ in range(cfg.n_layers)],
        "unembed": P("fsdp", "tp"),
    }


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------

def rms_norm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, S, N, Hd]; positions: [B, S]."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,Hd/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _attention(q, k, v, *, causal: bool = True):
    """Plain blockless attention — the sp=1 path. [B,S,N,Hd] layout.
    Ring attention (sp>1) is dispatched above this, in ops.attention."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bqnh,bknh->bnqk", q, k) * scale
    if causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q)
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bnqk,bknh->bqnh", probs.astype(v.dtype), v)


def _block_forward(block, x, positions, cfg: TransformerConfig,
                   attn_fn=None):
    dt = cfg.dtype
    h = rms_norm(x, block["attn_norm"])
    q = jnp.einsum("bsd,dnh->bsnh", h, block["wq"].astype(dt))
    k = jnp.einsum("bsd,dnh->bsnh", h, block["wk"].astype(dt))
    v = jnp.einsum("bsd,dnh->bsnh", h, block["wv"].astype(dt))
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    # GQA: repeat kv heads up to n_heads.
    rep = cfg.n_heads // cfg.n_kv_heads
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    attn = (attn_fn or _attention)(q, k, v)
    x = x + jnp.einsum("bsnh,nhd->bsd", attn, block["wo"].astype(dt))

    h = rms_norm(x, block["mlp_norm"])
    if "router" in block:
        x = x + _moe_mlp(block, h, cfg)
    else:
        gate = jax.nn.silu(h @ block["wg"].astype(dt))
        up = h @ block["wi"].astype(dt)
        x = x + (gate * up) @ block["wo_mlp"].astype(dt)
    return x


def _moe_mlp(block, h, cfg: TransformerConfig):
    """Dense-einsum MoE (every expert sees every token, masked by the
    router weights): compiler-friendly v1; the ragged all-to-all
    dispatch kernel replaces this under ep>1."""
    dt = cfg.dtype
    logits = h @ block["router"].astype(dt)                 # [B,S,E]
    weights = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_w, top_i = jax.lax.top_k(weights, cfg.expert_top_k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    mask = jax.nn.one_hot(top_i, cfg.n_experts, dtype=jnp.float32)
    combine = jnp.einsum("bsk,bske->bse", top_w, mask).astype(dt)
    gate = jax.nn.silu(jnp.einsum("bsd,edf->bsef", h, block["wg"].astype(dt)))
    up = jnp.einsum("bsd,edf->bsef", h, block["wi"].astype(dt))
    out = jnp.einsum("bsef,efd->bsed", gate * up, block["wo_mlp"].astype(dt))
    return jnp.einsum("bsed,bse->bsd", out, combine)


def forward(params, tokens: jax.Array, cfg: TransformerConfig,
            positions: Optional[jax.Array] = None,
            attn_fn=None) -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, V]."""
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :],
            tokens.shape)
    x = params["embed"].astype(cfg.dtype)[tokens]
    if attn_fn is None and cfg.use_flash:
        from ray_tpu.ops.flash_attention import flash_attention
        attn_fn = lambda q, k, v, causal=True: flash_attention(  # noqa: E731
            q, k, v, causal=causal)
    blk = functools.partial(_block_forward, cfg=cfg, attn_fn=attn_fn)
    if cfg.remat:
        blk = jax.checkpoint(blk, static_argnums=())
    for block in params["blocks"]:
        x = blk(block, x, positions)
    x = rms_norm(x, params["final_norm"])
    return (x @ params["unembed"].astype(cfg.dtype)).astype(jnp.float32)


def loss_fn(params, batch: Dict[str, jax.Array],
            cfg: TransformerConfig, attn_fn=None) -> jax.Array:
    """Next-token cross-entropy. batch: tokens [B,S]; optional
    loss_mask [B,S]. The forward runs on the full S (keeps the seq dim
    divisible by the sp axis for ring attention); the shift to next-
    token targets happens on the logits."""
    tokens = batch["tokens"]
    logits = forward(params, tokens, cfg, attn_fn=attn_fn)[:, :-1]
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    if mask is not None:
        mask = mask[:, 1:].astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
