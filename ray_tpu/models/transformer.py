"""Flagship model: decoder-only transformer, TPU-first.

Pure-functional jax (no flax): params are a pytree of arrays; the
sharding layout is a parallel pytree of ``PartitionSpec``s produced by
``param_specs`` so the same code runs dp/fsdp/tp/sp layouts by changing
only the mesh. Design notes:

- compute in bfloat16, params/optimizer in float32 (MXU-friendly); a
  serving replica may hold the params in bfloat16 as published;
- static shapes everywhere; no data-dependent Python control flow;
- per-block rematerialisation via ``jax.checkpoint`` (HBM for FLOPs);
- one ``forward`` over a **layer pattern** (``TransformerConfig.layers``,
  one ``LayerSpec`` a layer): each layer's attention sees every earlier
  key or a sliding window of them, rotates its queries and keys (RoPE)
  or encodes no position, and feeds a dense SwiGLU or routed experts
  beside shared ones (``ray_tpu.ops.moe``: this chip's experts' part,
  no token dropped). The default pattern is "full causal, RoPE, dense"
  repeated: GQA, RoPE, RMSNorm, SwiGLU, the contemporary dense block;
- model-wide switches for what some families add to every layer:
  RMSNorm on queries and keys by head, a sigmoid gate on the attention
  output, norms after attention and MLP as well as before (sandwich),
  an embedding scale; ``head_dim`` and ``rms_norm_eps`` are fields;
- ``config_from_hf`` reads a published ``config.json``'s keys (the
  ``mistral`` and ``afmoe`` families) into a ``TransformerConfig``;
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
class LayerSpec:
    """One layer of the pattern."""
    window: Optional[int] = None    # keys a query sees; None: all before it
    rope: bool = True               # False: no position encoding (NoPE)
    experts: bool = False           # routed + shared experts, else dense MLP


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
    # kernel, no S×S in HBM — the TPU path the benchmark's cells take
    # (what it costs a step and how far it stands from its roofline:
    # PERF.md §5). Off by default: CPU tests run the interpret path,
    # which is slower than dense XLA.
    use_flash: bool = False
    head_dim: Optional[int] = None      # None: d_model // n_heads
    rms_norm_eps: float = 1e-6
    # None: n_layers x LayerSpec() (full causal, RoPE, dense)
    layers: Optional[Tuple[LayerSpec, ...]] = None
    qk_norm: bool = False        # RMSNorm on q and k, one scale a head dim
    attn_gate: bool = False      # attention output * sigmoid(h @ wgate)
    sandwich_norm: bool = False  # norms after attention and MLP too
    embed_scale: float = 1.0     # the embedding's multiplier (muP)
    # Routed experts, for the layers whose spec asks for them. The
    # router is ``n_experts`` wide (the published count) whatever is
    # held here: ``experts_held = (first, count)``.
    n_experts: int = 0
    experts_held: Tuple[int, int] = (0, 0)
    expert_top_k: int = 0
    d_ff_expert: int = 0
    n_shared_experts: int = 0
    route_scale: float = 1.0

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim",
                               self.d_model // self.n_heads)
        if self.layers is None:
            object.__setattr__(self, "layers",
                               (LayerSpec(),) * self.n_layers)
        if len(self.layers) != self.n_layers:
            raise ValueError(f"{len(self.layers)} layer specs for "
                             f"{self.n_layers} layers")
        if any(spec.experts for spec in self.layers):
            first, count = self.experts_held
            if not (0 < self.expert_top_k <= self.n_experts
                    and count > 0 and first + count <= self.n_experts
                    and self.d_ff_expert > 0):
                raise ValueError(
                    f"expert layers need n_experts, expert_top_k, "
                    f"d_ff_expert and experts_held inside them, got "
                    f"{self.n_experts}, {self.expert_top_k}, "
                    f"{self.d_ff_expert}, {self.experts_held}")


def config_from_hf(config: dict, max_seq_len: int) -> TransformerConfig:
    """A published ``config.json``'s keys as the program names them:
    the ``mistral`` family (one dense block repeated) and the ``afmoe``
    family (``layer_types`` of window and global layers, the global
    ones without RoPE; leading dense layers, then routed experts beside
    shared ones; sandwich norms, q/k norms, gated attention, muP
    embedding scale). A window that no sequence of ``max_seq_len``
    outgrows is causal attention and is dropped. ``expert_parallel``
    ``{"size", "rank"}``, where given, says that ``num_experts`` counts
    the experts held here, the ``rank``-th of ``size`` equal shares of
    the router's width."""
    family = config["model_type"]
    n_layers = config["num_hidden_layers"]
    window = config.get("sliding_window")
    if window is not None and window >= max_seq_len:
        window = None
    common = dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=n_layers, n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], max_seq_len=max_seq_len,
        rope_theta=float(config["rope_theta"]),
        dtype=jnp.dtype(config.get("torch_dtype", "bfloat16")),
        head_dim=config.get("head_dim"),
        rms_norm_eps=float(config["rms_norm_eps"]))
    if family == "mistral":
        return TransformerConfig(
            **common, layers=(LayerSpec(window=window),) * n_layers)
    if family != "afmoe":
        raise ValueError(f"config_from_hf knows the model types 'mistral' "
                         f"and 'afmoe', not {family!r}")
    share = config.get("expert_parallel", {"size": 1, "rank": 0})
    held = config["num_experts"]
    layers = tuple(
        LayerSpec(window=window if kind == "sliding_attention" else None,
                  rope=kind == "sliding_attention",
                  experts=i >= config["num_dense_layers"])
        for i, kind in enumerate(config["layer_types"]))
    return TransformerConfig(
        **common, layers=layers, qk_norm=True, attn_gate=True,
        sandwich_norm=True,
        embed_scale=float(np.sqrt(config["hidden_size"]))
        if config.get("mup_enabled") else 1.0,
        n_experts=held * share["size"],
        experts_held=(held * share["rank"], held),
        expert_top_k=config["num_experts_per_tok"],
        d_ff_expert=config["moe_intermediate_size"],
        n_shared_experts=config.get("num_shared_experts", 0),
        route_scale=float(config.get("route_scale", 1.0)))


# --------------------------------------------------------------------------
# Parameters
# --------------------------------------------------------------------------

def _dense_init(key, shape, in_axis=0):
    fan_in = shape[in_axis] if isinstance(in_axis, int) else \
        int(np.prod([shape[a] for a in in_axis]))
    return jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)


def init_params(key: jax.Array, cfg: TransformerConfig) -> Dict:
    keys = jax.random.split(key, cfg.n_layers + 2)
    d, hd = cfg.d_model, cfg.head_dim
    ones = lambda n: jnp.ones((n,), jnp.float32)            # noqa: E731
    params: Dict[str, Any] = {
        "embed": jax.random.normal(keys[0], (cfg.vocab_size, d),
                                   jnp.float32) * 0.02,
        "final_norm": ones(d),
        "blocks": [],
    }
    for i, spec in enumerate(cfg.layers):
        bk = jax.random.split(keys[i + 1], 8)
        block = {
            "attn_norm": ones(d),
            "wq": _dense_init(bk[0], (d, cfg.n_heads, hd)),
            "wk": _dense_init(bk[1], (d, cfg.n_kv_heads, hd)),
            "wv": _dense_init(bk[2], (d, cfg.n_kv_heads, hd)),
            "wo": _dense_init(bk[3], (cfg.n_heads, hd, d), in_axis=(0, 1)),
            "mlp_norm": ones(d),
        }
        if cfg.qk_norm:
            block.update(q_norm=ones(hd), k_norm=ones(hd))
        if cfg.attn_gate:
            block["wgate"] = _dense_init(bk[7], (d, cfg.n_heads, hd))
        if cfg.sandwich_norm:
            block.update(post_attn_norm=ones(d), post_mlp_norm=ones(d))
        if spec.experts:
            ek = jax.random.split(bk[4], 8)
            f, held = cfg.d_ff_expert, cfg.experts_held[1]
            block["router"] = _dense_init(ek[0], (d, cfg.n_experts))
            block["router_bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
            block["experts_wi"] = _dense_init(ek[1], (held, d, f), in_axis=1)
            block["experts_wg"] = _dense_init(ek[2], (held, d, f), in_axis=1)
            block["experts_wo"] = _dense_init(ek[3], (held, f, d), in_axis=1)
            if cfg.n_shared_experts:
                fs = cfg.n_shared_experts * f
                block["shared_wi"] = _dense_init(ek[4], (d, fs))
                block["shared_wg"] = _dense_init(ek[5], (d, fs))
                block["shared_wo"] = _dense_init(ek[6], (fs, d))
        else:
            block["wi"] = _dense_init(bk[4], (d, cfg.d_ff))
            block["wg"] = _dense_init(bk[5], (d, cfg.d_ff))
            block["wo_mlp"] = _dense_init(bk[6], (cfg.d_ff, d))
        params["blocks"].append(block)
    params["unembed"] = _dense_init(keys[-1], (d, cfg.vocab_size))
    return params


def param_specs(cfg: TransformerConfig) -> Dict:
    """PartitionSpec tree matching init_params, layer by layer of the
    pattern.

    Layout: megatron-style tp on head/ff dims, fsdp on the d_model dim
    (ZeRO-3); norms replicated. A routed layer's experts shard their
    leading dim over tp (``ops.moe.make_moe_fn`` sums the shares'
    parts over that axis); its router and shared experts lie as a dense
    layer's matrices do.
    """
    def block_specs(spec: LayerSpec) -> Dict[str, Any]:
        block: Dict[str, Any] = {
            "attn_norm": P(None),
            "wq": P("fsdp", "tp", None),
            "wk": P("fsdp", "tp", None),
            "wv": P("fsdp", "tp", None),
            "wo": P("tp", None, "fsdp"),
            "mlp_norm": P(None),
        }
        if cfg.qk_norm:
            block.update(q_norm=P(None), k_norm=P(None))
        if cfg.attn_gate:
            block["wgate"] = P("fsdp", "tp", None)
        if cfg.sandwich_norm:
            block.update(post_attn_norm=P(None), post_mlp_norm=P(None))
        if spec.experts:
            block.update({
                "router": P("fsdp", None),
                "router_bias": P(None),
                "experts_wi": P("tp", "fsdp", None),
                "experts_wg": P("tp", "fsdp", None),
                "experts_wo": P("tp", None, "fsdp"),
            })
            if cfg.n_shared_experts:
                block.update({
                    "shared_wi": P("fsdp", "tp"),
                    "shared_wg": P("fsdp", "tp"),
                    "shared_wo": P("tp", "fsdp"),
                })
        else:
            block.update({
                "wi": P("fsdp", "tp"),
                "wg": P("fsdp", "tp"),
                "wo_mlp": P("tp", "fsdp"),
            })
        return block

    return {
        "embed": P("tp", "fsdp"),
        "final_norm": P(None),
        "blocks": [block_specs(spec) for spec in cfg.layers],
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


def _attention(q, k, v, *, causal: bool = True,
               window: Optional[int] = None):
    """Plain blockless attention — the sp=1 path. [B,S,N,Hd] layout.
    Ring attention (sp>1) is dispatched above this, in ops.attention."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bqnh,bknh->bnqk", q, k) * scale
    if causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((s_q, s_k), bool),
                              k=s_k - s_q - window)
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bnqk,bknh->bqnh", probs.astype(v.dtype), v)


def _swiglu(h, wg, wi, wo, dt):
    gate = jax.nn.silu(h @ wg.astype(dt))
    up = h @ wi.astype(dt)
    return (gate * up) @ wo.astype(dt)


def _experts_mlp(block, h, cfg: TransformerConfig):
    """The shared experts (every chip computes them alike) plus this
    chip's experts' part of the routed result. -> (f, rows [held])."""
    from ray_tpu.ops.moe import routed_experts
    b, s, d = h.shape
    routed, rows = routed_experts(
        h.reshape(b * s, d), block["router"], block["router_bias"],
        block["experts_wg"], block["experts_wi"], block["experts_wo"],
        held=cfg.experts_held, top_k=cfg.expert_top_k,
        route_scale=cfg.route_scale)
    routed = routed.reshape(b, s, d)
    if cfg.n_shared_experts:
        routed = routed + _swiglu(h, block["shared_wg"], block["shared_wi"],
                                  block["shared_wo"], cfg.dtype)
    return routed, rows


def _block_forward(block, x, positions, cfg: TransformerConfig,
                   attn_fn=None):
    """The dense block (full causal attention, RoPE, SwiGLU) that the
    ViT and the pipeline stages repeat. -> x."""
    return _layer_forward(block, x, positions, LayerSpec(), cfg,
                          attn_fn or _attention)[0]


def _layer_forward(block, x, positions, spec: LayerSpec,
                   cfg: TransformerConfig, attn_fn):
    """One layer of the pattern. -> (x, the rows each held expert was
    given, or None)."""
    dt, eps = cfg.dtype, cfg.rms_norm_eps
    h = rms_norm(x, block["attn_norm"], eps)
    q = jnp.einsum("bsd,dnh->bsnh", h, block["wq"].astype(dt))
    k = jnp.einsum("bsd,dnh->bsnh", h, block["wk"].astype(dt))
    v = jnp.einsum("bsd,dnh->bsnh", h, block["wv"].astype(dt))
    if cfg.qk_norm:
        q = rms_norm(q, block["q_norm"], eps)
        k = rms_norm(k, block["k_norm"], eps)
    if spec.rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    # GQA: repeat kv heads up to n_heads.
    rep = cfg.n_heads // cfg.n_kv_heads
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    attn = attn_fn(q, k, v) if spec.window is None else \
        attn_fn(q, k, v, window=spec.window)
    if cfg.attn_gate:
        gate = jnp.einsum("bsd,dnh->bsnh", h, block["wgate"].astype(dt))
        attn = attn * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(dt)
    attn = jnp.einsum("bsnh,nhd->bsd", attn, block["wo"].astype(dt))
    if cfg.sandwich_norm:
        attn = rms_norm(attn, block["post_attn_norm"], eps)
    x = x + attn

    h = rms_norm(x, block["mlp_norm"], eps)
    rows = None
    if spec.experts:
        f, rows = _experts_mlp(block, h, cfg)
    else:
        f = _swiglu(h, block["wg"], block["wi"], block["wo_mlp"], dt)
    if cfg.sandwich_norm:
        f = rms_norm(f, block["post_mlp_norm"], eps)
    return x + f, rows


def forward_with_stats(params, tokens: jax.Array, cfg: TransformerConfig,
                       positions: Optional[jax.Array] = None,
                       attn_fn=None,
                       logit_positions: Optional[jax.Array] = None):
    """tokens [B, S] int32 -> (logits, stats). Logits are [B, S, V],
    or [B, V] at ``logit_positions [B]`` where given (a prefill needs
    the last position's alone). ``stats["moe_rows"]`` [routed layers,
    held experts] int32: the rows each held expert was given, which
    ``ops.moe.record_route`` turns into the ``model.moe.route`` record
    once they are on the host with the logits. An ``attn_fn`` given
    from outside is called ``attn_fn(q, k, v)``, with ``window=`` on a
    layer that has one."""
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :],
            tokens.shape)
    x = params["embed"].astype(cfg.dtype)[tokens]
    if cfg.embed_scale != 1.0:
        x = x * jnp.asarray(cfg.embed_scale, cfg.dtype)
    if attn_fn is None:
        if cfg.use_flash:
            from ray_tpu.ops.flash_attention import flash_attention
            attn_fn = lambda q, k, v, window=None: flash_attention(  # noqa: E731
                q, k, v, causal=True, window=window)
        else:
            attn_fn = _attention
    # One function a kind of layer, shared by the layers of that kind:
    # jax traces a checkpointed function once for all the layers that
    # call it (a function made anew for each layer is traced anew, and
    # a twelve-layer program then takes four times as long to set up).
    layer_fns, moe_rows = {}, []
    for block, spec in zip(params["blocks"], cfg.layers):
        blk = layer_fns.get(spec)
        if blk is None:
            blk = functools.partial(_layer_forward, spec=spec, cfg=cfg,
                                    attn_fn=attn_fn)
            if cfg.remat:
                blk = jax.checkpoint(blk, static_argnums=())
            layer_fns[spec] = blk
        x, rows = blk(block, x, positions)
        if rows is not None:
            moe_rows.append(rows)
    if logit_positions is not None:
        x = jnp.take_along_axis(x, logit_positions[:, None, None],
                                axis=1)[:, 0]
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = (x @ params["unembed"].astype(cfg.dtype)).astype(jnp.float32)
    held = cfg.experts_held[1]
    stats = {"moe_rows": jnp.stack(moe_rows) if moe_rows
             else jnp.zeros((0, held), jnp.int32)}
    return logits, stats


def forward(params, tokens: jax.Array, cfg: TransformerConfig,
            positions: Optional[jax.Array] = None,
            attn_fn=None) -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, V]."""
    return forward_with_stats(params, tokens, cfg, positions, attn_fn)[0]


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
