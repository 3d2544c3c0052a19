"""Flagship model: decoder-only transformer, TPU-first.

Pure-functional jax (no flax): params are a pytree of arrays; the
sharding layout is a parallel pytree of ``PartitionSpec``s produced by
``param_specs`` so the same code runs dp/fsdp/tp/sp layouts by changing
only the mesh. Design notes:

- compute in bfloat16, params/optimizer in float32 (MXU-friendly); a
  serving replica may hold the params in bfloat16 as published;
- static shapes everywhere; no data-dependent Python control flow;
- ``remat=True`` keeps for the backward pass what the device has room
  for beside the step's state and recomputes the rest, layer by layer
  (``remat_plan``: arithmetic on shapes and the device's memory limit
  when the train step is traced; with no limit known, every layer is
  recomputed under ``jax.checkpoint``); ``remat=False`` keeps all;
- one ``forward`` over a **layer pattern** (``TransformerConfig.layers``,
  one ``LayerSpec`` a layer): each layer's attention sees every earlier
  key or a sliding window of them, rotates its queries and keys (RoPE)
  or encodes no position, and feeds a dense SwiGLU or routed experts
  beside shared ones (``ray_tpu.ops.moe``: this chip's experts' part,
  no token dropped). The default pattern is "full causal, RoPE, dense"
  repeated: GQA, RoPE, RMSNorm, SwiGLU, the contemporary dense block;
- a layer's **mixer** is part of its spec: softmax attention as above,
  ``lightning`` (linear attention with a per-head exponential decay, a
  chunked scan that carries a state and no keys:
  ``ray_tpu.ops.lightning_attention``) or ``sparse`` (every query
  attends to the ``top_k`` key blocks it scores highest, its own
  window and the first block, once the sequence outgrows
  ``SparseSizes.dense_len``: ``ray_tpu.ops.sparse_attention``) or
  ``eva`` (exact softmax over the keys of the query's own window and
  one learned summary for each chunk of the windows before it, under
  one normaliser: ``ray_tpu.ops.eva_attention``) or ``kda`` (a delta
  rule over a state a head with a data-dependent decay for every key
  channel, behind short causal convolutions and L2 norms on q and k,
  its output normed by head and gated: ``ray_tpu.ops.kda_attention``)
  or ``mla`` (causal softmax attention whose keys and values come out
  of one low-rank latent a token, the keys wider than the values by
  lanes all heads share, no position encoding). A spec may name its
  own number of KV heads;
- model-wide switches for what some families add to every layer:
  RMSNorm on queries and keys by head, a sigmoid gate on the attention
  output, norms after attention and MLP as well as before (sandwich),
  an RMSNorm over a lightning layer's merged heads, an embedding scale,
  a residual scale and a logit scale (muP), norm scales stored as
  their offset from 1, a float32 residual stream, float32 logits,
  several next-token heads side by side; ``head_dim`` and
  ``rms_norm_eps`` are fields;
- ``config_from_hf`` reads a published ``config.json``'s keys (the
  ``mistral``, ``afmoe``, ``minicpm_sala``, ``evabyte`` and
  ``kimi_linear`` families) into a ``TransformerConfig``;
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
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One layer of the pattern."""
    window: Optional[int] = None    # keys a query sees; None: all before it
    rope: bool = True               # False: no position encoding (NoPE)
    experts: bool = False           # routed + shared experts, else dense MLP
    mixer: str = "softmax"          # or another of MIXERS
    kv_heads: Optional[int] = None  # None: the model's n_kv_heads


MIXERS = ("softmax", "lightning", "sparse", "eva", "kda", "mla")


@dataclasses.dataclass(frozen=True)
class SparseSizes:
    """The sizes of a ``sparse`` layer (InfLLM-V2 as MiniCPM4 ships it;
    ``ops/sparse_attention.py`` says what each does)."""
    kernel: int = 32        # tokens a compressed key is the mean of
    stride: int = 16        # tokens between compressed keys
    block: int = 64         # keys a block
    top_k: int = 64         # blocks a query attends to, forced included
    window: int = 2048      # the latest keys, always taken (whole blocks)
    init_blocks: int = 1    # the first blocks, always taken
    dense_len: int = 8192   # up to this length: plain causal attention

    def __post_init__(self):
        if (self.kernel % self.stride or self.block % self.stride
                or self.window % self.block or self.window < self.block
                or self.init_blocks + self.window // self.block
                > self.top_k):
            raise ValueError(f"sparse sizes do not fit together: {self}")


@dataclasses.dataclass(frozen=True)
class EvaSizes:
    """The sizes of an ``eva`` layer (``ops/eva_attention.py``)."""
    window: int = 2048      # positions a window: exact attention inside
    chunk: int = 16         # keys a summary stands for

    def __post_init__(self):
        if self.window % self.chunk:
            raise ValueError(f"eva sizes do not fit together: {self}")


@dataclasses.dataclass(frozen=True)
class KdaSizes:
    """The sizes of a ``kda`` layer (``ops/kda_attention.py``)."""
    conv: int = 4           # taps of the causal convolutions on q, k, v
    rank: int = 128         # the decay's and the gate's inner width


@dataclasses.dataclass(frozen=True)
class MlaSizes:
    """The sizes of an ``mla`` layer: keys and values out of a latent."""
    kv_rank: int = 512      # the latent a token's K and V come out of
    nope: int = 128         # key lanes of a head's own
    shared: int = 64        # key lanes all heads share
    value: int = 128        # a head's values


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
    residual_scale: float = 1.0  # each half-layer's result, before the add
    logit_scale: float = 1.0     # the final norm's result, before the head
    mixer_out_norm: bool = False  # RMSNorm over a lightning layer's heads
    sparse: SparseSizes = SparseSizes()     # the ``sparse`` layers' sizes
    eva: EvaSizes = EvaSizes()              # the ``eva`` layers' sizes
    kda: KdaSizes = KdaSizes()              # the ``kda`` layers' sizes
    mla: MlaSizes = MlaSizes()              # the ``mla`` layers' sizes
    # next-token heads side by side in ``unembed``: head ``h`` (columns
    # ``vocab_size h ..``) predicts the token ``1 + h`` positions on
    n_pred_heads: int = 1
    norm_unit_offset: bool = False  # a norm's scale is 1 + what is stored
    residual_f32: bool = False      # the residual stream stays float32
    logits_f32: bool = False        # the head accumulates into float32
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
        for spec in self.layers:
            if spec.mixer not in MIXERS or (
                    spec.mixer != "softmax" and spec.window is not None):
                raise ValueError(f"no such layer: {spec}")
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
    embedding scale) and the ``minicpm_sala`` family (``mixer_types`` of
    ``minicpm4`` block-sparse and ``lightning-attn`` linear-attention
    layers, each with its own KV heads and RoPE switch; q/k norms, an
    output gate, an output norm on the lightning layers, MiniCPM's muP
    scalars; ``sparse_config``, where given, names the sparse layers'
    sizes, and ``published.num_hidden_layers`` the depth the residual
    scale is reckoned from where the file holds a slice) and the
    ``evabyte`` family (``attention_class`` ``eva`` in every layer with
    its ``window_size`` and ``chunk_size``, ``num_pred_heads`` heads,
    norm scales as offsets from 1, float32 residual stream and logits)
    and the ``kimi_linear`` family (``linear_attn_config`` names the
    ``kda`` and the ``mla`` layers, 1-based; the first
    ``first_k_dense_replace`` layers dense, then sigmoid-routed experts
    beside shared ones).
    A window that
    no sequence of ``max_seq_len`` outgrows is causal attention and is
    dropped. ``expert_parallel`` ``{"size", "rank"}``, where given, says
    that ``num_experts`` counts the experts held here, the ``rank``-th
    of ``size`` equal shares of the router's width."""
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
    if family == "minicpm_sala":
        return _sala_config(config, common)
    if family == "evabyte":
        return _evabyte_config(config, common)
    if family == "kimi_linear":
        return _kimi_config(config, common)
    if family != "afmoe":
        raise ValueError(f"config_from_hf knows the model types 'mistral', "
                         f"'afmoe', 'minicpm_sala', 'evabyte' and "
                         f"'kimi_linear', not {family!r}")
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
        **_expert_share(config),
        expert_top_k=config["num_experts_per_tok"],
        d_ff_expert=config["moe_intermediate_size"],
        n_shared_experts=config.get("num_shared_experts", 0),
        route_scale=float(config.get("route_scale", 1.0)))


def _expert_share(config: dict) -> dict:
    """``n_experts`` and ``experts_held`` from ``num_experts`` and,
    where given, ``expert_parallel``."""
    share = config.get("expert_parallel", {"size": 1, "rank": 0})
    held = config["num_experts"]
    return {"n_experts": held * share["size"],
            "experts_held": (held * share["rank"], held)}


# MiniCPM4's ``sparse_config`` keys as ``SparseSizes`` names them
_SPARSE_KEYS = {"kernel_size": "kernel", "kernel_stride": "stride",
                "block_size": "block", "topk": "top_k",
                "window_size": "window", "init_blocks": "init_blocks",
                "dense_len": "dense_len"}


def _sala_config(config: dict, common: dict) -> TransformerConfig:
    kinds = {"minicpm4": LayerSpec(mixer="sparse",
                                   rope=config["attn_use_rope"]),
             "lightning-attn": LayerSpec(mixer="lightning",
                                         rope=config["lightning_use_rope"],
                                         kv_heads=config["lightning_nkv"])}
    if (config["lightning_nh"] != config["num_attention_heads"]
            or config["lightning_head_dim"] != config["head_dim"]
            or config["use_output_gate"] != config["attn_use_output_gate"]):
        raise ValueError("config_from_hf reads a minicpm_sala model whose "
                         "two kinds of layer share heads, head size and "
                         "the output gate")
    depth = config.get("published", {}).get("num_hidden_layers",
                                            config["num_hidden_layers"])
    return TransformerConfig(
        **common, layers=tuple(kinds[k] for k in config["mixer_types"]),
        qk_norm=config["qk_norm"], attn_gate=config["use_output_gate"],
        mixer_out_norm=config["use_output_norm"],
        embed_scale=float(config["scale_emb"]),
        residual_scale=float(config["scale_depth"] / np.sqrt(depth)),
        logit_scale=config["dim_model_base"] / config["hidden_size"],
        sparse=SparseSizes(**{
            _SPARSE_KEYS[key]: size for key, size in
            config.get("sparse_config", {}).items()}))


def _evabyte_config(config: dict, common: dict) -> TransformerConfig:
    if config["attention_class"] != "eva":
        raise ValueError(f"config_from_hf reads an evabyte model whose "
                         f"attention_class is 'eva', not "
                         f"{config['attention_class']!r}")
    return TransformerConfig(
        **common, layers=(LayerSpec(mixer="eva"),) * common["n_layers"],
        eva=EvaSizes(config["window_size"], config["chunk_size"]),
        n_pred_heads=config["num_pred_heads"],
        norm_unit_offset=config["norm_add_unit_offset"],
        residual_f32=config["fp32_skip_add"],
        logits_f32=config["fp32_logits"])


# what a kimi_linear model must say for the layers written here
_KIMI_ONLY = {"num_expert_group": 1, "topk_group": 1, "q_lora_rank": None,
              "mla_use_nope": True, "moe_router_activation_func": "sigmoid",
              "moe_renormalize": True}


def _kimi_config(config: dict, common: dict) -> TransformerConfig:
    for key, only in _KIMI_ONLY.items():
        if config[key] != only:
            raise ValueError(f"config_from_hf reads a kimi_linear model "
                             f"whose {key} is {only!r}, not {config[key]!r}")
    linear = config["linear_attn_config"]
    if linear["num_heads"] != config["num_attention_heads"]:
        raise ValueError("config_from_hf reads a kimi_linear model whose "
                         "two kinds of layer have the same heads")
    kinds = {**{i: "mla" for i in linear["full_attn_layers"]},
             **{i: "kda" for i in linear["kda_layers"]}}
    return TransformerConfig(
        **dict(common, head_dim=linear["head_dim"]),
        layers=tuple(
            LayerSpec(mixer=kinds[i + 1], rope=False,
                      experts=i >= config["first_k_dense_replace"])
            for i in range(common["n_layers"])),
        kda=KdaSizes(conv=linear["short_conv_kernel_size"],
                     rank=linear["head_dim"]),
        mla=MlaSizes(config["kv_lora_rank"], config["qk_nope_head_dim"],
                     config["qk_rope_head_dim"], config["v_head_dim"]),
        **_expert_share(config),
        expert_top_k=config["num_experts_per_token"],
        d_ff_expert=config["moe_intermediate_size"],
        n_shared_experts=config["num_shared_experts"],
        route_scale=float(config["routed_scaling_factor"]))


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
    # a norm's scale of 1, as it is stored
    unit = lambda n: jnp.full(                              # noqa: E731
        (n,), 0.0 if cfg.norm_unit_offset else 1.0, jnp.float32)
    params: Dict[str, Any] = {
        "embed": jax.random.normal(keys[0], (cfg.vocab_size, d),
                                   jnp.float32) * 0.02,
        "final_norm": unit(d),
        "blocks": [],
    }
    for i, spec in enumerate(cfg.layers):
        bk = jax.random.split(keys[i + 1], 8)
        kv = cfg.n_heads if spec.mixer == "kda" else \
            spec.kv_heads or cfg.n_kv_heads
        if spec.mixer == "mla":     # keys and values out of one latent
            m, n = cfg.mla, cfg.n_heads
            ak, uk = jax.random.split(bk[7])
            mixer = {
                "wq": _dense_init(bk[0], (d, n, m.nope + m.shared)),
                "wkv_a": _dense_init(ak, (d, m.kv_rank + m.shared)),
                "kv_norm": unit(m.kv_rank),
                "wkv_b": _dense_init(uk, (m.kv_rank, n, m.nope + m.value)),
                "wo": _dense_init(bk[3], (n, m.value, d), in_axis=(0, 1))}
        else:
            mixer = {
                "wq": _dense_init(bk[0], (d, cfg.n_heads, hd)),
                "wk": _dense_init(bk[1], (d, kv, hd)),
                "wv": _dense_init(bk[2], (d, kv, hd)),
                "wo": _dense_init(bk[3], (cfg.n_heads, hd, d),
                                  in_axis=(0, 1))}
        block = {"attn_norm": unit(d), **mixer, "mlp_norm": unit(d)}
        if spec.mixer == "kda":
            block.update(_kda_init(bk[7], cfg, unit))
        if cfg.qk_norm:
            block.update(q_norm=unit(hd), k_norm=unit(hd))
        if cfg.attn_gate:
            block["wgate"] = _dense_init(bk[7], (d, cfg.n_heads, hd))
        if cfg.mixer_out_norm and spec.mixer == "lightning":
            block["out_norm"] = unit(cfg.n_heads * hd)
        if spec.mixer == "eva":
            pk, mk = jax.random.split(bk[7])
            block["eva_phi"] = jax.random.normal(
                pk, (cfg.n_heads, hd), jnp.float32) * 0.02
            block["eva_mu"] = jax.random.normal(
                mk, (cfg.n_heads, hd), jnp.float32) * 0.02
        if cfg.sandwich_norm:
            block.update(post_attn_norm=unit(d), post_mlp_norm=unit(d))
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
    params["unembed"] = _dense_init(
        keys[-1], (d, cfg.n_pred_heads * cfg.vocab_size))
    return params


def _kda_init(key, cfg: TransformerConfig, unit) -> Dict:
    """What a ``kda`` layer holds beside ``wq, wk, wv, wo``: the three
    convolutions, the decay (through ``rank``, then a bias and a rate a
    head, drawn as the published checkpoint's initialisation draws
    them: rates ``U(1, 16)``, steps log-uniform in ``[0.001, 0.1]``),
    the step, the output gate (through ``rank``) and the output norm."""
    d, n, hd, sz = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.kda
    ks = jax.random.split(key, 10)
    step = jnp.exp(jax.random.uniform(
        ks[8], (n, hd), jnp.float32, np.log(1e-3), np.log(1e-1)))
    return {
        **{name: jax.random.normal(k, (n, hd, sz.conv), jnp.float32)
           / np.sqrt(sz.conv)
           for name, k in zip(("conv_q", "conv_k", "conv_v"), ks[:3])},
        "wf_a": _dense_init(ks[3], (d, sz.rank)),
        "wf_b": _dense_init(ks[4], (sz.rank, n, hd)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),   # softplus^-1
        "a_log": jnp.log(jax.random.uniform(ks[9], (n,), jnp.float32,
                                            1.0, 16.0)),
        "w_beta": _dense_init(ks[5], (d, n)),
        "wg_a": _dense_init(ks[6], (d, sz.rank)),
        "wg_b": _dense_init(ks[7], (sz.rank, n, hd)),
        "bg": jnp.zeros((n, hd), jnp.float32),
        "out_norm": unit(hd),
    }


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
        by_head = P("fsdp", "tp", None)
        if spec.mixer == "mla":     # the latent and its norm on every shard
            keys = {"wkv_a": P("fsdp", None), "kv_norm": P(None),
                    "wkv_b": P(None, "tp", None)}
        else:
            keys = {"wk": by_head, "wv": by_head}
        block: Dict[str, Any] = {
            "attn_norm": P(None), "wq": by_head, **keys,
            "wo": P("tp", None, "fsdp"), "mlp_norm": P(None)}
        if spec.mixer == "kda":     # by head like the rest
            block.update(
                conv_q=P("tp", None, None), conv_k=P("tp", None, None),
                conv_v=P("tp", None, None), wf_a=P("fsdp", None),
                wf_b=P(None, "tp", None), dt_bias=P("tp", None),
                a_log=P("tp"), w_beta=P("fsdp", "tp"), wg_a=P("fsdp", None),
                wg_b=P(None, "tp", None), bg=P("tp", None),
                out_norm=P(None))
        if cfg.qk_norm:
            block.update(q_norm=P(None), k_norm=P(None))
        if cfg.attn_gate:
            block["wgate"] = P("fsdp", "tp", None)
        if cfg.mixer_out_norm and spec.mixer == "lightning":
            block["out_norm"] = P(None)
        if spec.mixer == "eva":
            block.update(eva_phi=P("tp", None), eva_mu=P("tp", None))
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

# What a layer under ``remat`` keeps for its backward pass beside its
# input, by level: nothing (the backward runs the layer's forward
# again), the attention kernel's results with its inputs (the forward
# kernel and the q/k/v projections are not run again), those and the
# gate and up projections' results, everything (the layer is not
# wrapped). ``remat_plan`` chooses a level a layer.
_ATTN_KEPT = ("attn_q", "attn_k", "attn_v", "attn_out", "attn_lse")
REMAT_KEEPS = ((), _ATTN_KEPT, _ATTN_KEPT + ("mlp_gate", "mlp_up"))
KEEP_LAYER = len(REMAT_KEEPS)


def rms_norm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(x.dtype)


def _rope_tables(positions: jax.Array, hd: int, theta: float, dtype):
    """What RoPE multiplies by: ``cos`` and ``sin`` of its angles, ``[B,
    S, 1, Hd]`` float32, a pair of lanes alike, and the signed pair swap
    ``(a, b) -> (-b, a)`` as a matrix ``[Hd, Hd]`` in ``dtype``."""
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    # ``repeat``: frequencies made at every index give the chip another
    # last place in the table than the sliced form had (PERF.md §6)
    angles = positions[..., None].astype(jnp.float32) * jnp.repeat(freqs, 2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    swap = np.zeros((hd, hd), np.float32)
    even = np.arange(0, hd, 2)
    swap[even + 1, even], swap[even, even + 1] = -1.0, 1.0
    return cos, sin, jnp.asarray(swap, dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, S, N, Hd]; positions: [B, S]. Rotates the pairs
    ``(2i, 2i+1)``. The pair swap ``(a, b) -> (-b, a)`` is a product
    with a constant signed permutation, exact in any type: a stride-2
    slice of the lanes is a gather on a TPU, and q and k then pass
    memory eight times (PERF.md §6, PR 36)."""
    cos, sin, swap = _rope_tables(positions, x.shape[-1], theta, x.dtype)
    turned = jnp.einsum("bsnh,hk->bsnk", x, swap,
                        precision=jax.lax.Precision.HIGHEST)
    return (x * cos + turned * sin).astype(x.dtype)


def _attention(q, k, v, *, causal: bool = True,
               window: Optional[int] = None):
    """Plain blockless attention — the sp=1 path. ``q [B,S,N,Hd]``,
    ``k, v`` at their KV heads (GQA), repeated here up to the query
    heads. Ring attention (sp>1) is dispatched above this, in
    ops.attention."""
    from ray_tpu.ops.flash_attention import repeat_kv
    k, v = repeat_kv(k, v, q.shape[2])
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


def _norm(x, scale, cfg: "TransformerConfig"):
    """``rms_norm`` at the model's eps, handed on in the compute type
    (a float32 residual stream is read through it); under
    ``norm_unit_offset`` the scale is 1 + what is stored."""
    if cfg.norm_unit_offset:
        scale = 1.0 + scale
    return rms_norm(x, scale, cfg.rms_norm_eps).astype(cfg.dtype)


def _swiglu(h, wg, wi, wo, dt, out_type=None):
    """``out_type``: the type the down projection accumulates into and
    returns (None: its operands')."""
    gate = jax.nn.silu(checkpoint_name(h @ wg.astype(dt), "mlp_gate"))
    up = checkpoint_name(h @ wi.astype(dt), "mlp_up")
    return jnp.matmul(gate * up, wo.astype(dt),
                      preferred_element_type=out_type)


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


def _kernel_prologue(spec: LayerSpec, cfg: TransformerConfig) -> bool:
    """Whether the layer's q/k norm and RoPE are the mixer's kernel's to
    do: a lightning layer's on the kernel path, where the kernel loads
    each tile of q and of k once."""
    return cfg.use_flash and spec.mixer == "lightning"


def _short_conv(x, taps):
    """A causal convolution by channel and SiLU: ``x [B, S, N, H]``,
    ``taps [N, H, K]`` -> float32 ``silu(sum_i taps[..., i] x_(t - K + 1
    + i))``, zeros before the first position."""
    width = taps.shape[-1]
    x = jnp.pad(x.astype(jnp.float32),
                ((0, 0), (width - 1, 0), (0, 0), (0, 0)))
    taps = taps.astype(jnp.float32)
    return jax.nn.silu(sum(x[:, i:x.shape[1] - width + 1 + i] * taps[..., i]
                           for i in range(width)))


_KDA_L2_EPS = 1e-6      # under the root of q's and k's L2 norms


def _kda_operands(block, h, cfg: TransformerConfig):
    """A ``kda`` layer's q, k, v (convolved; q and k of unit length by
    head, q scaled), its log-decays ``g [B, S, N, H]`` and steps ``beta
    [B, S, N]``, both float32, from the normed input ``h``."""
    dt = cfg.dtype

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                 + _KDA_L2_EPS)

    q, k, v = (_short_conv(
        jnp.einsum("bsd,dnh->bsnh", h, block["w" + name].astype(dt)),
        block["conv_" + name]) for name in "qkv")
    q = (unit(q) * cfg.head_dim ** -0.5).astype(dt)
    rate = jnp.einsum(
        "bsr,rnh->bsnh", h @ block["wf_a"].astype(dt),
        block["wf_b"].astype(dt), preferred_element_type=jnp.float32)
    g = -jnp.exp(block["a_log"].astype(jnp.float32))[:, None] * \
        jax.nn.softplus(rate + block["dt_bias"].astype(jnp.float32))
    beta = jax.nn.sigmoid(jnp.matmul(
        h, block["w_beta"].astype(dt), preferred_element_type=jnp.float32))
    return q, unit(k).astype(dt), v.astype(dt), g, beta


def _mla_operands(block, h, cfg: TransformerConfig):
    """An ``mla`` layer's q and k ``[B, S, N, nope + shared]`` and v
    ``[B, S, N, value]``: the latent normed and decompressed by head,
    the shared key lanes copied to every head, nothing rotated."""
    dt, m = cfg.dtype, cfg.mla
    q = jnp.einsum("bsd,dnh->bsnh", h, block["wq"].astype(dt))
    latent = h @ block["wkv_a"].astype(dt)
    up = jnp.einsum("bsr,rnh->bsnh",
                    _norm(latent[..., :m.kv_rank], block["kv_norm"], cfg),
                    block["wkv_b"].astype(dt))
    shared = jnp.broadcast_to(latent[:, :, None, m.kv_rank:],
                              (*up.shape[:3], m.shared))
    return (q, jnp.concatenate([up[..., :m.nope], shared], axis=-1),
            up[..., m.nope:])


def _mixer(q, k, v, block, positions, spec: LayerSpec,
           cfg: TransformerConfig, gates=()):
    """A ``lightning``, an ``eva`` or a ``kda`` layer's mixer (the
    last with its ``gates``, the log-decays and the steps), or a
    ``sparse`` one's past ``dense_len``: ``q [B, S, N, H]`` and ``k,
    v`` at the layer's KV heads -> (``[B, S, N, H]``, the units of keys
    the sparse kernel visited, or None). Under ``use_flash`` the Pallas
    kernels, else their plain references, as softmax attention has it;
    q and k come normed and rotated but under ``_kernel_prologue``."""
    if spec.mixer == "kda":
        from ray_tpu.ops.kda_attention import kda_attention, kda_reference
        fn = kda_attention if cfg.use_flash else kda_reference
        return fn(q, k, v, *gates), None
    if spec.mixer == "eva":
        from ray_tpu.ops.eva_attention import eva_attention, eva_reference
        fn = eva_attention if cfg.use_flash else eva_reference
        return fn(q, k, v, block["eva_phi"], block["eva_mu"],
                  cfg.eva.window, cfg.eva.chunk), None
    if spec.mixer == "lightning":
        from ray_tpu.ops.lightning_attention import (
            decay_slopes, lightning_attention, lightning_reference)
        slopes = decay_slopes(cfg.n_heads)
        if not cfg.use_flash:
            return lightning_reference(q, k, v, slopes), None
        # q and k as the projections left them: the kernel norms and
        # rotates each tile it loads (``_kernel_prologue``)
        scales = None
        if cfg.qk_norm:
            scales = jnp.stack([block["q_norm"], block["k_norm"]])
            if cfg.norm_unit_offset:
                scales = 1.0 + scales
            scales = scales.astype(cfg.dtype)
        tables = None
        if spec.rope:
            cos, sin, swap = _rope_tables(positions, cfg.head_dim,
                                          cfg.rope_theta, cfg.dtype)
            tables = cos[:, :, 0], sin[:, :, 0], swap
        return lightning_attention(
            q, k, v, slopes, qk_scales=scales, norm_eps=cfg.rms_norm_eps,
            rope=tables), None
    from ray_tpu.ops.sparse_attention import (
        select_blocks_reference, selected_attention, sparse_reference)
    if cfg.use_flash:
        return selected_attention(q, k, v, cfg.sparse)
    return sparse_reference(
        q, k, v, select_blocks_reference(q, k, cfg.sparse),
        cfg.sparse), None


def _sparse_keys(cfg: TransformerConfig, batch: int, seq: int) -> dict:
    """``ops.sparse_attention.keys_counted`` summed over the sparse
    layers, their KV groups and the batch (``visit_pairs`` as it is)."""
    from ray_tpu.ops.sparse_attention import keys_counted
    groups = sum(spec.kv_heads or cfg.n_kv_heads for spec in cfg.layers
                 if spec.mixer == "sparse") * batch
    return {name: count if name == "visit_pairs" else count * groups
            for name, count in keys_counted(seq, cfg.sparse).items()}


def _record_mixers_plan(cfg: TransformerConfig, batch: int, seq: int):
    """One ``model.mixers.plan`` record for the forward being traced, if
    the pattern holds a lightning or a sparse layer: what the mixers do
    at this shape, from shapes alone (docs/tracing.md)."""
    kinds = [spec.mixer for spec in cfg.layers]
    linear, sparse = kinds.count("lightning"), kinds.count("sparse")
    if not linear and not sparse:
        return
    import time

    from ray_tpu.ops.lightning_attention import choose_chunk
    from ray_tpu.util import tracing
    sparse_mode = int(sparse > 0 and seq > cfg.sparse.dense_len)
    keys = _sparse_keys(cfg, batch, seq)
    now = time.perf_counter_ns()
    tracing.record(
        "model.mixers.plan", now, now, tokens=batch * seq,
        linear_layers=linear, sparse_layers=sparse, sparse_mode=sparse_mode,
        chunk=choose_chunk(seq) if linear else 0,
        prologue_layers=sum(
            _kernel_prologue(spec, cfg) and (cfg.qk_norm or spec.rope)
            for spec in cfg.layers),
        state_bytes=linear * cfg.n_heads * cfg.head_dim ** 2 * 4,
        **{name: keys[name] if sparse_mode else 0
           for name in ("keys_selected", "keys_causal")})


def _record_eva_plan(cfg: TransformerConfig, batch: int, seq: int):
    """One ``model.eva.plan`` record for the forward being traced, if
    the pattern holds an ``eva`` layer: what those layers do at this
    shape, from shapes alone (docs/tracing.md)."""
    layers = sum(spec.mixer == "eva" for spec in cfg.layers)
    if not layers:
        return
    import time

    from ray_tpu.ops.eva_attention import pairs
    from ray_tpu.util import tracing
    window, chunk = cfg.eva.window, cfg.eva.chunk
    local, far = (p * batch * layers * cfg.n_heads
                  for p in pairs(seq, window, chunk))
    it = jnp.dtype(cfg.dtype).itemsize
    now = time.perf_counter_ns()
    tracing.record(
        "model.eva.plan", now, now, tokens=batch * seq, eva_layers=layers,
        window=window, chunk=chunk, windows=-(-seq // window),
        summaries=seq // chunk, local_pairs=local, far_pairs=far,
        # what a sequence would leave behind: one window of K and V and
        # a summary of each for every chunk, in every layer
        state_bytes=2 * (min(seq, window) + seq // chunk) * layers
        * cfg.n_heads * cfg.head_dim * it)


def _record_delta_plan(cfg: TransformerConfig, batch: int, seq: int):
    """One ``model.delta.plan`` record for the forward being traced, if
    the pattern holds a ``kda`` or an ``mla`` layer: what those layers
    do at this shape and what a sequence would leave behind in them,
    from shapes alone (docs/tracing.md)."""
    kinds = [spec.mixer for spec in cfg.layers]
    kda, mla = kinds.count("kda"), kinds.count("mla")
    if not kda and not mla:
        return
    import time

    from ray_tpu.ops.kda_attention import CHUNK
    from ray_tpu.util import tracing
    n, hd, m = cfg.n_heads, cfg.head_dim, cfg.mla
    it = jnp.dtype(cfg.dtype).itemsize
    tokens = batch * seq
    now = time.perf_counter_ns()
    tracing.record(
        "model.delta.plan", now, now, tokens=tokens, kda_layers=kda,
        mla_layers=mla, chunk=CHUNK if kda else 0,
        chunks=-(-seq // CHUNK) if kda else 0,
        # a token and head: the state decayed, read by k, moved by the
        # outer product, read by q
        kda_flops=kda * tokens * n * 7 * hd * hd,
        mla_pair_flops=mla * batch * (seq * (seq + 1) // 2) * n * 2
        * (m.nope + m.shared + m.value),
        # what a sequence would leave behind: the state and the three
        # convolutions' tails; the latent and the shared key lanes, or
        # the same layers' K and V decompressed
        state_bytes=kda * batch * (
            n * hd * hd * 4 + 3 * (cfg.kda.conv - 1) * n * hd * it),
        latent_bytes=mla * tokens * (m.kv_rank + m.shared) * it,
        kv_bytes=mla * tokens * n * (m.nope + m.shared + m.value) * it)


def record_sparse_visits(units, cfg: TransformerConfig, batch: int,
                         seq: int, request: Optional[str] = None) -> None:
    """One ``model.sparse.visits`` record for a forward whose
    ``stats["sparse_units"]`` came back with its logits (``units``, on
    the host), as ``ops.moe.record_route`` makes ``model.moe.route``:
    ``keys_read`` is what the visits fetched, a unit's keys for each
    query of the tile (docs/tracing.md)."""
    import time

    from ray_tpu.util import tracing
    counted = _sparse_keys(cfg, batch, seq)
    visited = int(np.sum(units))
    now = time.perf_counter_ns()
    tracing.record(
        "model.sparse.visits", now, now, request, tokens=batch * seq,
        layers=int(np.size(units)), units_visited=visited,
        units_before=counted["units_before"],
        keys_read=visited * counted["visit_pairs"],
        keys_selected=counted["keys_selected"],
        keys_causal=counted["keys_causal"])


def _block_forward(block, x, positions, cfg: TransformerConfig,
                   attn_fn=None):
    """The dense block (full causal attention, RoPE, SwiGLU) that the
    ViT and the pipeline stages repeat. -> x."""
    return _layer_forward(block, x, positions, LayerSpec(), cfg,
                          attn_fn or _attention)[0]


def _layer_forward(block, x, positions, spec: LayerSpec,
                   cfg: TransformerConfig, attn_fn):
    """One layer of the pattern. -> (x, the rows each held expert was
    given, or None, the units of keys a sparse kernel visited, or
    None)."""
    dt = cfg.dtype
    # a float32 residual stream: each half reads it through a norm, in
    # the compute type, and adds a float32 result back
    out_type = jnp.float32 if cfg.residual_f32 else None
    h = _norm(x, block["attn_norm"], cfg)
    gates = ()
    if spec.mixer == "kda":
        q, k, v, *gates = _kda_operands(block, h, cfg)
    elif spec.mixer == "mla":
        q, k, v = _mla_operands(block, h, cfg)
    else:
        q = jnp.einsum("bsd,dnh->bsnh", h, block["wq"].astype(dt))
        k = jnp.einsum("bsd,dnh->bsnh", h, block["wk"].astype(dt))
        v = jnp.einsum("bsd,dnh->bsnh", h, block["wv"].astype(dt))
        if not _kernel_prologue(spec, cfg):
            if cfg.qk_norm:
                q = _norm(q, block["q_norm"], cfg)
                k = _norm(k, block["k_norm"], cfg)
            if spec.rope:
                q = rope(q, positions, cfg.rope_theta)
                k = rope(k, positions, cfg.rope_theta)
    # a sparse layer is plain causal attention up to ``dense_len``
    if spec.mixer in ("softmax", "mla") or (
            spec.mixer == "sparse" and x.shape[1] <= cfg.sparse.dense_len):
        # GQA: k and v go to ``attn_fn`` at their KV heads, as the
        # projections left them. The flash kernel serves a KV group a
        # grid step (ops/flash_attention.py); a path that needs equal
        # head counts repeats inside itself.
        q, k, v = (checkpoint_name(a, name) for a, name in
                   ((q, "attn_q"), (k, "attn_k"), (v, "attn_v")))
        attn = attn_fn(q, k, v) if spec.window is None else \
            attn_fn(q, k, v, window=spec.window)
        visited = None
    else:
        attn, visited = _mixer(q, k, v, block, positions, spec, cfg, gates)
        if cfg.mixer_out_norm and spec.mixer == "lightning":
            attn = _norm(attn.reshape(*x.shape[:2], -1),
                         block["out_norm"], cfg).reshape(attn.shape)
        if spec.mixer == "kda":     # normed by head, gated through a rank
            gate = jnp.einsum(
                "bsr,rnh->bsnh", h @ block["wg_a"].astype(dt),
                block["wg_b"].astype(dt), preferred_element_type=jnp.float32)
            attn = _norm(attn, block["out_norm"], cfg) * jax.nn.sigmoid(
                gate + block["bg"].astype(jnp.float32)).astype(dt)
    if cfg.attn_gate:
        gate = jnp.einsum("bsd,dnh->bsnh", h, block["wgate"].astype(dt))
        attn = attn * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(dt)
    attn = jnp.einsum("bsnh,nhd->bsd", attn, block["wo"].astype(dt),
                      preferred_element_type=out_type)
    if cfg.sandwich_norm:
        attn = _norm(attn, block["post_attn_norm"], cfg)
    scaled = cfg.residual_scale != 1.0
    if scaled:
        attn = attn * jnp.asarray(cfg.residual_scale, dt)
    x = x + attn

    h = _norm(x, block["mlp_norm"], cfg)
    rows = None
    if spec.experts:
        f, rows = _experts_mlp(block, h, cfg)
    else:
        f = _swiglu(h, block["wg"], block["wi"], block["wo_mlp"], dt,
                    out_type)
    if cfg.sandwich_norm:
        f = _norm(f, block["post_mlp_norm"], cfg)
    if scaled:
        f = f * jnp.asarray(cfg.residual_scale, dt)
    return x + f, rows, visited


class RematPlan(NamedTuple):
    """What each layer keeps for the backward pass, and the counts (one
    device's bytes and FLOPs) the choice was made from."""
    levels: Tuple[int, ...]     # a layer: index into REMAT_KEEPS, or KEEP_LAYER
    kept_bytes: int             # all layers' kept activations
    budget_bytes: int           # the room they had, at the loss
    peak_bytes: int             # the step's counted peak, state included
    recompute_flops: int        # forward FLOPs the backward runs again
    layer_forward_flops: int    # the mean layer's forward


# Room left between the counted peak and the device's limit. The count
# is the activations and gradients the algorithm holds; the compiled
# step adds what no shape gives: its own code (90 MB at the benchmark's
# train cell), the scheduler's choice of what overlaps (the count reads
# 0.21 GB under to 0.18 GB over libtpu's total at that cell's shapes
# with 1-3 layers at every level) and the allocator's fragmentation.
# 4 % of a 16 GB chip is 0.64 GB.
REMAT_MARGIN = 0.04


def _layer_params(cfg: TransformerConfig, spec: LayerSpec) -> int:
    """The layer's matrices' parameters (norm scales left out)."""
    d, hd, n = cfg.d_model, cfg.head_dim, cfg.n_heads
    attention = d * hd * ((2 + cfg.attn_gate) * n
                          + 2 * (spec.kv_heads or cfg.n_kv_heads))
    if spec.mixer == "kda":
        attention = n * hd * (4 * d + 3 * cfg.kda.conv) + d * n \
            + 2 * cfg.kda.rank * (d + n * hd)
    elif spec.mixer == "mla":
        m = cfg.mla
        attention = d * n * (m.nope + m.shared + m.value) \
            + (m.kv_rank + m.shared) * d + m.kv_rank * n * (m.nope + m.value)
    if not spec.experts:
        return attention + 3 * d * cfg.d_ff
    return attention + d * cfg.n_experts + 3 * d * cfg.d_ff_expert * (
        cfg.experts_held[1] + cfg.n_shared_experts)


def _layer_counts(cfg: TransformerConfig, spec: LayerSpec, b: int, s: int,
                  tp: int):
    """One layer on one device, ``b`` sequences of ``s`` tokens, widths
    over ``tp`` shards -> (bytes kept by level, forward FLOPs the
    backward runs again by level, the forward's FLOPs). Level 0 keeps
    the layer's input alone; KEEP_LAYER what XLA holds of an unwrapped
    layer: the input, both norms' results and the sum between them, q,
    k and v at their KV heads as the kernel is given them, its
    result and log-sum-exp, gate and up (the silu and the product fuse
    into the down projection)."""
    it = jnp.dtype(cfg.dtype).itemsize
    t, d, hd = b * s, cfg.d_model, cfg.head_dim
    n, kv, f = (max(w // tp, 1) for w in
                (cfg.n_heads, spec.kv_heads or cfg.n_kv_heads, cfg.d_ff))
    wide, heads, lse = t * d * it, t * n * hd * it, b * n * s * 4
    attn_kept = 2 * heads + 2 * t * kv * hd * it + lse
    mlp_kept = 2 * t * f * it
    extra = (cfg.qk_norm * (heads + t * kv * hd * it)
             + cfg.attn_gate * 2 * heads + cfg.sandwich_norm * 2 * wide)
    kept = (wide, wide + attn_kept, wide + attn_kept + mlp_kept,
            4 * wide + attn_kept + mlp_kept + extra)
    seen = s / 2 if spec.window is None or spec.window >= s else spec.window
    if spec.mixer in ("lightning", "kda"):  # the state's form: H a token
        seen = hd / 2
    elif spec.mixer == "sparse" and s > cfg.sparse.dense_len:
        seen = cfg.sparse.top_k * cfg.sparse.block
    elif spec.mixer == "eva":
        from ray_tpu.ops.eva_attention import pairs
        seen = sum(pairs(s, cfg.eva.window, cfg.eva.chunk)) / s
    qkv = 2 * t * d * hd * ((1 + cfg.attn_gate) * n + 2 * kv)
    attention = 2 * 2 * t * n * hd * seen
    out = 2 * t * n * hd * d
    mlp_in, mlp_out = 2 * 2 * t * d * f, 2 * t * f * d
    forward = qkv + attention + out + mlp_in + mlp_out
    # the layer's result is the next layer's kept input, so no level
    # runs the down projection again
    again = (forward - mlp_out, out + mlp_in, out, 0)
    return kept, tuple(int(a) for a in again), int(forward)


def remat_plan(cfg: TransformerConfig, batch: int, seq: int,
               held_bytes: int, limit_bytes: Optional[int],
               shards: Optional[Dict[str, int]] = None,
               levels: Optional[Tuple[int, ...]] = None) -> RematPlan:
    """What ``remat=True`` keeps: as much as the device has room for
    beside the step's own state, no device touched. ``batch`` and
    ``seq`` are the whole batch's, ``shards`` the mesh's axis sizes
    (``mesh.shape``), ``held_bytes`` the parameters' and the optimizer
    state's bytes on one device, ``limit_bytes`` that device's memory
    (None: not known, and every layer is recomputed as before).
    ``levels`` given are counted and not chosen (tests).

    Levels are ranked by the recompute time a byte saves: the flash
    kernel's results with its inputs (at the benchmark's train cell
    0.34 TFLOP for 84 MB, a third of it the kernel at half the MXU's
    rate), then gate and up (0.96 TFLOP for 235 MB), then the rest of
    the layer (0.14 TFLOP for 151 MB). Every layer is raised to one
    level before any is raised to the next, the first layers first (at
    that cell a plan that keeps the first layers whole ran 1.3-4.6 ms a
    step faster than its mirror image and compiled 0.07-0.09 GB
    smaller, PERF.md §6 PR 29), so at most two levels are in use and
    ``forward_with_stats`` traces two functions a kind of layer. A
    raise is taken while the step's counted peak stays under the limit
    less ``REMAT_MARGIN``. The peak is the largest of these moments:
    at the loss, everything kept plus three float32 arrays the size of
    the logits (the logits, their shifted copy, their gradient); in
    the backward pass at each layer, what the earlier layers keep,
    this layer whole (kept or recomputed) and the gradients from this
    layer on, which wait in the compute type until the clip has seen
    them all; at the end, every gradient. Layers of routed experts and
    layers whose mixer is not softmax attention keep nothing (they
    cannot train yet: their kernels have no backward and say so by name
    under ``jax.grad``), and dense S x S attention is not counted:
    without ``use_flash`` nothing is kept."""
    shards = shards or {}
    tp = shards.get("tp", 1)
    # fsdp shards the parameters; whether it divides the activations is
    # the partitioner's choice, and at the 12-layer 2x2 share libtpu's
    # keeps the weights where they lie and all-reduces whole-batch
    # activations. Counted undivided, which errs to the safe side.
    b = max(batch // shards.get("dp", 1), 1)
    s = max(seq // shards.get("sp", 1), 1)
    it = jnp.dtype(cfg.dtype).itemsize
    counts = [_layer_counts(cfg, spec, b, s, tp) for spec in cfg.layers]
    split = shards.get("fsdp", 1) * tp          # ways a matrix is split
    table = cfg.vocab_size * cfg.d_model * it // split
    layer_grads = [_layer_params(cfg, spec) * it // split
                   for spec in cfg.layers]
    logits = 3 * b * s * (cfg.vocab_size // tp) * 4

    def peak(levels):
        kept = [c[0][lv] for c, lv in zip(counts, levels)]
        before = sum(kept)                  # what layers before i keep
        high = before + logits                          # at the loss
        grads = table                                   # the unembedding's
        for i in reversed(range(cfg.n_layers)):
            before -= kept[i]
            grads += layer_grads[i]
            high = max(high, before + counts[i][0][KEEP_LAYER] + grads)
        return held_bytes + max(high, grads + table + kept[0])

    room = int(limit_bytes * (1 - REMAT_MARGIN)) if limit_bytes else 0
    if levels is None:
        levels = [0] * cfg.n_layers
        raises = [(level, i) for level in range(1, KEEP_LAYER + 1)
                  for i in range(cfg.n_layers)
                  if not cfg.layers[i].experts
                  and cfg.layers[i].mixer == "softmax"]
        for level, i in raises if room and cfg.use_flash else ():
            trial = levels[:i] + [level] + levels[i + 1:]
            if peak(trial) > room:
                break
            levels = trial
    return RematPlan(
        levels=tuple(levels),
        kept_bytes=sum(c[0][lv] for c, lv in zip(counts, levels)),
        budget_bytes=max(0, room - held_bytes - logits),
        peak_bytes=peak(levels),
        recompute_flops=sum(c[1][lv] for c, lv in zip(counts, levels)),
        layer_forward_flops=sum(c[2] for c in counts) // cfg.n_layers)


def forward_with_stats(params, tokens: jax.Array, cfg: TransformerConfig,
                       positions: Optional[jax.Array] = None,
                       attn_fn=None,
                       logit_positions: Optional[jax.Array] = None,
                       remat_levels: Optional[Tuple[int, ...]] = None):
    """tokens [B, S] int32 -> (logits, stats). Logits are [B, S, V],
    or [B, V] at ``logit_positions [B]`` where given (a prefill needs
    the last position's alone); with ``n_pred_heads`` heads ``V`` is
    that many vocabularies side by side, head ``h`` at columns
    ``vocab_size h ..``. ``stats["moe_rows"]`` [routed layers,
    held experts] int32: the rows each held expert was given, which
    ``ops.moe.record_route`` turns into the ``model.moe.route`` record
    once they are on the host with the logits. Where sparse layers ran
    their kernels, ``stats["sparse_units"]`` [sparse layers] int32: the
    units of keys each one's attention visited, known on the device
    alone, which ``record_sparse_visits`` turns into the
    ``model.sparse.visits`` record the same way (a caller that does not
    ask for them pays nothing: the count is dropped from its program).
    An ``attn_fn`` given from outside is called ``attn_fn(q, k, v)``
    with k and v at their KV heads, and with ``window=`` on a layer
    that has one. ``remat_levels``, one a
    layer, say what each keeps for a backward pass (``REMAT_KEEPS``;
    None: nothing under ``cfg.remat``, everything without); a forward
    alone is the same program at every level."""
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :],
            tokens.shape)
    x = params["embed"].astype(cfg.dtype)[tokens]
    if cfg.residual_f32:
        x = x.astype(jnp.float32)
    if cfg.embed_scale != 1.0:
        x = x * jnp.asarray(cfg.embed_scale, cfg.dtype)
    if attn_fn is None:
        if cfg.use_flash:
            from ray_tpu.ops.flash_attention import flash_attention
            attn_fn = lambda q, k, v, window=None: flash_attention(  # noqa: E731
                q, k, v, causal=True, window=window)
        else:
            attn_fn = _attention
    # One function a kind of layer and level, shared by the layers of
    # that kind and level: jax traces a checkpointed function once for
    # all the layers that call it (a function made anew for each layer
    # is traced anew, and a twelve-layer program then takes four times
    # as long to set up).
    if remat_levels is None:
        remat_levels = (0 if cfg.remat else KEEP_LAYER,) * cfg.n_layers
    _record_mixers_plan(cfg, *tokens.shape)
    _record_eva_plan(cfg, *tokens.shape)
    _record_delta_plan(cfg, *tokens.shape)
    layer_fns, moe_rows, visits = {}, [], []
    for block, spec, level in zip(params["blocks"], cfg.layers,
                                  remat_levels):
        blk = layer_fns.get((spec, level))
        if blk is None:
            blk = functools.partial(_layer_forward, spec=spec, cfg=cfg,
                                    attn_fn=attn_fn)
            if level < KEEP_LAYER:
                blk = jax.checkpoint(
                    blk, static_argnums=(),
                    policy=jax.checkpoint_policies.save_only_these_names(
                        *REMAT_KEEPS[level]) if level else None)
            elif cfg.remat or spec.mixer in ("eva", "kda", "mla"):
                # a layer the plan keeps whole: one jitted function a
                # kind, traced once like the checkpointed ones (twelve
                # bare layers take three times as long to lower);
                # ``remat=False`` stays the bare function it was, but
                # for an ``eva``, a ``kda`` or an ``mla`` layer, whose
                # kernels' bodies or whose many equal layers would
                # lower anew in every layer
                blk = jax.jit(blk)
            layer_fns[spec, level] = blk
        x, rows, visited = blk(block, x, positions)
        if rows is not None:
            moe_rows.append(rows)
        if visited is not None:
            visits.append(visited)
    if logit_positions is not None:
        x = jnp.take_along_axis(x, logit_positions[:, None, None],
                                axis=1)[:, 0]
    x = _norm(x, params["final_norm"], cfg)
    if cfg.logit_scale != 1.0:
        x = x * jnp.asarray(cfg.logit_scale, cfg.dtype)
    logits = jnp.matmul(
        x, params["unembed"].astype(cfg.dtype),
        preferred_element_type=jnp.float32 if cfg.logits_f32 else None
    ).astype(jnp.float32)
    held = cfg.experts_held[1]
    stats = {"moe_rows": jnp.stack(moe_rows) if moe_rows
             else jnp.zeros((0, held), jnp.int32)}
    if visits:
        stats["sparse_units"] = jnp.stack(visits)
    return logits, stats


def forward(params, tokens: jax.Array, cfg: TransformerConfig,
            positions: Optional[jax.Array] = None,
            attn_fn=None) -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, V]."""
    return forward_with_stats(params, tokens, cfg, positions, attn_fn)[0]


def loss_fn(params, batch: Dict[str, jax.Array],
            cfg: TransformerConfig, attn_fn=None,
            remat_levels: Optional[Tuple[int, ...]] = None) -> jax.Array:
    """Next-token cross-entropy. batch: tokens [B,S]; optional
    loss_mask [B,S]. The forward runs on the full S (keeps the seq dim
    divisible by the sp axis for ring attention); the shift to next-
    token targets happens on the logits. ``remat_levels``: what each
    layer keeps for this gradient (``remat_plan``)."""
    tokens = batch["tokens"]
    logits = forward_with_stats(params, tokens, cfg, attn_fn=attn_fn,
                                remat_levels=remat_levels)[0][:, :-1]
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    if mask is not None:
        mask = mask[:, 1:].astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
