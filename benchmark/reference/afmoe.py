"""Plain reference for the `afmoe` architecture (Arcee Trinity): weights
from a seed and the forward pass, in float32 `jax.numpy` at
`precision=highest`. Imports nothing of `ray_tpu` and takes nothing it
made.

The layer, as HF `transformers`' `modeling_afmoe.py` has it (recalled
without a network; what the published `config.json` does not settle is
listed under `assumed` in the configuration file):

- `x = E[tokens] * sqrt(hidden)` (`mup_enabled`).
- Attention of layer `l`: `a = norm_in(x)`; `q = a Wq`, `k = a Wk`,
  `v = a Wv`, `g = a Wgate`; RMSNorm over the head size on `q` and `k`
  (one scale each, shared over heads); RoPE on `q, k` only where
  `layer_types[l]` is `sliding_attention`, nothing on a `full_attention`
  layer; key `j` visible to query `i` iff `j <= i`, and on a sliding
  layer also `i - j < sliding_window`;
  `x = x + norm_post_attn((o * sigmoid(g)) Wo)`.
- MLP: `m = norm_pre_mlp(x)`; the first `num_dense_layers` layers are a
  SwiGLU at `intermediate_size`; the others
  `s = sigmoid(m Wr)` over all published experts, `I = top_k(s + b)`
  (`b` selects and does not weigh), `w = route_scale * s[I] / (sum s[I]
  + 1e-20)`, `f = shared(m) + sum over e in I and held of w_e FFN_e(m)`,
  every FFN a SwiGLU at `moe_intermediate_size`;
  `x = x + norm_post_mlp(f)`.
- Final RMSNorm and the untied head. Every RMSNorm takes `rms_norm_eps`.

**The share.** `Sizes.experts` is how many experts are held and
`Sizes.first_expert` the first of them; the router keeps the published
width `Sizes.router_experts`. Experts in `I` that are not held add
nothing, and that partial `x` goes on to the next layer: it is what one
of the chips that share a layer computes before the exchange. With
`first_expert=0` and `experts == router_experts` it is the uncut layer
(`uncut`).

The weight tree is the one the program loads, in the configuration's
`torch_dtype` (bfloat16): `embed [V,D]`, `blocks[i]` of `attn_norm, wq
[D,N,H], wk [D,K,H], wv, wgate [D,N,H], q_norm [H], k_norm [H], wo
[N,H,D], post_attn_norm, mlp_norm`, then `wg, wi [D,F], wo_mlp [F,D]`
(dense) or `router [D,E], router_bias [E], shared_wg, shared_wi [D,Fs],
shared_wo [Fs,D], experts_wg, experts_wi [held,D,Fe], experts_wo
[held,Fe,D]`, then `post_mlp_norm`; `final_norm`, `unembed [D,V]`.

So that 8.6 GB of bfloat16 weights never stand a second time in
float32, a matrix is cast up where it is used, one layer at a time and
inside a routed layer one expert at a time. An expert is computed on
the rows routed to it, gathered in blocks of 128 (the last padded), not
on all rows masked. Attention runs one query head at a time.

`mode="int8"` is the control of the correctness check: the same
mathematics with every linear layer's operands rounded to int8 per
tensor, the nearest precision below the bfloat16 the configuration
states. The router stays float32 in it: it decides which experts run,
not how precisely.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST
_EXPERT_ROWS = 128


class Sizes(NamedTuple):
    vocab: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    d_ff_expert: int
    router_experts: int     # the router's width: the published experts
    first_expert: int       # the first expert held here
    experts: int            # how many are held here
    top_k: int
    shared_experts: int
    route_scale: float
    rope_theta: float
    norm_eps: float
    window: int
    embed_scale: float
    sliding: Tuple[bool, ...]       # by layer: window and RoPE, or neither
    routed: Tuple[bool, ...]        # by layer: experts, or the dense MLP
    dtype: str

    @property
    def layers(self) -> int:
        return len(self.sliding)

    @classmethod
    def from_config(cls, config: dict) -> "Sizes":
        """From a configuration file's HF keys. `expert_parallel`
        (`size`, `rank`) says which share `num_experts` is."""
        share = config.get("expert_parallel", {"size": 1, "rank": 0})
        held = config["num_experts"]
        kinds = config["layer_types"]
        if len(kinds) != config["num_hidden_layers"]:
            raise ValueError("layer_types does not name every layer")
        d = config["hidden_size"]
        return cls(
            vocab=config["vocab_size"], d_model=d,
            heads=config["num_attention_heads"],
            kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"], d_ff=config["intermediate_size"],
            d_ff_expert=config["moe_intermediate_size"],
            router_experts=held * share["size"],
            first_expert=held * share["rank"], experts=held,
            top_k=config["num_experts_per_tok"],
            shared_experts=config["num_shared_experts"],
            route_scale=float(config["route_scale"]),
            rope_theta=float(config["rope_theta"]),
            norm_eps=float(config["rms_norm_eps"]),
            window=config["sliding_window"],
            embed_scale=math.sqrt(d) if config["mup_enabled"] else 1.0,
            sliding=tuple(k == "sliding_attention" for k in kinds),
            routed=tuple(i >= config["num_dense_layers"]
                         for i in range(len(kinds))),
            dtype=config["torch_dtype"])


def uncut(sz: Sizes) -> Sizes:
    """The same model holding every expert the router names."""
    return sz._replace(first_expert=0, experts=sz.router_experts)


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------

ROUTER_BIAS_STD = 0.02


def leaf_table(sz: Sizes) -> list:
    """[(path, shape, kind)] in a fixed order; a leaf's index in it is
    folded into the seed's key, so any leaf can be made again alone."""
    d, n, k, h = sz.d_model, sz.heads, sz.kv_heads, sz.head_dim
    fe, fs = sz.d_ff_expert, sz.shared_experts * sz.d_ff_expert
    table = [(("embed",), (sz.vocab, d), 0.02),
             (("final_norm",), (d,), "ones"),
             (("unembed",), (d, sz.vocab), d)]
    for i in range(sz.layers):
        leaves = [
            ("attn_norm", (d,), "ones"), ("wq", (d, n, h), d),
            ("wk", (d, k, h), d), ("wv", (d, k, h), d),
            ("wgate", (d, n, h), d), ("q_norm", (h,), "ones"),
            ("k_norm", (h,), "ones"), ("wo", (n, h, d), n * h),
            ("post_attn_norm", (d,), "ones"), ("mlp_norm", (d,), "ones")]
        if sz.routed[i]:
            leaves += [
                ("router", (d, sz.router_experts), d),
                ("router_bias", (sz.router_experts,), ROUTER_BIAS_STD),
                ("shared_wg", (d, fs), d), ("shared_wi", (d, fs), d),
                ("shared_wo", (fs, d), fs),
                ("experts_wg", (sz.experts, d, fe), d),
                ("experts_wi", (sz.experts, d, fe), d),
                ("experts_wo", (sz.experts, fe, d), fe)]
        else:
            leaves += [("wg", (d, sz.d_ff), d), ("wi", (d, sz.d_ff), d),
                       ("wo_mlp", (sz.d_ff, d), sz.d_ff)]
        leaves.append(("post_mlp_norm", (d,), "ones"))
        table += [(("blocks", i, name), shape, kind)
                  for name, shape, kind in leaves]
    return table


def make_leaf(key, index: int, shape, kind, dtype) -> jax.Array:
    """`kind` is "ones", a float (the standard deviation) or an int (the
    fan-in: normal / sqrt(fan_in)); rounded to `dtype`."""
    if kind == "ones":
        return jnp.ones(shape, dtype)
    std = kind if isinstance(kind, float) else 1.0 / math.sqrt(kind)
    return (jax.random.normal(jax.random.fold_in(key, index), shape,
                              jnp.float32) * std).astype(dtype)


def seed_key(seed: int) -> jax.Array:
    # seeds run a little past 2**31: fold both halves in
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def build_tree(sz: Sizes, leaves: Sequence) -> Dict[str, Any]:
    """Leaves in `leaf_table` order -> the weight tree."""
    tree: Dict[str, Any] = {"blocks": [dict() for _ in range(sz.layers)]}
    for (path, _shape, _kind), leaf in zip(leaf_table(sz), leaves):
        if path[0] == "blocks":
            tree["blocks"][path[1]][path[2]] = leaf
        else:
            tree[path[0]] = leaf
    return tree


def make_weights(key, sz: Sizes) -> Dict[str, Any]:
    """The whole tree from one key, in the configuration's type.
    Traceable: jit it to make the weights on the device in one call."""
    dtype = jnp.dtype(sz.dtype)
    return build_tree(sz, [make_leaf(key, i, shape, kind, dtype) for i, (
        _p, shape, kind) in enumerate(leaf_table(sz))])


def share_of(weights, whole: Sizes, first: int, count: int):
    """(weights, sizes) of the share that holds experts `first ..
    first + count - 1` of an uncut model's tree: the experts' leaves
    sliced, everything else as it is."""
    blocks = [{name: leaf[first:first + count]
               if name.startswith("experts_") else leaf
               for name, leaf in block.items()}
              for block in weights["blocks"]]
    return (dict(weights, blocks=blocks),
            whole._replace(first_expert=first, experts=count))


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _int8(x):
    """Per-tensor absmax rounding to 127 levels."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _linear(spec: str, a, w, mode: str):
    """`w` arrives in the stored type and is cast up here, where it is
    used."""
    w = w.astype(jnp.float32)
    if mode == "int8":
        a, w = _int8(a), _int8(w)
    return jnp.einsum(spec, a, w, precision=_HIGHEST)


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """x [B,S,N,H]: rotate pairs (2i, 2i+1) by position * theta^(-2i/H)."""
    h = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, h, 2, dtype=jnp.float32) / h))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(
        angles)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _attend_head(q, k, v, window):
    """One query head: q, k and v [B,S,H] -> [B,S,H]. Key j counts for
    query i iff j <= i and, under a window, i - j < window."""
    s = q.shape[1]
    logits = jnp.einsum("bqh,bkh->bqk", q, k, precision=_HIGHEST)
    logits = logits / math.sqrt(q.shape[-1])
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    visible = j <= i
    if window is not None:
        visible &= i - j < window
    logits = jnp.where(visible[None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bqk,bkh->bqh", probs, v, precision=_HIGHEST)


def _attention(q, k, v, window):
    """q [B,S,N,H], k and v [B,S,K,H]: query head j reads KV head
    j // (N/K); one query head at a time."""
    rep = q.shape[2] // k.shape[2]

    def head(j):
        return _attend_head(q[:, :, j], k[:, :, j // rep], v[:, :, j // rep],
                            window)

    out = jax.lax.map(head, jnp.arange(q.shape[2]))
    return jnp.moveaxis(out, 0, 2)


def _swiglu(rows, wg, wi, wo, mode):
    gate = jax.nn.silu(_linear("rd,df->rf", rows, wg, mode))
    up = _linear("rd,df->rf", rows, wi, mode)
    return _linear("rf,fd->rd", gate * up, wo, mode)


def route(m, router, bias, sz: Sizes):
    """m [T,D] -> (chosen experts [T,k], their weights [T,k])."""
    scores = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", m, router.astype(jnp.float32), precision=_HIGHEST))
    _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), sz.top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = sz.route_scale * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return chosen, weights


def routed_part(p, m, sz: Sizes, mode: str):
    """The held experts' weighted part, m [T,D] -> [T,D]: expert after
    expert, each on the rows routed to it, 128 at a time."""
    t, d = m.shape
    chosen, weights = route(m, p["router"], p["router_bias"], sz)

    def one_expert(out, held):
        wg, wi, wo, index = held
        e = sz.first_expert + index
        weight = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        routed_here = jnp.any(chosen == e, axis=-1)
        rows = jnp.nonzero(routed_here, size=t, fill_value=0)[0]
        count = jnp.sum(routed_here)

        def block(b, out):
            at = jax.lax.dynamic_slice(
                jnp.pad(rows, (0, _EXPERT_ROWS)), (b * _EXPERT_ROWS,),
                (_EXPERT_ROWS,))
            real = b * _EXPERT_ROWS + jnp.arange(_EXPERT_ROWS) < count
            y = _swiglu(m[at], wg, wi, wo, mode)
            scale = jnp.where(real, weight[at], 0.0)
            return out.at[at].add(y * scale[:, None])

        blocks = (count + _EXPERT_ROWS - 1) // _EXPERT_ROWS
        return jax.lax.fori_loop(0, blocks, block, out), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros((t, d), jnp.float32),
        (p["experts_wg"], p["experts_wi"], p["experts_wo"],
         jnp.arange(sz.experts)))
    return out


def _block(p, x, layer: int, sz: Sizes, mode: str):
    b, s, d = x.shape
    a = _rms_norm(x, p["attn_norm"], sz.norm_eps)
    q = _linear("bsd,dnh->bsnh", a, p["wq"], mode)
    k = _linear("bsd,dkh->bskh", a, p["wk"], mode)
    v = _linear("bsd,dkh->bskh", a, p["wv"], mode)
    gate = _linear("bsd,dnh->bsnh", a, p["wgate"], mode)
    q = _rms_norm(q, p["q_norm"], sz.norm_eps)
    k = _rms_norm(k, p["k_norm"], sz.norm_eps)
    window = None
    if sz.sliding[layer]:
        q, k = _rope(q, sz.rope_theta), _rope(k, sz.rope_theta)
        window = sz.window
    attended = _attention(q, k, v, window) * jax.nn.sigmoid(gate)
    x = x + _rms_norm(_linear("bsnh,nhd->bsd", attended, p["wo"], mode),
                      p["post_attn_norm"], sz.norm_eps)

    m = _rms_norm(x, p["mlp_norm"], sz.norm_eps).reshape(b * s, d)
    if sz.routed[layer]:
        f = routed_part(p, m, sz, mode)
        if sz.shared_experts:
            f = f + _swiglu(m, p["shared_wg"], p["shared_wi"],
                            p["shared_wo"], mode)
    else:
        f = _swiglu(m, p["wg"], p["wi"], p["wo_mlp"], mode)
    return x + _rms_norm(f, p["post_mlp_norm"], sz.norm_eps).reshape(b, s, d)


def hidden(weights, tokens, sz: Sizes, mode: str = "f32"):
    """tokens [B,S] -> final-norm hidden states [B,S,D]."""
    x = weights["embed"][tokens].astype(jnp.float32) * sz.embed_scale
    for layer, p in enumerate(weights["blocks"]):
        x = _block(p, x, layer, sz, mode)
    return _rms_norm(x, weights["final_norm"], sz.norm_eps)


def logits_at(weights, tokens, positions, sz: Sizes, mode: str = "f32"):
    """tokens [B,S], positions [B] -> logits [B,V] at those positions."""
    x = hidden(weights, tokens, sz, mode)
    rows = jnp.take_along_axis(x, positions[:, None, None], axis=1)[:, 0]
    return _linear("rd,dv->rv", rows, weights["unembed"], mode)


def layer_out(weights, x, layer: int, sz: Sizes, mode: str = "f32"):
    """One layer on hidden states x [B,S,D]: what the test of the
    shares compares."""
    return _block(weights["blocks"][layer], x, layer, sz, mode)
