"""Plain reference for the `mistral` architecture: weights from a seed,
forward pass, next-token loss, and the AdamW update, in float32
`jax.numpy` at `precision=highest`. Imports nothing of `ray_tpu` and
takes nothing it made.

Follows Mistral-7B-v0.1 (arXiv:2310.06825; RMSNorm, RoPE, GQA, SwiGLU,
untied head). Departures, all shared with the program so that the two
compute the same function (they are listed in the configuration files
under `assumed`): RoPE rotates interleaved pairs (2i, 2i+1) as in Su et
al., where the HF checkpoint stores the half-split permutation of the
same weights; RMSNorm's epsilon is a size of the configuration; the
sliding window equals the longest sequence run, so it is causal
attention.

The weight tree is the checkpoint layout the program loads:
`embed [V,D]`, `blocks[i]` of `attn_norm, wq [D,N,H], wk [D,K,H], wv,
wo [N,H,D], mlp_norm, wg [D,F], wi [D,F], wo_mlp [F,D]`, `final_norm`,
`unembed [D,V]`.

So that it fits beside nothing else on the chip it works in blocks:
attention one query head at a time, the MLP and the loss in blocks of rows,
each block recomputed in the backward pass.

`mode="int8"` is the control of the correctness check: the same
mathematics with every linear layer's operands rounded to int8 per
tensor (straight-through in the backward pass), the nearest precision
below the bfloat16 the configuration states. `mode="tp_partial"` is a
planted fault: what one of two tensor-parallel shards computes when the
exchange with its partner is left out (half the heads, half of the MLP's
hidden units, the partial sums never added).
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_HIGHEST = jax.lax.Precision.HIGHEST
_ROW_BLOCK = 512


class Sizes(NamedTuple):
    vocab: int
    d_model: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    rope_theta: float
    norm_eps: float

    @classmethod
    def from_config(cls, config: dict) -> "Sizes":
        """From a configuration file's HF keys."""
        return cls(
            vocab=config["vocab_size"], d_model=config["hidden_size"],
            layers=config["num_hidden_layers"],
            heads=config["num_attention_heads"],
            kv_heads=config["num_key_value_heads"],
            head_dim=config["hidden_size"] // config["num_attention_heads"],
            d_ff=config["intermediate_size"],
            rope_theta=float(config["rope_theta"]),
            norm_eps=float(config["rms_norm_eps"]))


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------

def leaf_table(sz: Sizes) -> list:
    """[(path, shape, kind)] in a fixed order; a leaf's index in it is
    folded into the seed's key, so any leaf can be made again alone."""
    d, n, k, h, f = sz.d_model, sz.heads, sz.kv_heads, sz.head_dim, sz.d_ff
    table = [(("embed",), (sz.vocab, d), 0.02),
             (("final_norm",), (d,), "ones"),
             (("unembed",), (d, sz.vocab), d)]
    for i in range(sz.layers):
        for name, shape, kind in (
                ("attn_norm", (d,), "ones"), ("wq", (d, n, h), d),
                ("wk", (d, k, h), d), ("wv", (d, k, h), d),
                ("wo", (n, h, d), n * h), ("mlp_norm", (d,), "ones"),
                ("wg", (d, f), d), ("wi", (d, f), d),
                ("wo_mlp", (f, d), f)):
            table.append((("blocks", i, name), shape, kind))
    return table


def make_leaf(key, index: int, shape, kind) -> jax.Array:
    """`kind` is "ones", a float (the standard deviation) or an int (the
    fan-in: normal / sqrt(fan_in))."""
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    std = kind if isinstance(kind, float) else 1.0 / math.sqrt(kind)
    return jax.random.normal(jax.random.fold_in(key, index), shape,
                             jnp.float32) * std


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


def tree_leaves_in_table_order(sz: Sizes, tree) -> list:
    return [tree["blocks"][p[1]][p[2]] if p[0] == "blocks" else tree[p[0]]
            for p, _s, _k in leaf_table(sz)]


def make_weights(key, sz: Sizes) -> Dict[str, Any]:
    """The whole tree from one key. Traceable: jit it to make the
    weights on the device in one call."""
    return build_tree(sz, [make_leaf(key, i, shape, kind) for i, (
        _p, shape, kind) in enumerate(leaf_table(sz))])


def make_tokens(seed: int, step: int, batch: int, seq: int,
                vocab: int) -> np.ndarray:
    """The feed: step `step`'s rows, all different, from the seed."""
    rng = np.random.default_rng([seed, step])
    return rng.integers(0, vocab, (batch, seq), dtype=np.int32)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _int8(x):
    """Per-tensor absmax rounding to 127 levels; the gradient passes
    straight through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def _linear(spec: str, a, w, mode: str):
    if mode == "int8":
        a, w = _int8(a), _int8(w)
    return jnp.einsum(spec, a, w, precision=_HIGHEST)


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


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


def _attend_head(q, k, v):
    """One query head: q, k and v [B,S,H] -> [B,S,H]; causal."""
    s = q.shape[1]
    logits = jnp.einsum("bqh,bkh->bqk", q, k, precision=_HIGHEST)
    logits = logits / math.sqrt(q.shape[-1])
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    logits = jnp.where(causal[None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bqk,bkh->bqh", probs, v, precision=_HIGHEST)


def _attention(q, k, v):
    """q [B,S,N,H], k and v [B,S,K,H]: grouped-query causal attention
    (query head j reads KV head j // (N/K)), one query head at a time."""
    rep = q.shape[2] // k.shape[2]
    heads_first = [jnp.moveaxis(x, 2, 0) for x in (
        q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2))]
    out = jax.lax.map(lambda t: jax.checkpoint(_attend_head)(*t),
                      tuple(heads_first))
    return jnp.moveaxis(out, 0, 2)


def _in_row_blocks(fn, *row_arrays):
    """fn over [rows, ...] arrays in blocks of rows, each recomputed in
    the backward pass."""
    rows = row_arrays[0].shape[0]
    block = math.gcd(rows, _ROW_BLOCK)
    split = [a.reshape(rows // block, block, *a.shape[1:])
             for a in row_arrays]
    out = jax.lax.map(lambda t: jax.checkpoint(fn)(*t), tuple(split))
    return jax.tree.map(lambda o: o.reshape(rows, *o.shape[2:]), out)


def _block(p, x, sz: Sizes, mode: str):
    b, s, d = x.shape
    h = _rms_norm(x, p["attn_norm"], sz.norm_eps)
    q = _rope(_linear("bsd,dnh->bsnh", h, p["wq"], mode), sz.rope_theta)
    k = _rope(_linear("bsd,dkh->bskh", h, p["wk"], mode), sz.rope_theta)
    v = _linear("bsd,dkh->bskh", h, p["wv"], mode)
    attended = _attention(q, k, v)
    if mode == "tp_partial":
        attended = attended.at[:, :, sz.heads // 2:].set(0.0)
    x = x + _linear("bsnh,nhd->bsd", attended, p["wo"], mode)

    def mlp(rows):
        gate = jax.nn.silu(_linear("rd,df->rf", rows, p["wg"], mode))
        up = _linear("rd,df->rf", rows, p["wi"], mode)
        if mode == "tp_partial":
            up = up.at[:, sz.d_ff // 2:].set(0.0)
        return _linear("rf,fd->rd", gate * up, p["wo_mlp"], mode)

    h = _rms_norm(x, p["mlp_norm"], sz.norm_eps)
    return x + _in_row_blocks(mlp, h.reshape(b * s, d)).reshape(b, s, d)


def hidden(weights, tokens, sz: Sizes, mode: str = "f32"):
    """tokens [B,S] -> final-norm hidden states [B,S,D]."""
    x = weights["embed"][tokens]
    for p in weights["blocks"]:
        x = jax.checkpoint(lambda p, x: _block(p, x, sz, mode))(p, x)
    return _rms_norm(x, weights["final_norm"], sz.norm_eps)


def logits_at(weights, tokens, positions, sz: Sizes, mode: str = "f32"):
    """tokens [B,S], positions [B] -> logits [B,V] at those positions."""
    x = hidden(weights, tokens, sz, mode)
    rows = jnp.take_along_axis(x, positions[:, None, None], axis=1)[:, 0]
    return _linear("rd,dv->rv", rows, weights["unembed"], mode)


def loss(weights, tokens, sz: Sizes, mode: str = "f32",
         row_weight=None):
    """Mean next-token cross-entropy over the B x (S-1) predicting
    positions, or over those that `row_weight` [B,S] keeps."""
    b, s = tokens.shape
    x = hidden(weights, tokens, sz, mode).reshape(b * s, -1)
    targets = jnp.roll(tokens, -1, axis=1)
    keep = jnp.ones((b, s), jnp.float32) if row_weight is None else \
        row_weight.astype(jnp.float32)
    keep = keep.at[:, -1].set(0.0)      # the last position predicts nothing

    def nll(rows, target, w):
        logits = _linear("rd,dv->rv", rows, weights["unembed"], mode)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, target[:, None], axis=-1)[:, 0] * w

    per_row = _in_row_blocks(nll, x, targets.reshape(-1), keep.reshape(-1))
    return jnp.sum(per_row) / jnp.sum(keep)


# --------------------------------------------------------------------------
# one optimizer step: global-norm clip, then AdamW (Loshchilov & Hutter)
# under a cosine schedule, as the traffic file's `optimizer` states them
# --------------------------------------------------------------------------

def learning_rate(opt: dict, count):
    """Step `count` (from 0) of linear warm-up then cosine decay to 0."""
    warm, total, peak = opt["warmup_steps"], opt["total_steps"], opt["lr"]
    decay = max(total, warm + 1) - warm
    frac = jnp.clip((count - warm) / decay, 0.0, 1.0)
    cosine = peak * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
    return jnp.where(count < warm, peak * count / max(warm, 1), cosine)


def leaf_norms(sz: Sizes, tree) -> jax.Array:
    """Norm of each leaf, in `leaf_table` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in tree_leaves_in_table_order(sz, tree)])


def change_norms(sz: Sizes, tree, key) -> np.ndarray:
    """Norm of each leaf's distance from the seed's weights, in
    `leaf_table` order. The seed's leaf is made again one at a time, so
    only one is ever held beside `tree`."""
    out = []
    for index, ((_p, shape, kind), leaf) in enumerate(zip(
            leaf_table(sz), tree_leaves_in_table_order(sz, tree))):
        out.append(_change_norm(leaf, key, index, shape, kind))
    return np.asarray(jax.device_get(out), np.float64)


@jax.jit(static_argnames=("shape", "kind"))
def _change_norm(leaf, key, index, shape, kind):
    start = make_leaf(key, index, shape, kind)
    return jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32) - start)))


def apply_update(weights, mu, nu, grads, count, *, sz: Sizes, opt: dict):
    """Clip by the global norm, then AdamW. -> (weights, mu, nu, per-leaf
    norms of the clipped gradient in `leaf_table` order). `count` is the
    number of steps taken before this one. A program of its own, apart
    from the gradient's, so that the activations and the moments are
    never on the chip together."""
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(grads)))
    clip = opt["clip"]
    grads = jax.tree.map(
        lambda g: jnp.where(norm < clip, g, g / norm * clip), grads)
    b1, b2, t = opt["b1"], opt["b2"], count + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda n, g: b2 * n + (1 - b2) * g * g, nu, grads)
    lr = learning_rate(opt, count)

    def update(w, m, n):
        m_hat = m / (1 - b1 ** t)
        n_hat = n / (1 - b2 ** t)
        return w - lr * (m_hat / (jnp.sqrt(n_hat) + opt["eps"])
                         + opt["weight_decay"] * w)

    return (jax.tree.map(update, weights, mu, nu), mu, nu,
            leaf_norms(sz, grads))


# --------------------------------------------------------------------------
# placement: one chip, or every weight split over all the chips given
# --------------------------------------------------------------------------

def weight_shardings(sz: Sizes, devices) -> Optional[Dict[str, Any]]:
    """None on one device. On several, each leaf split along its longest
    axis that divides (the vectors are copied), over one mesh axis."""
    if len(devices) == 1:
        return None
    mesh = Mesh(np.asarray(devices), ("x",))
    n = len(devices)

    def place(shape):
        axes = [None] * len(shape)
        fits = [i for i, s in enumerate(shape) if s % n == 0 and s >= 1024]
        if len(shape) > 1 and fits:
            axes[max(fits, key=lambda i: shape[i])] = "x"
        return NamedSharding(mesh, P(*axes))

    return build_tree(sz, [place(shape) for _p, shape, _k in leaf_table(sz)])
