"""Plain reference for the `minicpm_sala` architecture (OpenBMB
MiniCPM-SALA): weights from a seed and the forward pass, in float32
`jax.numpy` at `precision=highest`. Imports nothing of `ray_tpu` and
takes nothing it made.

The layers, as the papers and MiniCPM's `modeling_minicpm.py` have them
(recalled without a network: Lightning Attention-2, arXiv:2401.04658;
InfLLM-V2, arXiv:2509.24663, as MiniCPM4 ships it, arXiv:2506.07900;
what the published `config.json` does not settle is listed under
`assumed` in the configuration file). `h = RMSNorm(x)` opens each half
of a layer; `r = scale_depth / sqrt(published depth)`.

- `x = E[tokens] * scale_emb`; `logits = (RMSNorm(x) * dim_model_base /
  hidden_size) W_head`.
- `lightning-attn` layer: `q, k, v = h Wq, h Wk, h Wv` `[S, N, H]`;
  RMSNorm over the head size on `q` and `k` (one scale each, shared
  over heads); RoPE on `q, k`; for head `n` the slope `s_n = 2^(-8 (n +
  1) / N)` and `o_t = sum over u <= t of exp(-s_n (t - u)) (q_t . k_u /
  sqrt(H)) v_u`, no softmax and no normaliser; heads merged, `o =
  RMSNorm(o)`, `o = o * sigmoid(h Wgate)`, `x = x + r (o Wo)`.
- `minicpm4` layer: `q` at `N` heads, `k, v` at `G` KV heads, q/k
  RMSNorm, no position encoding; query head `n` reads KV group `n // (N
  / G)`. Up to `dense_len` tokens plain causal softmax attention. Past
  it, for query `t` and group `g`: compressed keys `c_j = mean(k[stride
  j : stride j + kernel])`, visible iff `stride j + kernel - 1 <= t`;
  `a = softmax over visible j of (q . c_j / sqrt(H))` by head (zero
  where none is visible), summed over the group's heads; a block of
  `block` keys scores the largest `a` among the compressed keys whose
  tokens overlap it; with `b_t = t // block` the first `init_blocks`
  blocks and the `window / block` blocks up to `b_t` are always taken,
  blocks past `b_t` never, and the highest scores among the rest fill
  `top_k` (ties to the lower index); `o = softmax over keys u <= t of
  the taken blocks`. Then `o = o * sigmoid(h Wgate)`, `x = x + r (o Wo)`.
- MLP of either: `m = RMSNorm(x)`; `x = x + r (silu(m Wg) * (m Wu)) Wd`.

**It shares no algorithm with the program.** The lightning layer is
the quadratic form `((q k^T) * decay mask) v`, one head at a time in
blocks of query rows: no scan, no chunk, no state. The sparse layer
gathers each compressed key's tokens and takes their mean, ranks the
blocks by counting how many beat each, builds the mask of visible keys
and runs masked softmax attention over all keys in blocks of query
rows. Weights stay in the stored type and are cast up a matrix at a
time; the SwiGLU runs in blocks of rows: a 32,768-token prompt fits
beside 5.64 GB of weights.

Modes: `f32`; `int8`, the control of the correctness check (every
linear layer's operands rounded to int8 per tensor, the nearest
precision below the bfloat16 the configuration states); and two
planted faults for calibration only: `no_select` (the sparse layers
take the latest unforced blocks whatever their scores) and `no_decay`
(every slope 0).

The weight tree is the one the program loads: `embed [V,D]`,
`blocks[i]` of `attn_norm, wq [D,N,H], wk [D,K,H], wv, wgate [D,N,H],
q_norm [H], k_norm [H]`, on a lightning layer `out_norm [N*H]`, then
`wo [N,H,D], mlp_norm, wg, wi [D,F], wo_mlp [F,D]`; `final_norm`,
`unembed [D,V]`.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST
ROWS = 512              # query rows a block of either mixer
MLP_ROWS = 4096         # rows a block of the SwiGLU

MODES = ("f32", "int8", "no_select", "no_decay")


class Sizes(NamedTuple):
    vocab: int
    d_model: int
    heads: int
    kv_heads: int                   # of a sparse layer
    head_dim: int
    d_ff: int
    rope_theta: float
    norm_eps: float
    embed_scale: float
    residual_scale: float
    logit_scale: float
    sparse_rope: bool
    lightning_rope: bool
    kinds: Tuple[str, ...]          # by layer: "sparse" or "lightning"
    kernel: int
    stride: int
    block: int
    top_k: int
    window: int
    init_blocks: int
    dense_len: int
    dtype: str

    @property
    def layers(self) -> int:
        return len(self.kinds)

    @classmethod
    def from_config(cls, config: dict) -> "Sizes":
        """From a configuration file's HF keys. `published` names the
        depth the residual scale is reckoned from, `sparse_config` the
        sparse layers' sizes."""
        kinds = config["mixer_types"]
        if len(kinds) != config["num_hidden_layers"]:
            raise ValueError("mixer_types does not name every layer")
        if (config["lightning_nh"], config["lightning_nkv"],
                config["lightning_head_dim"]) != (
                config["num_attention_heads"],
                config["num_attention_heads"], config["head_dim"]):
            raise ValueError("lightning layers with heads of their own")
        depth = config.get("published", {}).get(
            "num_hidden_layers", config["num_hidden_layers"])
        sparse = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
                  "topk": 64, "window_size": 2048, "init_blocks": 1,
                  "dense_len": 8192, **config.get("sparse_config", {})}
        return cls(
            vocab=config["vocab_size"], d_model=config["hidden_size"],
            heads=config["num_attention_heads"],
            kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"], d_ff=config["intermediate_size"],
            rope_theta=float(config["rope_theta"]),
            norm_eps=float(config["rms_norm_eps"]),
            embed_scale=float(config["scale_emb"]),
            residual_scale=config["scale_depth"] / math.sqrt(depth),
            logit_scale=config["dim_model_base"] / config["hidden_size"],
            sparse_rope=config["attn_use_rope"],
            lightning_rope=config["lightning_use_rope"],
            kinds=tuple({"minicpm4": "sparse",
                         "lightning-attn": "lightning"}[k] for k in kinds),
            kernel=sparse["kernel_size"], stride=sparse["kernel_stride"],
            block=sparse["block_size"], top_k=sparse["topk"],
            window=sparse["window_size"], init_blocks=sparse["init_blocks"],
            dense_len=sparse["dense_len"],
            dtype=config["torch_dtype"])


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------

def leaf_table(sz: Sizes) -> list:
    """[(path, shape, kind)] in a fixed order; a leaf's index in it is
    folded into the seed's key, so any leaf can be made again alone.
    `kind`: "ones", a float (the standard deviation) or an int (the
    fan-in: normal / sqrt(fan_in))."""
    d, n, h, f = sz.d_model, sz.heads, sz.head_dim, sz.d_ff
    table = [(("embed",), (sz.vocab, d), 0.02),
             (("final_norm",), (d,), "ones"),
             (("unembed",), (d, sz.vocab), d)]
    for i, kind in enumerate(sz.kinds):
        k = n if kind == "lightning" else sz.kv_heads
        leaves = [
            ("attn_norm", (d,), "ones"), ("wq", (d, n, h), d),
            ("wk", (d, k, h), d), ("wv", (d, k, h), d),
            ("wgate", (d, n, h), d), ("q_norm", (h,), "ones"),
            ("k_norm", (h,), "ones")]
        if kind == "lightning":
            leaves.append(("out_norm", (n * h,), "ones"))
        leaves += [("wo", (n, h, d), n * h), ("mlp_norm", (d,), "ones"),
                   ("wg", (d, f), d), ("wi", (d, f), d),
                   ("wo_mlp", (f, d), f)]
        table += [(("blocks", i, name), shape, kind_)
                  for name, shape, kind_ in leaves]
    return table


def make_leaf(key, index: int, shape, kind, dtype) -> jax.Array:
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


def _rows_of(s: int, most: int) -> int:
    """Rows a block: the largest divisor of `s` that is at most `most`."""
    return max(r for r in range(1, min(s, most) + 1) if s % r == 0)


def _row_blocks(fn, s: int, most: int):
    """`fn(first row, row positions [R])` over the rows of a sequence
    in equal blocks -> the blocks' results laid end to end on axis 1."""
    rows = _rows_of(s, most)
    out = jax.lax.map(lambda i: fn(i * rows, i * rows + jnp.arange(rows)),
                      jnp.arange(s // rows))        # [count, B, R, ...]
    out = jnp.moveaxis(out, 0, 1)
    return out.reshape(out.shape[0], s, *out.shape[3:])


def _take_rows(x, first, most: int):
    """The block of rows of x [B,S,...] that begins at `first`."""
    return jax.lax.dynamic_slice_in_dim(
        x, first, _rows_of(x.shape[1], most), 1)


def _lightning(q, k, v, slopes):
    """q, k, v [B,S,N,H] -> [B,S,N,H]: for head n, `o_t = sum over u <=
    t of exp(-slopes[n] (t - u)) (q_t . k_u / sqrt(H)) v_u`."""
    s, h = q.shape[1], q.shape[-1]
    u = jnp.arange(s)

    def head(n):
        qn, kn, vn = q[:, :, n], k[:, :, n], v[:, :, n]

        def rows(first, t):
            gap = (t[:, None] - u[None, :]).astype(jnp.float32)
            decay = jnp.where(gap >= 0, jnp.exp(
                -slopes[n] * jnp.maximum(gap, 0.0)), 0.0)
            scores = jnp.einsum("bqh,bkh->bqk", _take_rows(qn, first, ROWS),
                                kn, precision=_HIGHEST) / math.sqrt(h)
            return jnp.einsum("bqk,bkh->bqh", scores * decay[None], vn,
                              precision=_HIGHEST)

        return _row_blocks(rows, s, ROWS)

    return jnp.moveaxis(jax.lax.map(head, jnp.arange(q.shape[2])), 0, 2)


def taken_blocks(q, k, t, sz: Sizes, mode: str = "f32"):
    """The sparse layer's choice for the query rows `q [B,R,E,H]` (one
    group's heads) at positions `t [R]` against that group's keys `k
    [B,S,H]` -> bool [B,R,blocks]."""
    s, h = k.shape[1], k.shape[-1]
    count = (s - sz.kernel) // sz.stride + 1
    tokens = sz.stride * jnp.arange(count)[:, None] + jnp.arange(sz.kernel)
    c = jnp.mean(k[:, tokens], axis=2)                      # [B,J,H]
    scores = jnp.einsum("breh,bjh->brej", q, c,
                        precision=_HIGHEST) / math.sqrt(h)
    j = jnp.arange(count)
    visible = (sz.stride * j + sz.kernel - 1 <= t[:, None])[None, :, None]
    a = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
    a = jnp.sum(jnp.where(visible, a, 0.0), axis=2)         # [B,R,J]
    n_blocks = -(-s // sz.block)
    blocks = jnp.arange(n_blocks)
    # compressed key j overlaps block b iff its tokens do: those from
    # the first that ends inside b to the last that begins inside it
    # (index `count` reads a zero: no such compressed key)
    lowest = -(-(sz.block * blocks - sz.kernel + 1) // sz.stride)
    over = lowest[:, None] + jnp.arange(
        (sz.block + sz.kernel) // sz.stride - 1)            # [blocks, W]
    over = jnp.where((over >= 0) & (over < count), over, count)
    a = jnp.concatenate([a, jnp.zeros_like(a[..., :1])], axis=-1)
    score = jnp.max(a[..., over], axis=-1)                  # [B,R,blocks]
    b_t = (t // sz.block)[:, None]
    forced = (blocks < sz.init_blocks) | (blocks > b_t - sz.window // sz.block)
    allowed = blocks <= b_t                                 # [R, blocks]
    if mode == "no_select":        # the planted fault: the latest win
        score = jnp.broadcast_to(blocks.astype(jnp.float32), score.shape)
    key = jnp.where(forced, jnp.inf, score)
    beats = (key[..., None, :] > key[..., :, None]) | (
        (key[..., None, :] == key[..., :, None])
        & (blocks[None, :] < blocks[:, None]))
    rank = jnp.sum(beats & allowed[:, None, :], axis=-1)
    return allowed & (rank < sz.top_k)


def _sparse(q, k, v, sz: Sizes, mode: str):
    """q [B,S,N,H], k and v [B,S,G,H] -> [B,S,N,H]."""
    b, s, n, h = q.shape
    g = k.shape[2]
    e = n // g
    u = jnp.arange(s)

    def group(gi):
        qg = jax.lax.dynamic_slice_in_dim(q, gi * e, e, 2)  # [B,S,E,H]
        kg, vg = k[:, :, gi], v[:, :, gi]

        def rows(first, t):
            qr = _take_rows(qg, first, ROWS)
            seen = u[None, :] <= t[:, None]                 # [R,S]
            if s > sz.dense_len:
                taken = taken_blocks(qr, kg, t, sz, mode)
                seen = seen[None] & jnp.take_along_axis(
                    taken, jnp.broadcast_to(
                        (u // sz.block)[None, None, :],
                        (b, t.shape[0], s)), axis=2)        # [B,R,S]
            else:
                seen = seen[None]

            def head(qe):                                   # [B,R,H]
                logits = jnp.einsum("bqh,bkh->bqk", qe, kg,
                                    precision=_HIGHEST) / math.sqrt(h)
                probs = jax.nn.softmax(
                    jnp.where(seen, logits, -jnp.inf), axis=-1)
                probs = jnp.where(seen, probs, 0.0)
                return jnp.einsum("bqk,bkh->bqh", probs, vg,
                                  precision=_HIGHEST)

            out = jax.lax.map(head, jnp.moveaxis(qr, 2, 0))  # [E,B,R,H]
            return jnp.moveaxis(out, 0, 2)                   # [B,R,E,H]

        return _row_blocks(rows, s, ROWS)                    # [B,S,E,H]

    out = jax.lax.map(group, jnp.arange(g))                  # [G,B,S,E,H]
    return jnp.moveaxis(out, 0, 2).reshape(b, s, n, h)


def _swiglu(m, wg, wi, wo, mode):
    """m [T,D] in blocks of rows."""
    def rows(first, _t):
        part = _take_rows(m[None], first, MLP_ROWS)[0]
        gate = jax.nn.silu(_linear("rd,df->rf", part, wg, mode))
        up = _linear("rd,df->rf", part, wi, mode)
        return _linear("rf,fd->rd", gate * up, wo, mode)[None]

    return _row_blocks(rows, m.shape[0], MLP_ROWS)[0]


def _block(p, x, layer: int, sz: Sizes, mode: str):
    b, s, d = x.shape
    kind = sz.kinds[layer]
    a = _rms_norm(x, p["attn_norm"], sz.norm_eps)
    q = _linear("bsd,dnh->bsnh", a, p["wq"], mode)
    k = _linear("bsd,dkh->bskh", a, p["wk"], mode)
    v = _linear("bsd,dkh->bskh", a, p["wv"], mode)
    gate = _linear("bsd,dnh->bsnh", a, p["wgate"], mode)
    q = _rms_norm(q, p["q_norm"], sz.norm_eps)
    k = _rms_norm(k, p["k_norm"], sz.norm_eps)
    if sz.lightning_rope if kind == "lightning" else sz.sparse_rope:
        q, k = _rope(q, sz.rope_theta), _rope(k, sz.rope_theta)
    if kind == "lightning":
        slopes = 2.0 ** (-8.0 * (jnp.arange(sz.heads) + 1.0) / sz.heads)
        if mode == "no_decay":      # the planted fault
            slopes = jnp.zeros_like(slopes)
        o = _lightning(q, k, v, slopes)
        o = _rms_norm(o.reshape(b, s, -1), p["out_norm"],
                      sz.norm_eps).reshape(o.shape)
    else:
        o = _sparse(q, k, v, sz, mode)
    o = o * jax.nn.sigmoid(gate)
    x = x + sz.residual_scale * _linear("bsnh,nhd->bsd", o, p["wo"], mode)
    m = _rms_norm(x, p["mlp_norm"], sz.norm_eps).reshape(b * s, d)
    f = _swiglu(m, p["wg"], p["wi"], p["wo_mlp"], mode)
    return x + sz.residual_scale * f.reshape(b, s, d)


def hidden(weights, tokens, sz: Sizes, mode: str = "f32"):
    """tokens [B,S] -> final-norm hidden states [B,S,D]."""
    if mode not in MODES:
        raise ValueError(f"no mode {mode!r} (has {MODES})")
    x = weights["embed"][tokens].astype(jnp.float32) * sz.embed_scale
    for layer, p in enumerate(weights["blocks"]):
        x = _block(p, x, layer, sz, mode)
    return _rms_norm(x, weights["final_norm"], sz.norm_eps)


def logits_at(weights, tokens, positions, sz: Sizes, mode: str = "f32"):
    """tokens [B,S], positions [B] -> logits [B,V] at those positions."""
    x = hidden(weights, tokens, sz, mode)
    rows = jnp.take_along_axis(x, positions[:, None, None], axis=1)[:, 0]
    return _linear("rd,dv->rv", rows * sz.logit_scale, weights["unembed"],
                   mode)


def layer_out(weights, x, layer: int, sz: Sizes, mode: str = "f32"):
    """One layer on hidden states x [B,S,D]."""
    return _block(weights["blocks"][layer], x, layer, sz, mode)
