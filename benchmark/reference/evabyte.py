"""Plain reference for the `evabyte` architecture (EvaByte, HKU NLP and
SambaNova): weights from a seed and the forward pass, in float32
`jax.numpy` at `precision=highest`. Imports nothing of `ray_tpu` and
takes nothing it made.

The layer, as the EvaByte release describes it (recalled without a
network: EVA, "Efficient Attention via Control Variates",
arXiv:2302.04542, with its random feature made a learned vector a head;
what the published `config.json` does not settle is listed under
`assumed` in the configuration file). Per head, `d` the head size, `w`
the window, `c` the chunk, `W(t) = t // w`:

- `h = RMSNorm(x)` with scale `1 + g` (`norm_add_unit_offset`); `q, k,
  v = h Wq, h Wk, h Wv` `[S, N, d]`; RoPE on `q` and `k`.
- Chunk `j` holds positions `c j .. c j + c - 1`. With two learned
  vectors a head, `phi` and `mu`: `a = softmax over the chunk's
  positions u of (k_u . phi)`; `vs_j = sum a_u v_u`; `ks_j = mean(k_u)
  + mu`.
- Query `t` attends, under one softmax at scale `1 / sqrt(d)`, to the
  keys `u <= t` with `W(u) = W(t)` and to the summaries `(ks_j, vs_j)`
  of the chunks with `c j // w < W(t)`.
- `x = x + o Wo`; `m = RMSNorm(x)`; `x = x + (silu(m Wg) * (m Wu)) Wd`.
- `logits = RMSNorm(x) W_head`, `W_head [D, heads x V]`: head `p` at
  columns `V p .. V p + V - 1` predicts the byte `1 + p` positions on.

**It shares no algorithm with the program.** The attention is a dense
masked softmax over the concatenated key set `[summaries ; bytes]`, one
head at a time (from that head's projections on) in blocks of query
rows: no window of keys is cut out,
no running maximum is carried, and the summaries come from a gather of
each chunk's positions. Weights stay in the stored type and are cast up
a matrix at a time; the SwiGLU runs in blocks of rows: a 32,768-byte
prompt fits beside 6.50 GB of weights.

Modes: `f32`; `int8`, the control of the correctness check (every
linear layer's operands rounded to int8 per tensor, the nearest
precision below the bfloat16 the configuration states); and two planted
faults for calibration only: `no_far` (no query sees a summary) and
`mean_pool` (`vs_j` the plain mean of the chunk's values, `mu` left out).

The weight tree is the one the program loads: `embed [V,D]`,
`blocks[i]` of `attn_norm [D]` (the offset `g`), `wq, wk, wv [D,N,H]`,
`eva_phi, eva_mu [N,H]`, `wo [N,H,D]`, `mlp_norm`, `wg, wi [D,F]`,
`wo_mlp [F,D]`; `final_norm`, `unembed [D, heads x V]`.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Sequence

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST
ROWS = 512              # query rows a block of the attention
MLP_ROWS = 4096         # rows a block of the SwiGLU

MODES = ("f32", "int8", "no_far", "mean_pool")


class Sizes(NamedTuple):
    vocab: int
    d_model: int
    layers: int
    heads: int
    head_dim: int
    d_ff: int
    pred_heads: int
    window: int
    chunk: int
    rope_theta: float
    norm_eps: float
    dtype: str

    @classmethod
    def from_config(cls, config: dict) -> "Sizes":
        """From a configuration file's HF keys."""
        if config["attention_class"] != "eva":
            raise ValueError(f"no attention_class "
                             f"{config['attention_class']!r}")
        heads = config["num_attention_heads"]
        if config["num_key_value_heads"] != heads:
            raise ValueError("eva layers with grouped KV heads")
        if not (config["norm_add_unit_offset"] and config["fp32_skip_add"]
                and config["fp32_logits"]):
            raise ValueError("the reference is the published arithmetic: "
                             "unit-offset norms, float32 stream and logits")
        return cls(
            vocab=config["vocab_size"], d_model=config["hidden_size"],
            layers=config["num_hidden_layers"], heads=heads,
            head_dim=config.get("head_dim")
            or config["hidden_size"] // heads,
            d_ff=config["intermediate_size"],
            pred_heads=config["num_pred_heads"],
            window=config["window_size"], chunk=config["chunk_size"],
            rope_theta=float(config["rope_theta"]),
            norm_eps=float(config["rms_norm_eps"]),
            dtype=config["torch_dtype"])


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------

def leaf_table(sz: Sizes) -> list:
    """[(path, shape, kind)] in a fixed order; a leaf's index in it is
    folded into the seed's key, so any leaf can be made again alone.
    `kind`: "zeros" (a norm's offset from 1), a float (the standard
    deviation) or an int (the fan-in: normal / sqrt(fan_in))."""
    d, n, h, f = sz.d_model, sz.heads, sz.head_dim, sz.d_ff
    table = [(("embed",), (sz.vocab, d), 0.02),
             (("final_norm",), (d,), "zeros"),
             (("unembed",), (d, sz.pred_heads * sz.vocab), d)]
    for i in range(sz.layers):
        table += [(("blocks", i, name), shape, kind) for name, shape, kind in [
            ("attn_norm", (d,), "zeros"), ("wq", (d, n, h), d),
            ("wk", (d, n, h), d), ("wv", (d, n, h), d),
            ("eva_phi", (n, h), 0.02), ("eva_mu", (n, h), 0.02),
            ("wo", (n, h, d), n * h), ("mlp_norm", (d,), "zeros"),
            ("wg", (d, f), d), ("wi", (d, f), d), ("wo_mlp", (f, d), f)]]
    return table


def make_leaf(key, index: int, shape, kind, dtype) -> jax.Array:
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
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
    """The whole tree from one key, in the configuration's type (`phi`
    and `mu` too: what the program is given is what the reference
    reads). Traceable: jit it to make the weights on the device in one
    call."""
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


def _operand(x, mode: str):
    """A linear layer's operand as the mode has it: cast up from the
    stored type here, where it is used, and rounded whole in `int8`."""
    x = x.astype(jnp.float32)
    return _int8(x) if mode == "int8" else x


def _linear(spec: str, a, w, mode: str):
    return jnp.einsum(spec, _operand(a, mode), _operand(w, mode),
                      precision=_HIGHEST)


def _rms_norm(x, offset, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + offset.astype(jnp.float32))


def _rope(x, theta):
    """x [B,S,H], one head: rotate pairs (2i, 2i+1) by position *
    theta^(-2i/H)."""
    h = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, h, 2, dtype=jnp.float32) / h))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[None], jnp.sin(angles)[None]
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


def summaries(k, v, phi, mu, sz: Sizes, mode: str):
    """One head's `k, v [B,S,H]` -> `ks, vs [B,J,H]`, `J = S // chunk`
    whole chunks (a tail shorter than a chunk lies in the last window,
    which no query sees summarised)."""
    count = k.shape[1] // sz.chunk
    at = sz.chunk * jnp.arange(count)[:, None] + jnp.arange(sz.chunk)
    kc, vc = k[:, at], v[:, at]                             # [B,J,c,H]
    if mode == "mean_pool":     # the planted fault
        return jnp.mean(kc, axis=2), jnp.mean(vc, axis=2)
    a = jax.nn.softmax(jnp.einsum("bjch,h->bjc", kc, phi,
                                  precision=_HIGHEST), axis=-1)
    return (jnp.mean(kc, axis=2) + mu,
            jnp.einsum("bjc,bjch->bjh", a, vc, precision=_HIGHEST))


def _eva(a, p, sz: Sizes, mode: str):
    """The normed stream `a [B,S,D]` through one layer's attention ->
    `[B,S,N,H]`, a head at a time from its projections on (q, k and v
    of all heads never stand whole: at 32,768 bytes they and their
    rotated copies would be 3 GB beside the weights)."""
    s, h = a.shape[1], sz.head_dim
    u = jnp.arange(s)
    j = jnp.arange(s // sz.chunk)
    a = _operand(a, mode)
    wq, wk, wv = (_operand(p[name], mode) for name in ("wq", "wk", "wv"))
    phi, mu = (p[name].astype(jnp.float32) for name in ("eva_phi", "eva_mu"))

    def head(n):
        qn, kn, vn = (jnp.einsum("bsd,dh->bsh", a, w[:, n],
                                 precision=_HIGHEST) for w in (wq, wk, wv))
        qn, kn = _rope(qn, sz.rope_theta), _rope(kn, sz.rope_theta)
        ks, vs = summaries(kn, vn, phi[n], mu[n], sz, mode)
        keys = jnp.concatenate([ks, kn], axis=1)            # [B,J+S,H]
        values = jnp.concatenate([vs, vn], axis=1)

        def rows(first, t):
            own = t[:, None] // sz.window
            far = (sz.chunk * j[None, :]) // sz.window < own
            if mode == "no_far":        # the planted fault
                far = jnp.zeros_like(far)
            near = (u[None, :] <= t[:, None]) & (
                u[None, :] // sz.window == own)
            seen = jnp.concatenate([far, near], axis=1)[None]  # [1,R,J+S]
            logits = jnp.einsum("bqh,bkh->bqk", _take_rows(qn, first, ROWS),
                                keys, precision=_HIGHEST) / math.sqrt(h)
            probs = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf),
                                   axis=-1)
            return jnp.einsum("bqk,bkh->bqh", jnp.where(seen, probs, 0.0),
                              values, precision=_HIGHEST)

        return _row_blocks(rows, s, ROWS)

    return jnp.moveaxis(jax.lax.map(head, jnp.arange(sz.heads)), 0, 2)


def _swiglu(m, wg, wi, wo, mode):
    """m [T,D] in blocks of rows."""
    def rows(first, _t):
        part = _take_rows(m[None], first, MLP_ROWS)[0]
        gate = jax.nn.silu(_linear("rd,df->rf", part, wg, mode))
        up = _linear("rd,df->rf", part, wi, mode)
        return _linear("rf,fd->rd", gate * up, wo, mode)[None]

    return _row_blocks(rows, m.shape[0], MLP_ROWS)[0]


def _block(p, x, sz: Sizes, mode: str):
    b, s, d = x.shape
    o = _eva(_rms_norm(x, p["attn_norm"], sz.norm_eps), p, sz, mode)
    x = x + _linear("bsnh,nhd->bsd", o, p["wo"], mode)
    m = _rms_norm(x, p["mlp_norm"], sz.norm_eps).reshape(b * s, d)
    f = _swiglu(m, p["wg"], p["wi"], p["wo_mlp"], mode)
    return x + f.reshape(b, s, d)


def hidden(weights, tokens, sz: Sizes, mode: str = "f32"):
    """tokens [B,S] -> final-norm hidden states [B,S,D]."""
    if mode not in MODES:
        raise ValueError(f"no mode {mode!r} (has {MODES})")
    x = weights["embed"][tokens].astype(jnp.float32)
    for p in weights["blocks"]:
        x = _block(p, x, sz, mode)
    return _rms_norm(x, weights["final_norm"], sz.norm_eps)


def logits_at(weights, tokens, positions, sz: Sizes, mode: str = "f32"):
    """tokens [B,S], positions [B] -> logits [B, heads x V] at those
    positions, the prediction heads side by side."""
    x = hidden(weights, tokens, sz, mode)
    rows = jnp.take_along_axis(x, positions[:, None, None], axis=1)[:, 0]
    return _linear("rd,dv->rv", rows, weights["unembed"], mode)

