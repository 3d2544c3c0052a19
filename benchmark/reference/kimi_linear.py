"""Plain reference for the `kimi_linear` architecture (Moonshot
Kimi-Linear): weights from a seed and the forward pass, in float32
`jax.numpy` at `precision=highest`. Imports nothing of `ray_tpu` and
takes nothing it made.

The layers, as the Kimi Linear technical report (arXiv:2510.26692) and
the checkpoint's `modeling_kimi.py` have them (recalled without a
network; what the published `config.json` does not settle is listed
under `assumed` in the configuration file). `a = RMSNorm(x)` opens the
attention half of a layer, `m = RMSNorm(x)` the MLP half; every RMSNorm
takes `rms_norm_eps`.

- `kda` layer (`linear_attn_config.kda_layers`, 1-based): `q, k, v = a
  Wq, a Wk, a Wv` `[S, N, H]`; each through its own causal convolution
  by channel (`K` taps, no bias, zeros before position 0) and SiLU; `q
  <- q / sqrt(sum q^2 + 1e-6) / sqrt(H)` and `k <- k / sqrt(sum k^2 +
  1e-6)` by head; `g = -exp(A_log[n]) softplus((a Wf_a) Wf_b +
  dt_bias)` by head and channel, `beta = sigmoid(a W_beta)` by head;
  for each head, from `S_0 = 0` (`H x H`): `S' = Diag(exp g_t)
  S_(t-1)`, `S_t = S' + beta_t k_t (v_t - S'^T k_t)^T`, `o_t = S_t^T
  q_t`; `o <- RMSNorm_H(o) * sigmoid((a Wg_a) Wg_b + b_g)` by head; `x
  = x + o Wo`.
- `mla` layer (`full_attn_layers`): `q = a Wq` `[S, N, nope + rope]`;
  `(c, k_s) = a Wkv_a` (`kv_lora_rank` and `rope` wide); `c <-
  RMSNorm(c)`; `(k_n, v) = c Wkv_b` `[S, N, nope]` and `[S, N, v]`; `k =
  [k_n ; k_s]`, `k_s` the same for every head, nothing rotated
  (`mla_use_nope`); `o = softmax(q k^T / sqrt(nope + rope) + causal
  mask) v`; `x = x + o Wo`.
- MLP: the first `first_k_dense_replace` layers a SwiGLU at
  `intermediate_size`; the others `s = sigmoid(m Wr)` over all
  published experts, `I = top_k(s + b)` (`b` selects and does not
  weigh), `w = routed_scaling_factor * s[I] / (sum s[I] + 1e-20)`, `f =
  shared(m) + sum over e in I and held of w_e FFN_e(m)`, every FFN a
  SwiGLU at `moe_intermediate_size`; `x = x + f`.
- Final RMSNorm and the untied head.

**The share.** `Sizes.experts` is how many experts are held and
`Sizes.first_expert` the first of them; the router keeps the published
width `Sizes.router_experts`. Experts in `I` that are not held add
nothing, and that partial `x` goes on to the next layer. With
`first_expert=0` and `experts == router_experts` it is the uncut layer
(`uncut`).

**It shares no algorithm with the program.** The delta rule runs token
by token (`lax.scan`): no chunk, no triangular solve, no sub-block. The
latent attention is a masked softmax over all keys, one head at a time
in blocks of query rows; the delta rule's heads run eight at a time,
their projections with them. An expert is computed on the rows routed to
it, gathered in blocks of 128. Weights stay in the stored type and are
cast up a matrix at a time; the SwiGLUs run in blocks of rows: a
32,768-token prompt fits beside 6.9 GB of weights.

Modes: `f32`; and `int8`, the control of the correctness check (every
linear layer's operands rounded to int8 per tensor, the nearest
precision below the bfloat16 the configuration states; the router
stays float32: it decides which experts run, not how precisely).

The weight tree is the one the program loads: `embed [V,D]`,
`blocks[i]` of `attn_norm`, then on a `kda` layer `wq, wk, wv [D,N,H],
conv_q, conv_k, conv_v [N,H,K], wf_a [D,R], wf_b [R,N,H], dt_bias
[N,H], a_log [N], w_beta [D,N], wg_a [D,R], wg_b [R,N,H], bg [N,H],
out_norm [H], wo [N,H,D]` and on an `mla` layer `wq [D,N,nope+rope],
wkv_a [D,rank+rope], kv_norm [rank], wkv_b [rank,N,nope+v], wo
[N,v,D]`; `mlp_norm`; `wg, wi [D,F], wo_mlp [F,D]` (dense) or `router
[D,E], router_bias [E], shared_wg, shared_wi [D,Fs], shared_wo [Fs,D],
experts_wg, experts_wi [held,D,Fe], experts_wo [held,Fe,D]`;
`final_norm`, `unembed [D,V]`. Every matrix is drawn normal / sqrt(fan_in)
but a routed expert's down projection, drawn at a quarter of that
(`EXPERT_OUT_SHRINK`, below, says why); the decays' `A_log` and
`dt_bias` as the checkpoint's initialisation draws them.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST
ROWS = 512              # query rows a block of the latent attention
MLP_ROWS = 4096         # rows a block of a SwiGLU
HEAD_GROUP = 8          # heads of a kda layer worked at a time
_EXPERT_ROWS = 128
L2_EPS = 1e-6

MODES = ("f32", "int8")


class Sizes(NamedTuple):
    vocab: int
    d_model: int
    heads: int
    head_dim: int           # of a kda layer: keys and values alike
    d_ff: int
    d_ff_expert: int
    router_experts: int     # the router's width: the published experts
    first_expert: int       # the first expert held here
    experts: int            # how many are held here
    top_k: int
    shared_experts: int
    route_scale: float
    norm_eps: float
    conv: int               # taps of the short convolutions
    rank: int               # the decay's and the gate's inner width
    kv_rank: int
    nope: int
    shared: int             # key lanes all heads share (`qk_rope_head_dim`)
    value: int
    kinds: Tuple[str, ...]          # by layer: "kda" or "mla"
    routed: Tuple[bool, ...]        # by layer: experts, or the dense MLP
    dtype: str

    @property
    def layers(self) -> int:
        return len(self.kinds)

    @classmethod
    def from_config(cls, config: dict) -> "Sizes":
        """From a configuration file's HF keys. `expert_parallel`
        (`size`, `rank`) says which share `num_experts` is."""
        share = config.get("expert_parallel", {"size": 1, "rank": 0})
        held = config["num_experts"]
        linear = config["linear_attn_config"]
        depth = config["num_hidden_layers"]
        kinds = {**{i: "mla" for i in linear["full_attn_layers"]},
                 **{i: "kda" for i in linear["kda_layers"]}}
        if sorted(kinds) != list(range(1, depth + 1)):
            raise ValueError("linear_attn_config does not name every layer")
        return cls(
            vocab=config["vocab_size"], d_model=config["hidden_size"],
            heads=linear["num_heads"], head_dim=linear["head_dim"],
            d_ff=config["intermediate_size"],
            d_ff_expert=config["moe_intermediate_size"],
            router_experts=held * share["size"],
            first_expert=held * share["rank"], experts=held,
            top_k=config["num_experts_per_token"],
            shared_experts=config["num_shared_experts"],
            route_scale=float(config["routed_scaling_factor"]),
            norm_eps=float(config["rms_norm_eps"]),
            conv=linear["short_conv_kernel_size"], rank=linear["head_dim"],
            kv_rank=config["kv_lora_rank"], nope=config["qk_nope_head_dim"],
            shared=config["qk_rope_head_dim"], value=config["v_head_dim"],
            kinds=tuple(kinds[i + 1] for i in range(depth)),
            routed=tuple(i >= config["first_k_dense_replace"]
                         for i in range(depth)),
            dtype=config["torch_dtype"])


def uncut(sz: Sizes) -> Sizes:
    """The same model holding every expert the router names."""
    return sz._replace(first_expert=0, experts=sz.router_experts)


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------

ROUTER_BIAS_STD = 0.02
GATE_BIAS_STD = 0.02
# A routed expert's down projection is drawn at 1 / (EXPERT_OUT_SHRINK
# sqrt(fan_in)). At 1 / sqrt(fan_in) a routed expert's weighted output
# is a third of a layer's whole update, every router tie that rounding
# turns (0.8 % of a layer's tokens for the experts held here, from
# bfloat16 rounding of one layer's input alone) moves its token by that
# much, the moved tokens feed the states and keys of every later token,
# and over twelve routed layers a bfloat16 program stands 0.16-0.74
# from the float32 reference through turned ties alone, where the int8
# control reads 0.79-1.47 (my chip runs, PR 40: PERF.md section 6): the
# check then measures the chaos of top-8 ties and not the arithmetic.
# At 4 the program reads 0.07-0.15 and the control 0.47-0.93. What the
# check sees of the routed layer is no worse for it: a route scale left
# out reads 1.3 x the program's largest at 4 and 0.96 x at 1, the whole
# routed part missing 1.85 x and 1.69 x (faults planted in this
# reference, same runs); the float32 tests on the CPU hold that layer.
EXPERT_OUT_SHRINK = 4


def leaf_table(sz: Sizes) -> list:
    """[(path, shape, kind)] in a fixed order; a leaf's index in it is
    folded into the seed's key, so any leaf can be made again alone.
    `kind`: "ones", a float (the standard deviation), an int (the
    fan-in: normal / sqrt(fan_in)), "rate" (`A_log = log U(1, 16)`) or
    "step" (`dt_bias = softplus^-1(dt)`, `dt` log-uniform in `[0.001,
    0.1]`), the last two as the checkpoint's initialisation draws
    them."""
    d, n, h, r = sz.d_model, sz.heads, sz.head_dim, sz.rank
    fe, fs = sz.d_ff_expert, sz.shared_experts * sz.d_ff_expert
    table = [(("embed",), (sz.vocab, d), 0.02),
             (("final_norm",), (d,), "ones"),
             (("unembed",), (d, sz.vocab), d)]
    for i, kind in enumerate(sz.kinds):
        leaves = [("attn_norm", (d,), "ones")]
        if kind == "kda":
            leaves += [
                ("wq", (d, n, h), d), ("wk", (d, n, h), d),
                ("wv", (d, n, h), d),
                ("conv_q", (n, h, sz.conv), sz.conv),
                ("conv_k", (n, h, sz.conv), sz.conv),
                ("conv_v", (n, h, sz.conv), sz.conv),
                ("wf_a", (d, r), d), ("wf_b", (r, n, h), r),
                ("dt_bias", (n, h), "step"), ("a_log", (n,), "rate"),
                ("w_beta", (d, n), d), ("wg_a", (d, r), d),
                ("wg_b", (r, n, h), r), ("bg", (n, h), GATE_BIAS_STD),
                ("out_norm", (h,), "ones"), ("wo", (n, h, d), n * h)]
        else:
            leaves += [
                ("wq", (d, n, sz.nope + sz.shared), d),
                ("wkv_a", (d, sz.kv_rank + sz.shared), d),
                ("kv_norm", (sz.kv_rank,), "ones"),
                ("wkv_b", (sz.kv_rank, n, sz.nope + sz.value), sz.kv_rank),
                ("wo", (n, sz.value, d), n * sz.value)]
        leaves.append(("mlp_norm", (d,), "ones"))
        if sz.routed[i]:
            leaves += [
                ("router", (d, sz.router_experts), d),
                ("router_bias", (sz.router_experts,), ROUTER_BIAS_STD),
                ("shared_wg", (d, fs), d), ("shared_wi", (d, fs), d),
                ("shared_wo", (fs, d), fs),
                ("experts_wg", (sz.experts, d, fe), d),
                ("experts_wi", (sz.experts, d, fe), d),
                ("experts_wo", (sz.experts, fe, d),
                 EXPERT_OUT_SHRINK ** 2 * fe)]
        else:
            leaves += [("wg", (d, sz.d_ff), d), ("wi", (d, sz.d_ff), d),
                       ("wo_mlp", (sz.d_ff, d), sz.d_ff)]
        table += [(("blocks", i, name), shape, kind_)
                  for name, shape, kind_ in leaves]
    return table


def make_leaf(key, index: int, shape, kind, dtype) -> jax.Array:
    if kind == "ones":
        return jnp.ones(shape, dtype)
    key = jax.random.fold_in(key, index)
    if kind == "rate":
        return jnp.log(jax.random.uniform(
            key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)
    if kind == "step":
        step = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)
    std = kind if isinstance(kind, float) else 1.0 / math.sqrt(kind)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


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


def _operand(x, mode: str):
    """A linear layer's operand as the mode has it."""
    return _int8(x) if mode == "int8" else x


def _linear(spec: str, a, w, mode: str):
    """`w` arrives in the stored type and is cast up here, where it is
    used."""
    return jnp.einsum(spec, _operand(a, mode),
                      _operand(w.astype(jnp.float32), mode),
                      precision=_HIGHEST)


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rows_of(s: int, most: int) -> int:
    """Rows a block: the largest divisor of `s` that is at most `most`."""
    return max(r for r in range(1, min(s, most) + 1) if s % r == 0)


def _row_blocks(fn, s: int, most: int):
    """`fn(first row)` over the rows of a sequence in equal blocks ->
    the blocks' results laid end to end on axis 1."""
    rows = _rows_of(s, most)
    out = jax.lax.map(lambda i: fn(i * rows), jnp.arange(s // rows))
    out = jnp.moveaxis(out, 0, 1)                   # [B, count, R, ...]
    return out.reshape(out.shape[0], s, *out.shape[3:])


def _take_rows(x, first, most: int):
    """The block of rows of x [B,S,...] that begins at `first`."""
    return jax.lax.dynamic_slice_in_dim(
        x, first, _rows_of(x.shape[1], most), 1)


def _short_conv(x, taps):
    """x [B,S,N,H], taps [N,H,K]: `silu(sum_i taps[.., i] x_(t-K+1+i))`,
    zeros before position 0."""
    s, width = x.shape[1], taps.shape[-1]
    taps = taps.astype(jnp.float32)
    x = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0), (0, 0)))
    return jax.nn.silu(sum(x[:, i:i + s] * taps[..., i]
                           for i in range(width)))


def delta_rule(q, k, v, g, beta):
    """q, k, v, g [B,S,N,H], beta [B,S,N] -> o [B,S,N,H]: the
    recurrence, token by token."""
    def step(state, xs):
        qt, kt, vt, gt, bt = xs                     # [B,N,H]; bt [B,N]
        state = state * jnp.exp(gt)[..., None]
        seen = jnp.sum(kt[..., None] * state, axis=-2)
        state = state + (bt[..., None] * kt)[..., None] * (
            vt - seen)[..., None, :]
        return state, jnp.sum(qt[..., None] * state, axis=-2)

    b, _s, n, h = q.shape
    _, out = jax.lax.scan(
        step, jnp.zeros((b, n, h, h), jnp.float32),
        [jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)])
    return jnp.moveaxis(out, 0, 1)


def _heads_of(x, first, count: int, axis: int):
    return jax.lax.dynamic_slice_in_dim(x, first, count, axis)


def _kda(a, p, sz: Sizes, mode: str):
    """`HEAD_GROUP` heads at a time, projections and all, so that a
    32,768-token prompt's q, k, v and decays never stand whole."""
    h, count = sz.head_dim, _rows_of(sz.heads, HEAD_GROUP)
    a_in = _operand(a, mode)
    wide = {name: _operand(p[name].astype(jnp.float32), mode)
            for name in ("wq", "wk", "wv", "wf_b", "wg_b")}
    rate_in, gate_in = (_operand(_linear("bsd,dr->bsr", a, p[name], mode),
                                 mode) for name in ("wf_a", "wg_a"))
    beta = jax.nn.sigmoid(_linear("bsd,dn->bsn", a, p["w_beta"], mode))

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                 + L2_EPS)

    def group(first):
        def mine(name, axis):
            source = wide[name] if name in wide else p[name]
            return _heads_of(source, first, count, axis).astype(jnp.float32)

        q, k, v = (_short_conv(
            jnp.einsum("bsd,dnh->bsnh", a_in, mine("w" + name, 1),
                       precision=_HIGHEST), mine("conv_" + name, 0))
            for name in "qkv")
        rate = jnp.einsum("bsr,rnh->bsnh", rate_in, mine("wf_b", 1),
                          precision=_HIGHEST)
        g = -jnp.exp(mine("a_log", 0))[:, None] * jax.nn.softplus(
            rate + mine("dt_bias", 0))
        o = delta_rule(unit(q) / math.sqrt(h), unit(k), v, g,
                       _heads_of(beta, first, count, 2))
        gate = jnp.einsum("bsr,rnh->bsnh", gate_in, mine("wg_b", 1),
                          precision=_HIGHEST) + mine("bg", 0)
        return _rms_norm(o, p["out_norm"], sz.norm_eps) * jax.nn.sigmoid(gate)

    out = jax.lax.map(group, jnp.arange(0, sz.heads, count))
    out = jnp.moveaxis(out, 0, 2)                   # [B,S,groups,count,H]
    return out.reshape(*out.shape[:2], sz.heads, h)


def _mla(a, p, sz: Sizes, mode: str):
    """One head at a time, its projections too."""
    s = a.shape[1]
    a_in = _operand(a, mode)
    wq = _operand(p["wq"].astype(jnp.float32), mode)
    wkv_b = _operand(p["wkv_b"].astype(jnp.float32), mode)
    latent = _linear("bsd,dr->bsr", a, p["wkv_a"], mode)
    c_in = _operand(_rms_norm(latent[..., :sz.kv_rank], p["kv_norm"],
                              sz.norm_eps), mode)
    k_shared = latent[..., sz.kv_rank:]                     # [B,S,shared]
    key_at = jnp.arange(s)

    def head(n):
        q = jnp.einsum("bsd,dh->bsh", a_in, _heads_of(wq, n, 1, 1)[:, 0],
                       precision=_HIGHEST)
        up = jnp.einsum("bsr,rh->bsh", c_in, _heads_of(wkv_b, n, 1, 1)[:, 0],
                        precision=_HIGHEST)
        kn = jnp.concatenate([up[..., :sz.nope], k_shared], axis=-1)
        vn = up[..., sz.nope:]

        def rows(first):
            qn = _take_rows(q, first, ROWS)
            at = first + jnp.arange(qn.shape[1])
            logits = jnp.einsum("bqh,bkh->bqk", qn, kn, precision=_HIGHEST)
            logits = logits / math.sqrt(sz.nope + sz.shared)
            logits = jnp.where(key_at[None, :] <= at[:, None], logits,
                               -jnp.inf)
            return jnp.einsum("bqk,bkh->bqh", jax.nn.softmax(logits, -1), vn,
                              precision=_HIGHEST)

        return _row_blocks(rows, s, ROWS)

    return jnp.moveaxis(jax.lax.map(head, jnp.arange(sz.heads)), 0, 2)


def _swiglu_rows(rows, wg, wi, wo, mode):
    gate = jax.nn.silu(_linear("rd,df->rf", rows, wg, mode))
    up = _linear("rd,df->rf", rows, wi, mode)
    return _linear("rf,fd->rd", gate * up, wo, mode)


def _swiglu(m, wg, wi, wo, mode):
    """m [T,D] in blocks of rows."""
    def rows(first):
        return _swiglu_rows(_take_rows(m[None], first, MLP_ROWS)[0], wg, wi,
                            wo, mode)[None]

    return _row_blocks(rows, m.shape[0], MLP_ROWS)[0]


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
            y = _swiglu_rows(m[at], wg, wi, wo, mode)
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
    o = (_kda if sz.kinds[layer] == "kda" else _mla)(a, p, sz, mode)
    x = x + _linear("bsnh,nhd->bsd", o, p["wo"], mode)
    m = _rms_norm(x, p["mlp_norm"], sz.norm_eps).reshape(b * s, d)
    if sz.routed[layer]:
        f = routed_part(p, m, sz, mode)
        if sz.shared_experts:
            f = f + _swiglu(m, p["shared_wg"], p["shared_wi"],
                            p["shared_wo"], mode)
    else:
        f = _swiglu(m, p["wg"], p["wi"], p["wo_mlp"], mode)
    return x + f.reshape(b, s, d)


def hidden(weights, tokens, sz: Sizes, mode: str = "f32"):
    """tokens [B,S] -> final-norm hidden states [B,S,D]."""
    if mode not in MODES:
        raise ValueError(f"no mode {mode!r} (has {MODES})")
    x = weights["embed"][tokens].astype(jnp.float32)
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
