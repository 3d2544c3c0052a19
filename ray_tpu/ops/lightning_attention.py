"""Lightning attention: causal linear attention with a per-head
exponential decay, as a Pallas TPU kernel.

ABSENT from the reference (attention enters via torch in hosted
workloads, SURVEY.md §2.5). For head ``n`` with slope ``s_n``

    o_t = sum over u <= t of exp(-s_n (t - u)) (q_t . k_u) v_u * scale

with no softmax and no normaliser: the state ``M_t = exp(-s_n) M_(t-1)
+ k_t^T v_t`` (head_dim x head_dim) and ``o_t = q_t M_t * scale``,
``scale = 1 / sqrt(head_dim)``
(Lightning Attention-2, arXiv:2401.04658). The kernel walks the
sequence in chunks of ``C`` tokens, one (batch, head) at a time, and
carries the state from chunk to chunk in VMEM in float32: inside a
chunk the quadratic form under the decay mask ``exp(-s (i - j))``,
``i >= j``; from the chunks before, ``(q_i exp(-s (i + 1))) M``; and
``M <- exp(-s C) M + (k_j exp(-s (C - 1 - j)))^T v``. No factor greater
than 1 is ever formed, so nothing overflows at any length.

It is the one kernel of the tree that loads every tile of q and of k
exactly once, so a layer's own first steps on q and k can be done on
the tile in VMEM in place of passes over memory: the q/k RMS norm and
RoPE, where the caller hands in the scales and the tables. The grid
stays ``(batch, head, chunk)``, so a table's tile is fetched again for
each head: the kernel is bound by its own arithmetic and the fetch
hides behind it (PERF.md §6, PR 38).

Arithmetic: the matmuls take their operands in the caller's type (the
state rounded to it for its product) and accumulate in float32; the
state, the decay factors and the mask are float32.

The operands are read where the projections left them, ``[B, S, N *
H]`` viewed by head through the block index: no transpose in HBM.
Work and bytes are linear in ``S``: the least the recurrence needs is
reading q, k, v and writing o once
(``benchmark/costs_sala.py::lightning_cost``).

Forward only: a gradient asked of it raises by name.

Layout: ``[B, S, N, H]``; ``slopes [N]`` float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash_attention import (
    _LANE, _NN, _NT, _TN, _dot, _for_lowering_platform, _round_up)

# No chunk beyond this: the quadratic part of a chunk costs C a token,
# the state's part H, so past a few times the head size a longer chunk
# only adds work.
_MAX_CHUNK = 256


def decay_slopes(n_heads: int) -> np.ndarray:
    """ALiBi's slopes for ``n_heads`` heads: ``2^(-8 (n + 1) / N)``,
    steepest first."""
    return (2.0 ** (-8.0 * (np.arange(n_heads) + 1) / n_heads)).astype(
        np.float32)


def choose_chunk(seq: int) -> int:
    """The chunk from the shape, as ``_choose_blocks`` chooses flash's:
    the sequence pads to a lane multiple and the chunk is the largest
    multiple of 128 that divides it, at most ``_MAX_CHUNK``."""
    padded = _round_up(seq, _LANE)
    return max(c for c in range(_LANE, min(padded, _MAX_CHUNK) + 1, _LANE)
               if padded % c == 0)


def lightning_reference(q, k, v, slopes):
    """The quadratic masked form in ``jax.numpy``, float32."""
    s = q.shape[1]
    gap = (jnp.arange(s)[:, None] - jnp.arange(s)[None, :]).astype(
        jnp.float32)
    decay = jnp.where(
        gap >= 0,
        jnp.exp(-jnp.asarray(slopes, jnp.float32)[:, None, None]
                * jnp.maximum(gap, 0.0)), 0.0)                  # [N, S, S]
    scores = jnp.einsum("bqnh,bknh->bnqk", q, k,
                        preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST)
    out = jnp.einsum("bnqk,bknh->bqnh", scores * decay * q.shape[-1] ** -0.5,
                     v.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return out.astype(q.dtype)


def _lightning_kernel(slopes_ref, *refs, norm_eps, rope):
    # q_ref, k_ref, v_ref, o_ref: [1, C, H], one head's chunk; where the
    # layer's q/k norm is done here scales_ref [2, H] float32 (q's, k's),
    # where its RoPE is cos_ref, sin_ref [1, C, H] float32 and swap_ref
    # [H, H]; state_ref [H, H] and decay_ref [C, C] float32, kept from
    # chunk to chunk of one head
    q_ref, k_ref, v_ref = refs[:3]
    o_ref, state_ref, decay_ref = refs[-3:]
    scales_ref = refs[3] if norm_eps is not None else None
    cos_ref, sin_ref, swap_ref = refs[-6:-3] if rope else (None,) * 3
    chunk = q_ref.shape[1]
    slope = slopes_ref[pl.program_id(1)]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)
        i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        gap = jnp.maximum(i - j, 0).astype(jnp.float32)
        decay_ref[...] = jnp.where(i >= j, jnp.exp(-slope * gap), 0.0)

    def prepared(x, which):
        # the layer's RMS norm over the head and its rotation, each step
        # rounded as ``models/transformer.py`` rounds it
        if norm_eps is not None:
            x32 = x.astype(jnp.float32)
            var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
            x = (x32 * jax.lax.rsqrt(var + norm_eps)).astype(x.dtype)
            x = (x.astype(jnp.float32)
                 * scales_ref[which:which + 1, :]).astype(x.dtype)
        if rope:
            turned = _dot(x, swap_ref[...], _NN)
            x = (x.astype(jnp.float32) * cos_ref[0]
                 + turned * sin_ref[0]).astype(x.dtype)
        return x

    q, k, v = prepared(q_ref[0], 0), prepared(k_ref[0], 1), v_ref[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0).astype(
        jnp.float32)
    q_in = (q * jnp.exp(-slope * (row + 1.0))).astype(q.dtype)
    k_out = (k * jnp.exp(-slope * (chunk - 1.0 - row))).astype(k.dtype)
    state = state_ref[...]
    within = _dot(q, k, _NT) * decay_ref[...]               # [C, C]
    o = _dot(within.astype(v.dtype), v, _NN)
    o = o + _dot(q_in, state.astype(q.dtype), _NN)
    o_ref[0] = (o * q.shape[-1] ** -0.5).astype(o_ref.dtype)
    whole = jnp.exp(-slope * jnp.full((1, 1), chunk, jnp.float32))
    state_ref[...] = state * whole + _dot(k_out, v, _TN)


# The kernel's name in every device trace is this function's (a Mosaic
# call takes the name of the innermost jitted function round it, see
# ops/moe.py::_gmm_call). One function for the module, traced once for
# each set of shapes and settings: a program's lightning layers then
# share one lowering of the body, which every ``pallas_call`` made anew
# lowers anew before the program can be looked up in the compile cache
# (PERF.md §6, PR 34 and PR 38).
@functools.partial(jax.jit, static_argnames=(
    "chunk", "norm_eps", "rope", "interpret"))
def lightning_attn(slopes, q, k, v, *prologue, chunk, norm_eps, rope,
                   interpret):
    # q, k, v [B, whole chunks, N * H]; ``prologue``: the norm's scales
    # [2, H] float32 if ``norm_eps``, then cos, sin [B, whole chunks, H]
    # float32 and the pair swap [H, H] if ``rope``
    b, padded, width = q.shape
    n = slopes.shape[0]
    h = width // n
    block = pl.BlockSpec((1, chunk, h), lambda bi, ni, ci, _s: (bi, ci, ni))
    table = pl.BlockSpec((1, chunk, h), lambda bi, ni, ci, _s: (bi, ci, 0))
    specs = [block] * 3
    if norm_eps is not None:
        specs.append(pl.BlockSpec((2, h), lambda *_: (0, 0)))
    if rope:
        specs += [table, table, pl.BlockSpec((h, h), lambda *_: (0, 0))]
    return pl.pallas_call(
        functools.partial(_lightning_kernel, norm_eps=norm_eps, rope=rope),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n, padded // chunk),
            in_specs=specs,
            out_specs=block,
            scratch_shapes=[pltpu.VMEM((h, h), jnp.float32),
                            pltpu.VMEM((chunk, chunk), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(slopes, q, k, v, *prologue)


def _lightning_call(slopes, q, k, v, *prologue, chunk, norm_eps, rope,
                    interpret):
    b, s, n, h = q.shape
    padded = _round_up(s, chunk)

    def by_head(x):         # [B, S, ...] -> [B, whole chunks, N * H or H]
        x = x.reshape(b, s, -1)
        return jnp.pad(x, ((0, 0), (0, padded - s), (0, 0)))

    operands = [by_head(q), by_head(k), by_head(v)]
    if norm_eps is not None:
        scales, *prologue = prologue
        operands.append(scales.astype(jnp.float32))
    if rope:
        cos, sin, swap = prologue
        operands += [by_head(cos), by_head(sin), swap.astype(q.dtype)]
    out = lightning_attn(slopes, *operands, chunk=chunk, norm_eps=norm_eps,
                         rope=rope, interpret=interpret)
    return out[:, :s].reshape(b, s, n, h)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 7))
def lightning_attention(q, k, v, slopes, chunk: Optional[int] = None,
                        interpret: Optional[bool] = None, qk_scales=None,
                        norm_eps: float = 1e-6, rope=None):
    """``[B, S, N, H]`` -> the same: head ``n`` decays by
    ``exp(-slopes[n])`` a token. ``chunk`` left at None is chosen from
    the shape (``choose_chunk``); the sequence is padded to whole
    chunks with zero keys, which add nothing.

    A layer's own first steps on q and k, done on each tile in VMEM as
    it is loaded, so that q and k pass memory once, as the projections
    wrote them: with ``qk_scales [2, H]`` (q's and k's, in the compute
    type) the RMS norm over each head at ``norm_eps``; with ``rope``
    (``cos``, ``sin``, each ``[B, S, H]`` float32, a pair of lanes
    alike, and the signed pair swap ``[H, H]``, as
    ``models/transformer.py`` makes them) RoPE on the pairs ``(2i, 2i +
    1)``; the norm first. Both round where that module's ``rms_norm``
    and ``rope`` round."""
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(f"lightning attention takes q, k and v alike, "
                         f"got {q.shape}, {k.shape}, {v.shape}")
    prologue = ([] if qk_scales is None else [qk_scales]) + list(rope or ())
    return _for_lowering_platform(
        functools.partial(
            _lightning_call, chunk=chunk or choose_chunk(q.shape[1]),
            norm_eps=None if qk_scales is None else norm_eps,
            rope=rope is not None),
        interpret, jnp.asarray(slopes, jnp.float32), q, k, v, *prologue)


def _lightning_fwd(q, k, v, slopes, chunk, interpret, qk_scales, norm_eps,
                   rope):
    return lightning_attention(q, k, v, slopes, chunk, interpret, qk_scales,
                               norm_eps, rope), None


def _lightning_bwd(chunk, interpret, norm_eps, residuals, g):
    raise NotImplementedError(
        "ray_tpu.ops.lightning_attention has no backward kernel: the "
        "lightning layer runs forward only (serving); training through it "
        "needs the reverse scan over chunks (ROADMAP R5)")


lightning_attention.defvjp(_lightning_fwd, _lightning_bwd)
