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

Arithmetic: the matmuls take their operands in the caller's type (the
state rounded to it for its product) and accumulate in float32; the
state, the decay factors and the mask are float32.

The operands are read where the projections left them, ``[B, S, N *
H]`` viewed by head through the block index: no transpose in HBM.
Work and bytes are linear in ``S``: the kernel is bound by reading q,
k, v and writing o once (``benchmark/costs_sala.py::lightning_cost``).

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


def _lightning_kernel(slopes_ref, q_ref, k_ref, v_ref, o_ref, state_ref,
                      decay_ref):
    # q_ref, k_ref, v_ref, o_ref: [1, C, H]; state_ref [H, H] and
    # decay_ref [C, C] float32, kept from chunk to chunk of one head
    chunk = q_ref.shape[1]
    slope = slopes_ref[pl.program_id(1)]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)
        i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        gap = jnp.maximum(i - j, 0).astype(jnp.float32)
        decay_ref[...] = jnp.where(i >= j, jnp.exp(-slope * gap), 0.0)

    q, k, v = q_ref[0], k_ref[0], v_ref[0]
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


def _lightning_call(slopes, q, k, v, *, chunk, interpret):
    b, s, n, h = q.shape
    padded = _round_up(s, chunk)

    def by_head(x):         # [B, S, N, H] -> [B, padded, N * H]
        x = x.reshape(b, s, n * h)
        return jnp.pad(x, ((0, 0), (0, padded - s), (0, 0)))

    block = pl.BlockSpec((1, chunk, h), lambda bi, ni, ci, _s: (bi, ci, ni))
    call = pl.pallas_call(
        _lightning_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n, padded // chunk),
            in_specs=[block, block, block],
            out_specs=block,
            scratch_shapes=[pltpu.VMEM((h, h), jnp.float32),
                            pltpu.VMEM((chunk, chunk), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, padded, n * h), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )

    # the kernel's name in every device trace (see ops/moe.py::_gmm_call)
    def lightning_attn(*operands):
        return call(*operands)

    out = jax.jit(lightning_attn)(slopes, by_head(q), by_head(k), by_head(v))
    return out[:, :s].reshape(b, s, n, h)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def lightning_attention(q, k, v, slopes, chunk: Optional[int] = None,
                        interpret: Optional[bool] = None):
    """``[B, S, N, H]`` -> the same: head ``n`` decays by
    ``exp(-slopes[n])`` a token. ``chunk`` left at None is chosen from
    the shape (``choose_chunk``); the sequence is padded to whole
    chunks with zero keys, which add nothing."""
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(f"lightning attention takes q, k and v alike, "
                         f"got {q.shape}, {k.shape}, {v.shape}")
    return _for_lowering_platform(
        functools.partial(_lightning_call,
                          chunk=chunk or choose_chunk(q.shape[1])),
        interpret, jnp.asarray(slopes, jnp.float32), q, k, v)


def _lightning_fwd(q, k, v, slopes, chunk, interpret):
    return lightning_attention(q, k, v, slopes, chunk, interpret), None


def _lightning_bwd(chunk, interpret, residuals, g):
    raise NotImplementedError(
        "ray_tpu.ops.lightning_attention has no backward kernel: the "
        "lightning layer runs forward only (serving); training through it "
        "needs the reverse scan over chunks (ROADMAP R5)")


lightning_attention.defvjp(_lightning_fwd, _lightning_bwd)
