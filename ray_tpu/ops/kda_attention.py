"""Delta-rule linear attention with a decay for every key channel (the
recurrence of Kimi Delta Attention, arXiv:2510.26692), as a Pallas TPU
kernel.

ABSENT from the reference (attention enters via torch in hosted
workloads, SURVEY.md §2.5). A head keeps a state ``S [H_k, H_v]``,
float32, and at token ``t`` with log-decays ``g_t [H_k] <= 0`` and a
step ``beta_t`` in ``[0, 1]``

    S' = Diag(exp g_t) S_(t-1)
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

(``kda_reference``: exactly that, token by token). The kernel walks the
sequence in chunks of ``C`` tokens, one (batch, head) at a time, the
state carried in VMEM. With ``G_i = sum over u <= i of g_u`` inside the
chunk, by channel, and ``S_0`` the state the chunk enters with:

    A_ij = beta_i sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])    i > j
    (I + A) [W | U] = beta [K exp G | V]      (unit lower triangular)
    U~ = U - W S_0
    O  = (Q exp G) S_0 + tril[sum_c q_i[c] k_j[c] exp(G_i[c] - G_j[c])] U~
    S_C = Diag(exp G_C) S_0 + (K exp(G_C - G))^T U~

**No factor greater than 1 is ever formed**, whatever the decays: a
chunk is cut into sub-blocks of ``_SUB`` rows. Between a sub-block and
the rows before it ``exp(G_i - G_j) = exp(G_i - G_r) exp(G_r - G_j)``
with ``r`` the sub-block's first row, both exponents at most 0, so the
sum over channels is one matmul. Inside a sub-block nothing factors
safely (``exp(G_r - G_j)`` overflows float32 within 16 steps of a
log-decay of -6), so column ``j`` is made on the vector unit from
``exp(min(G_i - G_j, 0))``, and the same pass eliminates it from the
triangular system (forward substitution, column by column): the
inverse is never formed. What that costs and where the time goes:
PERF.md §6, PR 40.

Arithmetic: the matmuls take their operands in the caller's type (the
state and the solved rows rounded to it for their products) and
accumulate in float32; the decays, their sums, the solve and the state
are float32.

The operands are read where the projections left them, ``[B, S, N *
H]`` viewed by head through the block index: no transpose in HBM.

Forward only: a gradient asked of it raises by name.

Layout: ``q, k, v [B, S, N, H]``, ``g [B, S, N, H]`` float32, ``beta
[B, S, N]`` float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash_attention import (
    _NN, _NT, _TN, _dot, _for_lowering_platform, _round_up)

# Tokens a chunk: the solve and the reach of a sub-block grow with the
# chunk, the state's products shrink (PERF.md §6, PR 40).
CHUNK = 64
# Rows a sub-block: the rows whose pairs are made channel by channel.
_SUB = 16


def kda_reference(q, k, v, g, beta):
    """The recurrence token by token (``lax.scan``), float32."""
    def step(state, xs):
        qt, kt, vt, gt, bt = xs             # [B, N, H] each, bt [B, N]
        state = state * jnp.exp(gt)[..., None]
        seen = jnp.einsum("bnk,bnkv->bnv", kt, state,
                          precision=jax.lax.Precision.HIGHEST)
        state = state + (bt[..., None] * kt)[..., None] * (
            vt - seen)[..., None, :]
        return state, jnp.einsum("bnk,bnkv->bnv", qt, state,
                                 precision=jax.lax.Precision.HIGHEST)

    b, _s, n, h = q.shape
    xs = [jnp.moveaxis(x.astype(jnp.float32), 1, 0)
          for x in (q, k, v, g, beta)]
    _, out = jax.lax.scan(
        step, jnp.zeros((b, n, h, v.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(out, 0, 1).astype(q.dtype)


def _kda_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, state_ref,
                gsum_ref, solved_ref, pairs_ref):
    # q_ref, k_ref, v_ref, o_ref [1, C, H], one head's chunk; g_ref [1, C,
    # H] and beta_ref [1, C, N] float32. Kept from chunk to chunk of one
    # head: state_ref [H, H] float32, the state transposed (values x
    # keys). Of this chunk: gsum_ref [C, H] the decays summed, solved_ref
    # [C, 2 H] the system's right side and then its solution W | U,
    # pairs_ref [C, C] the decayed q . k pairs.
    chunk, h = q_ref.shape[1], q_ref.shape[2]
    dt = q_ref.dtype
    f32 = jnp.float32
    head = pl.program_id(1)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    def beta_of(rows):      # this head's column of beta, [rows, 1]
        mine = jax.lax.broadcasted_iota(
            jnp.int32, rows.shape, 1) == head
        return jnp.sum(jnp.where(mine, rows, 0.0), axis=1, keepdims=True)

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    gsum = jax.lax.dot_general(
        (row >= col).astype(f32), g_ref[0], _NN,
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=f32)
    gsum_ref[...] = gsum
    decay = jnp.exp(gsum)
    k32 = k_ref[0].astype(f32)
    beta = beta_of(beta_ref[0])
    solved_ref[:, :h] = beta * k32 * decay
    solved_ref[:, h:] = beta * v_ref[0].astype(f32)

    local = jax.lax.broadcasted_iota(jnp.int32, (_SUB, 1), 0)
    at = jax.lax.broadcasted_iota(jnp.int32, (_SUB, chunk), 1)

    def sub_block(i, carry):
        first = pl.multiple_of(i * _SUB, _SUB)
        rows = pl.ds(first, _SUB)
        gi = gsum_ref[rows, :]
        g0 = gsum_ref[pl.ds(first, 1), :]
        ki = k_ref[0, rows, :].astype(f32)
        qi = q_ref[0, rows, :].astype(f32)
        bi = beta_of(beta_ref[0, rows, :])
        # the rows before the sub-block: one matmul through its first row
        inner = jnp.exp(gi - g0)
        behind = (k_ref[0].astype(f32) * jnp.exp(
            jnp.minimum(g0 - gsum_ref[...], 0.0))).astype(dt)
        before = at < first
        a = jnp.where(before, _dot((ki * inner).astype(dt), behind, _NT)
                      * bi, 0.0)
        pairs = jnp.where(before, _dot((qi * inner).astype(dt), behind,
                                       _NT), 0.0)
        x = solved_ref[rows, :] - _dot(
            a.astype(dt), solved_ref[...].astype(dt), _NN)
        # its own rows, a column at a time: the pair's decay, then the
        # column out of the rows under it
        for j in range(_SUB):
            e = jnp.exp(jnp.minimum(gi - gi[j:j + 1], 0.0)) * ki[j:j + 1]
            a_col = bi * jnp.sum(ki * e, axis=1, keepdims=True)
            q_col = jnp.sum(qi * e, axis=1, keepdims=True)
            x = x - jnp.where(local > j, a_col, 0.0) * x[j:j + 1]
            pairs = jnp.where((at == first + j) & (local >= j), q_col,
                              pairs)
        solved_ref[rows, :] = x
        pairs_ref[rows, :] = pairs
        return carry

    jax.lax.fori_loop(0, chunk // _SUB, sub_block, 0)

    state = state_ref[...]
    carried = state.astype(dt)
    u = solved_ref[:, h:] - _dot(solved_ref[:, :h].astype(dt), carried, _NT)
    u = u.astype(dt)
    o = _dot((q_ref[0].astype(f32) * decay).astype(dt), carried, _NT)
    o_ref[0] = (o + _dot(pairs_ref[...].astype(dt), u, _NN)).astype(
        o_ref.dtype)
    last = gsum_ref[pl.ds(chunk - 1, 1), :]
    k_out = (k32 * jnp.exp(last - gsum)).astype(dt)
    state_ref[...] = state * jnp.exp(last) + _dot(u, k_out, _TN)


# The kernel's name in every device trace is this function's (a Mosaic
# call takes the name of the innermost jitted function round it), and
# a program's layers share one lowering of the body
# (ops/lightning_attention.py::lightning_attn).
@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def kda_attn(q, k, v, g, beta, *, heads, interpret):
    # q, k, v, g [B, whole chunks, N * H]; beta [B, whole chunks, N]
    b, padded, width = q.shape
    h = width // heads
    block = pl.BlockSpec((1, CHUNK, h), lambda bi, ni, ci: (bi, ci, ni))
    return pl.pallas_call(
        _kda_kernel,
        grid=(b, heads, padded // CHUNK),
        in_specs=[block] * 4 + [
            pl.BlockSpec((1, CHUNK, heads), lambda bi, ni, ci: (bi, ci, 0))],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((h, h), jnp.float32),
                        pltpu.VMEM((CHUNK, h), jnp.float32),
                        pltpu.VMEM((CHUNK, 2 * h), jnp.float32),
                        pltpu.VMEM((CHUNK, CHUNK), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v, g, beta)


def _kda_call(q, k, v, g, beta, *, interpret):
    b, s, n, h = q.shape
    padded = _round_up(s, CHUNK)

    def by_head(x):             # [B, S, ...] -> [B, whole chunks, N * H]
        x = x.reshape(b, s, -1)
        return jnp.pad(x, ((0, 0), (0, padded - s), (0, 0)))

    out = kda_attn(by_head(q), by_head(k), by_head(v), by_head(g),
                   by_head(beta), heads=n, interpret=interpret)
    return out[:, :s].reshape(b, s, n, h)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def kda_attention(q, k, v, g, beta, interpret: Optional[bool] = None):
    """``q, k, v [B, S, N, H]``, ``g [B, S, N, H]`` (log-decays, at most
    0) and ``beta [B, S, N]`` -> ``[B, S, N, H]``. The sequence is
    padded to whole chunks with tokens of no decay and no step, which
    leave the state as it is."""
    if not (q.shape == k.shape == v.shape == g.shape
            and beta.shape == q.shape[:3]):
        raise ValueError(f"kda attention takes q, k, v and g alike and a "
                         f"beta a head, got {q.shape}, {k.shape}, "
                         f"{v.shape}, {g.shape}, {beta.shape}")
    return _for_lowering_platform(
        _kda_call, interpret, q, k, v, g.astype(jnp.float32),
        beta.astype(jnp.float32))


def _kda_fwd(q, k, v, g, beta, interpret):
    return kda_attention(q, k, v, g, beta, interpret), None


def _kda_bwd(interpret, residuals, grad):
    raise NotImplementedError(
        "ray_tpu.ops.kda_attention has no backward kernel: the layer runs "
        "forward only (serving); training through it needs the reverse "
        "scan over chunks (ROADMAP R5)")


kda_attention.defvjp(_kda_fwd, _kda_bwd)
