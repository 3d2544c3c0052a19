"""EVA attention: exact softmax inside a query's own window of keys,
one learned summary for every chunk of keys in the windows before it,
one normaliser over both, as two Pallas TPU kernels.

ABSENT from the reference (attention enters via torch in hosted
workloads, SURVEY.md §2.5). The estimator is EVA's ("Efficient
Attention via Control Variates", arXiv:2302.04542) with its random
feature made a learned vector a head, as the EvaByte release describes
it. With window ``w``, chunk ``c`` and ``W(t) = t // w``, per head:

- **summaries**, one a chunk ``j`` (positions ``c j .. c j + c - 1``),
  from two learned vectors ``phi, mu [H]``: ``a = softmax over the
  chunk's keys of (k_u . phi)``; ``vs_j = sum a_u v_u``; ``ks_j =
  mean(k_u) + mu``;
- **attention** of query ``t`` over two sets under one softmax: the
  keys ``u <= t`` of its own window, ``W(u) = W(t)``, and the summaries
  of every chunk of the windows before it, ``c j // w < W(t)``::

      o_t = (sum_L e^(q.k_u s) v_u + sum_F e^(q.ks_j s) vs_j)
            / (sum_L e^(q.k_u s) + sum_F e^(q.ks_j s)),  s = 1/sqrt(H)

  In the first window ``F`` is empty: plain causal attention.

``eva_summaries`` makes the summaries once a layer (one read of K and
V, a sixteenth written). ``eva_attn`` serves a block of queries of a
few heads a grid step: it holds those heads' window of K and V and all
their summaries in VMEM, walks the window's key tiles up to the
diagonal and then the tiles of summaries of the windows before, and
carries one running maximum and one normaliser through both walks.
Every query of a block lies in one window, so the summaries it sees
are a prefix that only the last tile has to mask. Tiles are held
transposed, keys down the sublanes and queries along the lanes, and
the heads of a step run in straight-line code with a head's second
product laid after the next head's softmax, as the flash forward has
it (``ops/flash_attention.py``; PERF.md §6, PR 34). Work is ``w / 2 +
(t // w) w / c`` pairs a query where causal attention has ``t``.

Arithmetic: matmul operands in the caller's type, float32
accumulation; the summaries' weights, both softmaxes' statistics and
the accumulators float32; summaries rounded to the caller's type.

A device trace knows the launches by their HLO instruction names,
``eva_summaries.<n>`` and ``eva_attn.<n>``. Forward only: a gradient
asked of it raises by name.

Layout: ``q, k, v [B, S, N, H]`` (equal head counts); ``phi, mu [N,
H]`` float32.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash_attention import (
    _MASKED, _NT, _TN, _dot, _for_lowering_platform, _round_up)

_MAX_BLOCK = 512        # rows a tile, as the flash kernels' (PR 26)
_HEAD_RUN = 2           # heads a grid step serves, in straight-line code
_VMEM_LIMIT = 64 * 2 ** 20


def pairs(seq: int, window: int, chunk: int) -> Tuple[int, int]:
    """(query, key) pairs inside windows and (query, summary) pairs of
    one head over one sequence, from shapes."""
    t = np.arange(seq, dtype=np.int64)
    return (int((t % window + 1).sum()),
            int((t // window * (window // chunk)).sum()))


def _check(q, k, v, phi, mu, window: int, chunk: int) -> None:
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(f"eva attention takes q, k and v alike, got "
                         f"{q.shape}, {k.shape}, {v.shape}")
    if phi.shape != q.shape[2:] or mu.shape != q.shape[2:]:
        raise ValueError(f"phi and mu are [heads, head_dim] "
                         f"{q.shape[2:]}, got {phi.shape}, {mu.shape}")
    if window % chunk:
        raise ValueError(f"a window of {window} is not whole chunks of "
                         f"{chunk}")


# --------------------------------------------------------------------------
# Plain jax.numpy
# --------------------------------------------------------------------------

def summaries_reference(k, v, phi, mu, chunk: int):
    """``k, v [B, S, N, H]`` (``S`` whole chunks) -> ``ks, vs [B, S /
    chunk, N, H]`` in float32."""
    b, s, n, h = k.shape
    k = k.astype(jnp.float32).reshape(b, s // chunk, chunk, n, h)
    v = v.astype(jnp.float32).reshape(b, s // chunk, chunk, n, h)
    a = jax.nn.softmax(jnp.einsum("bjcnh,nh->bjcn", k,
                                  phi.astype(jnp.float32)), axis=2)
    return (jnp.mean(k, axis=2) + mu.astype(jnp.float32),
            jnp.einsum("bjcn,bjcnh->bjnh", a, v))


def eva_reference(q, k, v, phi, mu, window: int, chunk: int):
    """The same in ``jax.numpy``, a window of queries at a time against
    its own keys and every summary, both masked: what the CPU tests and
    a model without ``use_flash`` run. Any length."""
    _check(q, k, v, phi, mu, window, chunk)
    b, s, n, h = q.shape
    windows = -(-s // window)
    wide = min(s, window)           # a lone window is as long as it is
    padded = windows * wide
    if padded != s:
        q, k, v = (jnp.pad(x, ((0, 0), (0, padded - s), (0, 0), (0, 0)))
                   for x in (q, k, v))
    scale = h ** -0.5
    by_window = lambda x: x.reshape(b, windows, wide, n, h)  # noqa: E731
    local = jnp.einsum("bwqnh,bwknh->bwnqk", by_window(q), by_window(k),
                       preferred_element_type=jnp.float32) * scale
    causal = jnp.tril(jnp.ones((wide, wide), bool))
    local = jnp.where(causal, local, _MASKED)
    values = by_window(v)
    if windows > 1:
        ks, vs = summaries_reference(k, v, phi, mu, chunk)
        ks, vs = ks.astype(q.dtype), vs.astype(q.dtype)
        far = jnp.einsum("bwqnh,bjnh->bwnqj", by_window(q), ks,
                         preferred_element_type=jnp.float32) * scale
        before = (jnp.arange(padded // chunk)[None, :] * chunk // window
                  < jnp.arange(windows)[:, None])           # [W, J]
        far = jnp.where(before[None, :, None, None, :], far, _MASKED)
        probs = jax.nn.softmax(jnp.concatenate([local, far], axis=-1),
                               axis=-1).astype(v.dtype)
        out = (jnp.einsum("bwnqk,bwknh->bwqnh", probs[..., :wide], values)
               + jnp.einsum("bwnqj,bjnh->bwqnh", probs[..., wide:], vs))
    else:
        probs = jax.nn.softmax(local, axis=-1).astype(v.dtype)
        out = jnp.einsum("bwnqk,bwknh->bwqnh", probs, values)
    return out.reshape(b, padded, n, h)[:, :s]


# --------------------------------------------------------------------------
# The summaries
# --------------------------------------------------------------------------

def _summaries_kernel(k_ref, v_ref, phi_ref, mu_ref, ks_ref, vs_ref, *,
                      chunk: int):
    # k_ref, v_ref [1, 1, rows, H]; phi_ref, mu_ref [1, 1, H] float32;
    # ks_ref, vs_ref [1, 1, rows / chunk, H]
    rows, h = k_ref.shape[2], k_ref.shape[3]
    k = k_ref[0, 0].astype(jnp.float32).reshape(rows // chunk, chunk, h)
    v = v_ref[0, 0].astype(jnp.float32).reshape(rows // chunk, chunk, h)
    score = jnp.sum(k * phi_ref[0], axis=-1, keepdims=True)
    p = jnp.exp(score - jnp.max(score, axis=1, keepdims=True))
    a = p / jnp.sum(p, axis=1, keepdims=True)
    ks_ref[0, 0] = (jnp.mean(k, axis=1) + mu_ref[0]).astype(ks_ref.dtype)
    vs_ref[0, 0] = jnp.sum(a * v, axis=1).astype(vs_ref.dtype)


def _summaries_call(k, v, phi, mu, *, chunk, rows, interpret):
    """``k, v [B, N, S, H]`` heads-first, ``S`` a multiple of ``rows``
    -> ``ks, vs [B, N, S / chunk, H]`` in their type."""
    b, n, s, h = k.shape
    block = pl.BlockSpec((1, 1, rows, h), lambda bi, ni, i: (bi, ni, i, 0))
    vector = pl.BlockSpec((1, 1, h), lambda bi, ni, i: (ni, 0, 0))
    out = pl.BlockSpec((1, 1, rows // chunk, h),
                       lambda bi, ni, i: (bi, ni, i, 0))
    call = pl.pallas_call(
        functools.partial(_summaries_kernel, chunk=chunk),
        grid=(b, n, s // rows),
        in_specs=[block, block, vector, vector],
        out_specs=[out, out],
        out_shape=[jax.ShapeDtypeStruct((b, n, s // chunk, h), k.dtype)] * 2,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
    )

    # the kernel's name in every device trace (see ops/moe.py::_gmm_call)
    def eva_summaries(*operands):
        return call(*operands)

    return jax.jit(eva_summaries)(k, v, phi[:, None], mu[:, None])


# --------------------------------------------------------------------------
# The attention
# --------------------------------------------------------------------------

def _eva_kernel(q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, acc_ref, m_ref,
                l_ref, *, sm_scale: float, block_k: int, block_s: int,
                per_window: int):
    # One block of queries of ``heads`` heads. q_ref, o_ref [1, heads,
    # block_q, H]; k_ref, v_ref [1, heads, window, H], the block's own
    # window; ks_ref, vs_ref [1, heads, summaries, H], whole. Scratch:
    # acc_ref [heads, H, block_q], m_ref and l_ref [heads, 1, block_q]
    # float32. Score tiles are [keys, block_q].
    heads, _h, block_q = acc_ref.shape
    window = k_ref.shape[2]
    qi = pl.program_id(2)
    blocks = window // block_q              # query blocks a window
    before = qi // blocks                   # windows before this one
    q0 = (qi % blocks) * block_q            # the block's place in its own
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _MASKED)
    l_ref[...] = jnp.zeros_like(l_ref)

    def attend(keys_ref, values_ref, start, rows, visible):
        """One tile of ``rows`` keys (or summaries) from ``start`` for
        every head of the step; ``visible [rows, block_q]``."""
        bias = jnp.where(visible, 0.0, _MASKED)

        def weights(e):
            s = _dot(keys_ref[0, e, pl.ds(start, rows), :], q_ref[0, e],
                     _NT) * sm_scale + bias
            # the first key of a window is visible to each of its
            # queries and the walk starts there, so m is finite from
            # the first tile on and a hidden score's exp is exactly 0
            m = m_ref[e]
            m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            m_ref[e] = m_new
            l_ref[e] = l_ref[e] * alpha + jnp.sum(p, axis=0, keepdims=True)
            return p.astype(values_ref.dtype), alpha

        def gather(e, p, alpha):
            acc_ref[e] = acc_ref[e] * alpha + _dot(
                values_ref[0, e, pl.ds(start, rows), :], p, _TN)

        waiting = weights(0)
        for e in range(1, heads):
            ready = weights(e)
            gather(e - 1, *waiting)
            waiting = ready
        gather(heads - 1, *waiting)

    k_row = jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0)
    ahead = jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q),
                                     1) - k_row

    def local(j, carry):
        start = pl.multiple_of(j * block_k, block_k)
        attend(k_ref, v_ref, start, block_k, ahead + (q0 - start) >= 0)
        return carry

    jax.lax.fori_loop(0, pl.cdiv(q0 + block_q, block_k), local, 0)

    s_row = jax.lax.broadcasted_iota(jnp.int32, (block_s, block_q), 0)
    seen = before * per_window              # summaries this block sees

    def far(j, carry):
        start = pl.multiple_of(j * block_s, block_s)
        attend(ks_ref, vs_ref, start, block_s, s_row + start < seen)
        return carry

    jax.lax.fori_loop(0, pl.cdiv(seen, block_s), far, 0)

    for e in range(heads):
        o_ref[0, e] = (acc_ref[e] / l_ref[e]).T.astype(o_ref.dtype)


def _largest_block(n: int) -> int:
    """The largest divisor of ``n`` up to ``_MAX_BLOCK``, a multiple of
    128 where ``n`` is one (the compiled kernel's tiling)."""
    unit = 128 if n % 128 == 0 else 1
    return max(b for b in range(unit, min(n, _MAX_BLOCK) + 1, unit)
               if n % b == 0)


def _attn_call(q, k, v, ks, vs, *, window, chunk, interpret):
    """Heads-first operands ``[B, N, S, H]``, ``S`` whole windows, and
    the summaries ``[B, N, S / chunk, H]`` -> ``[B, N, S, H]``."""
    b, n, s, h = q.shape
    per_window = window // chunk
    block = _largest_block(window)
    block_s = min(_MAX_BLOCK, ks.shape[2])
    if ks.shape[2] % block_s:
        ks, vs = (jnp.pad(x, ((0, 0), (0, 0), (0, -x.shape[2] % block_s),
                              (0, 0))) for x in (ks, vs))
    heads = max(u for u in range(1, _HEAD_RUN + 1) if n % u == 0)
    blocks = window // block
    tile = pl.BlockSpec((1, heads, block, h),
                        lambda bi, ni, i: (bi, ni, i, 0))
    own = pl.BlockSpec((1, heads, window, h),
                       lambda bi, ni, i: (bi, ni, i // blocks, 0))
    whole = pl.BlockSpec((1, heads, ks.shape[2], h),
                         lambda bi, ni, i: (bi, ni, 0, 0))
    call = pl.pallas_call(
        functools.partial(_eva_kernel, sm_scale=h ** -0.5, block_k=block,
                          block_s=block_s, per_window=per_window),
        grid=(b, n // heads, s // block),
        in_specs=[tile, own, own, whole, whole],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((b, n, s, h), q.dtype),
        scratch_shapes=[pltpu.VMEM((heads, h, block), jnp.float32),
                        pltpu.VMEM((heads, 1, block), jnp.float32),
                        pltpu.VMEM((heads, 1, block), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )

    def eva_attn(*operands):
        return call(*operands)

    return jax.jit(eva_attn)(q, k, v, ks, vs)


def _eva_call(q, k, v, phi, mu, *, window, chunk, interpret):
    s = q.shape[1]
    padded = _round_up(s, window)
    # heads first, as the flash forward takes its operands: the
    # transposes fuse into the projections' neighbours
    q, k, v = (jnp.pad(x.transpose(0, 2, 1, 3),
                       ((0, 0), (0, 0), (0, padded - s), (0, 0)))
               for x in (q, k, v))
    ks, vs = _summaries_call(
        k, v, phi.astype(jnp.float32), mu.astype(jnp.float32), chunk=chunk,
        rows=window, interpret=interpret)
    out = _attn_call(q, k, v, ks, vs, window=window, chunk=chunk,
                     interpret=interpret)
    return out[:, :, :s].transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def eva_attention(q, k, v, phi, mu, window: int, chunk: int,
                  interpret: Optional[bool] = None):
    """``q, k, v [B, S, N, H]``, ``phi, mu [N, H]`` -> ``[B, S, N,
    H]``: each query attends to the keys up to it inside its window of
    ``window`` positions and to one summary for each ``chunk`` keys of
    the windows before. A length that is not whole windows is padded
    with keys that no query sees."""
    _check(q, k, v, phi, mu, window, chunk)
    return _for_lowering_platform(
        functools.partial(_eva_call, window=window, chunk=chunk),
        interpret, q, k, v, phi, mu)


def _eva_fwd(q, k, v, phi, mu, window, chunk, interpret):
    return eva_attention(q, k, v, phi, mu, window, chunk, interpret), None


def _eva_bwd(window, chunk, interpret, residuals, g):
    raise NotImplementedError(
        "ray_tpu.ops.eva_attention has no backward kernel: the eva layer "
        "runs forward only (serving); training through it needs the "
        "backward of both walks and of the summaries (ROADMAP R2 j)")


eva_attention.defvjp(_eva_fwd, _eva_bwd)
