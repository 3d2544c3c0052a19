"""Block-sparse attention with a per-query choice of key blocks
(InfLLM-V2, arXiv:2509.24663, as MiniCPM4 ships it): two Pallas TPU
kernels, one that makes the choice and one that attends over the chosen
blocks only.

ABSENT from the reference (attention enters via torch in hosted
workloads, SURVEY.md §2.5). ``sizes`` is any value with the seven
fields of ``models.transformer.SparseSizes``: ``kernel``, ``stride``
(the compressed keys), ``block`` (keys a block), ``top_k`` (blocks a
query attends to, forced ones included), ``window`` and
``init_blocks`` (the forced ones), ``dense_len`` (read by the layer,
not here). Query head ``n`` belongs to KV group ``n // (N / G)``; the
heads of a group share one choice and one copy of K and V.

**The choice** (steps 1-4), for query ``t`` and group ``g``:

1. compressed keys ``c_j = mean(k[stride j : stride j + kernel])``,
   visible to ``t`` iff ``stride j + kernel - 1 <= t``;
2. ``a = softmax over visible j of (q . c_j) / sqrt(H)`` by head, in
   float32 (all zero where none is visible), summed over the group's
   heads;
3. a block's score is the largest ``a`` among the compressed keys whose
   tokens overlap it;
4. with ``b_t = t // block``: the first ``init_blocks`` blocks and the
   ``window / block`` blocks up to ``b_t`` are always taken, blocks
   past ``b_t`` never, and the highest scores among the rest fill
   ``top_k`` (ties to the lower index).

**The attention** (step 5) is softmax attention of each query over the
keys ``u <= t`` of its chosen blocks.

Both kernels serve a tile of 128 queries a step and hold their tiles
transposed, keys or blocks down the sublanes and queries along the
lanes, so that a softmax's statistics are one row a head and its
reductions run down the sublanes. Between them the choice is a mask,
``[B, G, blocks, S]`` int32, one row a block: no ``[N, S, S / stride]``
score (8.6 GB at 32,768 tokens) and no list of indices ever stands in
HBM (``selected_attention``).

- ``sparse_select`` holds the group's compressed keys whole in VMEM,
  laid out by their place in a block so that step 3 is a maximum of
  row segments. Step 4 needs no sort: the ``k``-th largest score of a
  query is found by bisection on the scores' bit patterns (31 counts
  down the sublanes), ties by a prefix count (one small matmul).
- ``sparse_attn`` holds the group's K and V whole in VMEM, walks the
  keys before the tile in units of 128 and **skips a unit that no query
  of the tile chose**; in a unit it visits, a query counts only the keys
  of blocks it chose itself, so what is computed is step 5 exactly
  whatever the tile. What it reads is the union of the tile's choices:
  queries that choose alike (trained weights) read little more than
  ``top_k`` blocks, queries that choose at random read every block
  before them. Online softmax as in ``ops/flash_attention.py``, one
  head of the group after another against the same unit of keys. How
  many units it visited is counted from the mask it was given
  (``units_visited``) and comes back beside the result.

The plain references beside them are ``jax.numpy`` and speak in lists
of block indices (``[B, S, G, top_k]`` int32, ascending, ``-1`` in the
slots a query with fewer blocks before it leaves empty).

Forward only: a gradient asked of the attention raises by name.

Layout: ``q [B, S, N, H]``, ``k, v [B, S, G, H]``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash_attention import (
    _LANE, _MASKED, _NN, _NT, _TN, _dot, _for_lowering_platform, _round_up)

_TILE_Q = _LANE                     # queries a kernel step: the lanes
_VMEM_DEFAULT, _VMEM_MOST = 16 * 2 ** 20, 100 * 2 ** 20


def _blocks_padded(seq: int, sizes) -> int:
    """Rows of the mask: the blocks of the sequence, to whole lanes."""
    return _round_up(-(-seq // sizes.block), _LANE)


def _compiler_params(need: int) -> pltpu.CompilerParams:
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=min(_VMEM_MOST, max(_VMEM_DEFAULT, need)))


# --------------------------------------------------------------------------
# The choice
# --------------------------------------------------------------------------

def compressed_keys(k, sizes):
    """``k [B, S, G, H]`` -> ``[B, J, G, H]`` float32, ``J = (S -
    kernel) // stride + 1``: the mean of each ``kernel`` tokens, a
    ``stride`` apart (``kernel`` a multiple of ``stride``)."""
    b, s, g, h = k.shape
    span = sizes.kernel // sizes.stride
    strides = s // sizes.stride
    part = k[:, :strides * sizes.stride].astype(jnp.float32).reshape(
        b, strides, sizes.stride, g, h).mean(axis=2)
    count = strides - span + 1
    return sum(part[:, o:o + count] for o in range(span)) / span


def _by_place(c, n_blocks: int, sizes):
    """The compressed keys ``c [B, J, G, H]`` laid out for the kernel,
    ``[B, G, segments * n_blocks, H]``: segment ``o`` holds, in row
    ``b``, compressed key ``ratio b - (span - 1) + o``, the ``o``-th of
    those whose tokens overlap block ``b`` (zeros where there is none).
    The first ``span - 1`` segments repeat keys that a later segment
    holds for the block before."""
    ratio, span = (sizes.block // sizes.stride,
                   sizes.kernel // sizes.stride)
    c = jnp.pad(c, ((0, 0), (span - 1, max(
        0, ratio * n_blocks - c.shape[1])), (0, 0), (0, 0)))
    placed = jnp.concatenate([c[:, o:o + ratio * n_blocks:ratio]
                              for o in range(ratio + span - 1)], axis=1)
    return jnp.moveaxis(placed, 2, 1)


def _select_kernel(q_ref, c_ref, mask_ref, *, sizes, heads: int, count: int):
    # q_ref [1, Tq, heads * H]; c_ref [1, 1, segments * nb, H];
    # mask_ref [1, 1, nb, Tq] int32. Everything below is [rows, Tq]:
    # compressed keys or blocks down the sublanes, queries along lanes.
    tq, nb = mask_ref.shape[3], mask_ref.shape[2]
    h = c_ref.shape[3]
    rows = c_ref.shape[2]
    ratio, span = (sizes.block // sizes.stride,
                   sizes.kernel // sizes.stride)
    t = pl.program_id(2) * tq + lax.broadcasted_iota(jnp.int32, (1, tq), 1)
    row = lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    # the compressed key each row holds (``count`` of them exist)
    segment = sum((row >= o * nb).astype(jnp.int32)
                  for o in range(1, ratio + span - 1))
    j = ratio * (row - segment * nb) - (span - 1) + segment
    visible = (j >= 0) & (j < count) & (
        sizes.stride * j + sizes.kernel - 1 <= t)           # [rows, Tq]
    bias = jnp.where(visible, 0.0, _MASKED)
    once = (row >= (span - 1) * nb).astype(jnp.float32)     # not a repeat
    c = c_ref[0, 0]

    def head(e, total):
        qe = q_ref[0, :, pl.ds(pl.multiple_of(e * h, h), h)]    # [Tq, H]
        s = _dot(c, qe, _NT) * h ** -0.5 + bias
        top = jnp.max(s, axis=0, keepdims=True)                 # [1, Tq]
        w = jnp.exp(s - top)
        norm = jnp.sum(w * once, axis=0, keepdims=True)
        # a query that sees no compressed key yet holds top = _MASKED
        # and w = 1 everywhere: it scores nothing
        return total + w * jnp.where(top > 0.5 * _MASKED, 1.0 / norm, 0.0)

    a = lax.fori_loop(0, heads, head, jnp.zeros((rows, tq), jnp.float32))
    score = functools.reduce(jnp.maximum, (
        a[o * nb:(o + 1) * nb] for o in range(ratio + span - 1)))
    blk = lax.broadcasted_iota(jnp.int32, (nb, 1), 0)
    allowed = blk * sizes.block <= t                            # b <= b_t
    latest = (blk + sizes.window // sizes.block) * sizes.block > t
    forced = allowed & ((blk < sizes.init_blocks) | latest)
    free = allowed & ~forced
    quota = sizes.top_k - jnp.sum(forced.astype(jnp.int32), axis=0,
                                  keepdims=True)                # [1, Tq]
    # scores are >= 0, so their bit patterns order as they do
    bits = jnp.where(free, lax.bitcast_convert_type(score, jnp.int32), -1)

    def bisect(i, least):
        trial = least | lax.shift_left(jnp.int32(1), 30 - i)
        enough = jnp.sum((bits >= trial).astype(jnp.int32), axis=0,
                         keepdims=True) >= quota
        return jnp.where(enough, trial, least)

    # the largest value that ``quota`` free blocks reach (0: fewer do)
    least = lax.fori_loop(0, 31, bisect, jnp.zeros((1, tq), jnp.int32))
    above = bits > least
    tied = bits == least
    before = lax.broadcasted_iota(jnp.int32, (nb, nb), 1) < \
        lax.broadcasted_iota(jnp.int32, (nb, nb), 0)
    rank = _dot(before.astype(jnp.bfloat16), tied.astype(jnp.bfloat16),
                _NN)                            # tied blocks before each
    room = quota - jnp.sum(above.astype(jnp.int32), axis=0, keepdims=True)
    taken = forced | above | (tied & (rank < room.astype(jnp.float32)))
    mask_ref[0, 0] = taken.astype(jnp.int32)


def _select_call(q, k, *, sizes, interpret):
    b, s, n, h = q.shape
    g = k.shape[2]
    heads = n // g
    nb = _blocks_padded(s, sizes)
    padded = _round_up(s, _TILE_Q)
    c = compressed_keys(k, sizes)
    placed = _by_place(c.astype(q.dtype), nb, sizes)
    rows = placed.shape[2]
    need = 2 * rows * h * q.dtype.itemsize + 8 * rows * _TILE_Q * 4 + \
        2 * nb * nb * 4 + 4 * 2 ** 20
    call = pl.pallas_call(
        functools.partial(_select_kernel, sizes=sizes, heads=heads,
                          count=c.shape[1]),
        grid=(b, g, padded // _TILE_Q),
        in_specs=[pl.BlockSpec((1, _TILE_Q, heads * h),
                               lambda bi, gi, i: (bi, i, gi)),
                  pl.BlockSpec((1, 1, rows, h),
                               lambda bi, gi, i: (bi, gi, 0, 0))],
        out_specs=pl.BlockSpec((1, 1, nb, _TILE_Q),
                               lambda bi, gi, i: (bi, gi, 0, i)),
        out_shape=jax.ShapeDtypeStruct((b, g, nb, padded), jnp.int32),
        interpret=interpret,
        compiler_params=_compiler_params(need),
    )

    # the kernel's name in every device trace (see ops/moe.py::_gmm_call)
    def sparse_select(*operands):
        return call(*operands)

    return jax.jit(sparse_select)(
        jnp.pad(q.reshape(b, s, n * h), ((0, 0), (0, padded - s), (0, 0))),
        placed)


def select_mask(q, k, sizes, interpret: Optional[bool] = None):
    """Steps 1-4: ``q [B, S, N, H]``, ``k [B, S, G, H]`` -> the choice
    as a mask ``[B, G, blocks, S]`` int32 (both sizes padded to whole
    lanes), 1 where the query of that column takes the block of that
    row. The compressed keys are rounded to ``q``'s type for their
    product, as every operand is; scores, softmax and choice are
    float32."""
    return _for_lowering_platform(
        functools.partial(_select_call, sizes=sizes), interpret, q, k)


def _group_scores(q, c, t, sizes):
    """Steps 1-2 for the query rows ``q [B, R, N, H]`` at positions
    ``t [R]`` against compressed keys ``c [B, J, G, H]`` ->
    ``[B, R, G, J]`` float32."""
    b, r, n, h = q.shape
    g = c.shape[2]
    scores = jnp.einsum("brgeh,bjgh->brgej", q.reshape(b, r, g, n // g, h),
                        c, preferred_element_type=jnp.float32) * h ** -0.5
    j = jnp.arange(c.shape[1])
    visible = (sizes.stride * j + sizes.kernel - 1
               <= t[:, None])[:, None, None, :]            # [R, 1, 1, J]
    scores = jnp.where(visible, scores, _MASKED)
    weights = jnp.where(visible, jnp.exp(
        scores - jnp.max(scores, axis=-1, keepdims=True)), 0.0)
    total = jnp.sum(weights, axis=-1, keepdims=True)
    return jnp.sum(weights / jnp.maximum(total, 1e-30), axis=3)


def block_scores(a, n_blocks: int, sizes):
    """Step 3: ``a [..., J]`` by compressed key -> ``[..., n_blocks]`` by
    block, the largest among the compressed keys whose tokens overlap
    the block (a max-pool of ``ratio + span - 1`` wide, ``ratio``
    apart)."""
    ratio = sizes.block // sizes.stride
    span = sizes.kernel // sizes.stride
    wide = [(0, 0)] * (a.ndim - 1)
    a = jnp.pad(a, wide + [(span - 1, max(
        0, ratio * n_blocks - a.shape[-1]))])
    return functools.reduce(jnp.maximum, (
        a[..., o:o + ratio * n_blocks:ratio]
        for o in range(ratio + span - 1)))


def select_blocks_reference(q, k, sizes):
    """Steps 1-4 as lists, ``[B, S, G, top_k]`` int32: every score
    standing whole and the choice by ranking (a block's rank is how
    many blocks beat it), float32."""
    b, s, n, h = q.shape
    n_blocks = -(-s // sizes.block)
    t = jnp.arange(s)
    c = compressed_keys(k, sizes).astype(q.dtype).astype(jnp.float32)
    a = _group_scores(q.astype(jnp.float32), c, t, sizes)
    scores = block_scores(a, n_blocks, sizes)               # [B, S, G, nb]
    blocks = jnp.arange(n_blocks)
    b_t = (t // sizes.block)[None, :, None, None]
    forced = (blocks < sizes.init_blocks) | (
        blocks > b_t - sizes.window // sizes.block)
    allowed = blocks <= b_t
    key = jnp.where(forced, jnp.inf, scores)
    beats = (key[..., None, :] > key[..., :, None]) | (
        (key[..., None, :] == key[..., :, None])
        & (blocks[None, :] < blocks[:, None]))              # [.., b, b']
    rank = jnp.sum(beats & allowed[..., None, :], axis=-1)
    taken = allowed & (rank < sizes.top_k)                  # [B, S, G, nb]
    order = jnp.argsort(~taken, axis=-1, stable=True)[..., :sizes.top_k]
    order = jnp.pad(order, [(0, 0)] * 3 + [
        (0, max(0, sizes.top_k - n_blocks))], constant_values=n_blocks)
    valid = jnp.arange(sizes.top_k) < jnp.sum(taken, axis=-1, keepdims=True)
    return jnp.where(valid, order, -1).astype(jnp.int32)


# --------------------------------------------------------------------------
# Attention over the chosen blocks
# --------------------------------------------------------------------------

def sparse_reference(q, k, v, blocks, sizes):
    """Step 5 with the mask standing whole: key ``u`` counts for query
    ``t`` iff ``u <= t`` and ``u // block`` is among ``blocks[t]``."""
    b, s, n, h = q.shape
    g = k.shape[2]
    u = jnp.arange(s)
    chosen = jnp.any(blocks[..., None] == (u // sizes.block), axis=3)
    visible = chosen & (u <= u[:, None])[None, :, None, :]  # [B, S, G, S]
    logits = jnp.einsum(
        "bqgeh,bkgh->bqgek", q.reshape(b, s, g, n // g, h), k,
        preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST) * h ** -0.5
    logits = jnp.where(visible[:, :, :, None, :], logits, _MASKED)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bqgek,bkgh->bqgeh", probs, v.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    return out.reshape(b, s, n, h).astype(q.dtype)


def _attend_kernel(mask_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, top_ref,
                   sum_ref, *, block: int, unit: int, heads: int):
    # mask_ref [1, 1, nb, Tq] int32; q_ref, o_ref [1, Tq, heads * H];
    # k_ref, v_ref [1, S, H]: one group's keys, whole. Scratch, kept
    # over the walk: acc_ref [heads, H, Tq], top_ref and sum_ref
    # [heads, Tq] float32. Score tiles are [keys, Tq].
    tq, h = q_ref.shape[1], k_ref.shape[2]
    keys = unit * block                                 # keys a visit
    group = max(8, unit)            # mask rows a load takes, aligned
    t0 = pl.program_id(2) * tq
    acc_ref[...] = jnp.zeros_like(acc_ref)
    top_ref[...] = jnp.full_like(top_ref, _MASKED)
    sum_ref[...] = jnp.zeros_like(sum_ref)
    t = t0 + lax.broadcasted_iota(jnp.int32, (keys, tq), 1)
    place = lax.broadcasted_iota(jnp.int32, (keys, tq), 0)
    within = lax.broadcasted_iota(jnp.int32, (group, 1), 0)

    def visit(j, carry):
        first = pl.multiple_of((j * unit // group) * group, group)
        rows = mask_ref[0, 0, pl.ds(first, group), :]       # [group, Tq]
        chose = [jnp.sum(jnp.where(within == j * unit + u - first, rows, 0),
                         axis=0, keepdims=True) > 0 for u in range(unit)]

        @pl.when(jnp.any(functools.reduce(jnp.logical_or, chose)))
        def _():
            start = pl.multiple_of(j * keys, keys)
            k_blk = k_ref[0, pl.ds(start, keys), :]
            v_blk = v_ref[0, pl.ds(start, keys), :]
            mine = functools.reduce(jnp.logical_or, (
                chose[u] & (place >= u * block) & (place < (u + 1) * block)
                for u in range(unit)))
            # a masked score is _MASKED whatever it was: its exp is 0
            # once the query has met a key, and a query that has met
            # none yet gathers exp(0), which its first key wipes
            # (alpha = 0): its own block holds its own position
            bias = jnp.where(mine & (start + place <= t), 0.0, _MASKED)
            for e in range(heads):
                qe = q_ref[0, :, e * h:(e + 1) * h]             # [Tq, H]
                s = _dot(k_blk, qe, _NT) * h ** -0.5 + bias     # [keys, Tq]
                old = top_ref[e:e + 1]
                top = jnp.maximum(old, jnp.max(s, axis=0, keepdims=True))
                p = jnp.exp(s - top)
                alpha = jnp.exp(old - top)
                top_ref[e:e + 1] = top
                sum_ref[e:e + 1] = sum_ref[e:e + 1] * alpha + jnp.sum(
                    p, axis=0, keepdims=True)
                acc_ref[e] = acc_ref[e] * alpha + _dot(
                    v_blk, p.astype(v_blk.dtype), _TN)          # [H, Tq]

        return carry

    lax.fori_loop(0, pl.cdiv(t0 + tq, keys), visit, 0)
    for e in range(heads):
        out = acc_ref[e] / jnp.maximum(sum_ref[e:e + 1], 1e-30)
        o_ref[0, :, e * h:(e + 1) * h] = out.T.astype(o_ref.dtype)


def _attend_call(mask, q, k, v, *, block, interpret):
    b, s, n, h = q.shape
    g = k.shape[2]
    heads = n // g
    nb = mask.shape[2]
    unit = max(1, _LANE // block)
    padded = _round_up(s, max(_TILE_Q, unit * block))
    grow = ((0, 0), (0, padded - s), (0, 0))
    mask = jnp.pad(mask[..., :padded], (
        (0, 0), (0, 0), (0, 0), (0, max(0, padded - mask.shape[3]))))
    tile = pl.BlockSpec((1, _TILE_Q, heads * h), lambda bi, gi, i: (bi, i, gi))
    whole = pl.BlockSpec((1, padded, h), lambda bi, gi, i: (bi, 0, gi))
    need = 2 * 2 * padded * h * q.dtype.itemsize + 2 * nb * _TILE_Q * 4 + \
        heads * h * _TILE_Q * 4 + 8 * 2 ** 20
    call = pl.pallas_call(
        functools.partial(_attend_kernel, block=block, unit=unit,
                          heads=heads),
        grid=(b, g, padded // _TILE_Q),
        in_specs=[pl.BlockSpec((1, 1, nb, _TILE_Q),
                               lambda bi, gi, i: (bi, gi, 0, i)),
                  tile, whole, whole],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((b, padded, n * h), q.dtype),
        scratch_shapes=[pltpu.VMEM((heads, h, _TILE_Q), jnp.float32),
                        pltpu.VMEM((heads, _TILE_Q), jnp.float32),
                        pltpu.VMEM((heads, _TILE_Q), jnp.float32)],
        interpret=interpret,
        compiler_params=_compiler_params(need),
    )

    # the kernel's name in every device trace (see ops/moe.py::_gmm_call)
    def sparse_attn(*operands):
        return call(*operands)

    out = jax.jit(sparse_attn)(
        mask, jnp.pad(q.reshape(b, s, n * h), grow),
        jnp.pad(k.reshape(b, s, g * h), grow),
        jnp.pad(v.reshape(b, s, g * h), grow))
    return out[:, :s].reshape(b, s, n, h)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def attend_mask(q, k, v, mask, sizes, interpret: Optional[bool] = None):
    """Step 5: ``q [B, S, N, H]``, ``k, v [B, S, G, H]``, ``mask [B, G,
    blocks, S]`` int32 as ``select_mask`` gives it -> ``[B, S, N, H]``.
    Every query's own block has to be among its blocks."""
    return _for_lowering_platform(
        functools.partial(_attend_call, block=sizes.block), interpret,
        mask, q, k, v)


def _attend_fwd(q, k, v, mask, sizes, interpret):
    return attend_mask(q, k, v, mask, sizes, interpret), None


def _attend_bwd(sizes, interpret, residuals, g):
    raise NotImplementedError(
        "ray_tpu.ops.sparse_attention has no backward kernel: the sparse "
        "layer runs forward only (serving); training through it needs the "
        "transposed block walk (ROADMAP R2)")


attend_mask.defvjp(_attend_fwd, _attend_bwd)


def units_visited(mask, sizes):
    """The (tile of queries, unit of keys) pairs ``sparse_attn`` visits
    under ``mask [B, G, blocks, S]``: those in which some query of the
    tile chose some block of the unit, the kernel's own test. -> int32
    scalar. One pass over the mask (134 MB a layer at 32,768 tokens)."""
    window = (1, 1, max(1, _LANE // sizes.block), _TILE_Q)
    return jnp.sum(lax.reduce_window(mask, 0, lax.max, window, window,
                                     "VALID"))


def selected_attention(q, k, v, sizes, interpret: Optional[bool] = None):
    """Steps 1-5, the sparse layer's mixer: the choice goes from one
    kernel to the other as a mask. -> (``[B, S, N, H]``, the units of
    keys the attention visited over all tiles and groups)."""
    mask = select_mask(lax.stop_gradient(q), lax.stop_gradient(k), sizes,
                       interpret)
    return attend_mask(q, k, v, mask, sizes, interpret), units_visited(
        mask, sizes)


def keys_counted(seq: int, sizes) -> dict:
    """What one sparse layer's attention touches over one sequence and
    one KV group, from shapes: ``keys_selected`` (the keys ``u <= t`` of
    each query's chosen blocks), ``keys_causal`` (every key before each
    query) and ``units_before`` (the units of keys before each tile of
    queries, its own included: the most the kernel visits;
    ``visit_pairs`` (query, key) pairs are read in a visit, a unit's
    keys for each query of the tile)."""
    t = np.arange(seq, dtype=np.int64)
    selected = np.where(
        t // sizes.block < sizes.top_k, t + 1,
        (sizes.top_k - 1) * sizes.block + t % sizes.block + 1)
    keys = max(1, _LANE // sizes.block) * sizes.block
    upto = (np.arange(-(-seq // _TILE_Q), dtype=np.int64) + 1) * _TILE_Q
    return {"keys_selected": int(selected.sum()),
            "keys_causal": int((t + 1).sum()),
            "units_before": int((-(-upto // keys)).sum()),
            "visit_pairs": keys * _TILE_Q}
