"""Flash attention as a Pallas TPU kernel.

The reference has no attention kernels — attention enters via torch in
workloads hosted on it [SURVEY.md §2.5]. Here the fused blockwise
kernel is first-class: the MXU does the two matmuls per block, online
softmax keeps running (max, normalizer) so the S×S score matrix never
materializes in HBM. Which of FLOPs and HBM bytes bounds a call
depends on its shape (``benchmark/costs.py::flash_cost``; at S 4096
it is the FLOPs), and how far the kernels stand from that bound is
measured, not stated here: PERF.md §5.

Forward is one Pallas kernel that serves a KV group a grid step
(grouped-query attention: ``q [B, S, N, H]``, ``k, v [B, S, G, H]``,
``G`` dividing ``N``; query head ``n`` reads KV head ``n // (N / G)``).
The grid is (batch, KV head, query block). A step holds the group's K
and V whole in VMEM, fetched once a group, and walks their tiles once;
on each tile it builds what decides visibility (causal, window, true
length) once, as an additive bias, then serves the group's ``N / G``
query heads one after another against the same tile of keys and
values. Score tiles are held transposed, ``[block_k, block_q]``: a
head's running maximum and normalizer are one lane-dense row, their
reductions run down the sublanes, and the result accumulates as
``[H, block_q]``, transposed once a query block when it is written.
The kernel is given q heads-first, ``[B, N, S, H]``, and K and V at
their KV heads, ``[B, G, S, H]``, and finds a group's heads through the
block index: no repeat. (The transposes fuse into their neighbours in
the models' programs; reading ``[B, S, heads * H]`` as the projections
leave it is a change of tiled layout there, a copy of its own: PERF.md
§6, PR 34.) Only (O, LSE) are saved.

Backward is a Pallas FlashAttention-2 backward — blockwise dq/dk/dv
recomputed from (O, LSE), so no S×S probability matrix ever touches
HBM in either direction. Its two kernels take equal head counts: K and
V are repeated up to the query heads for them and dk, dv summed over a
group afterwards. Gradients are exact (grad-checked against the
dense reference in tests/test_attention.py, on real TPU lowering too).

Arithmetic: every matmul takes its operands in the type the caller
gave (the MXU's own for bf16) and accumulates in float32. Q·Kᵀ and
dO·Vᵀ lose nothing by that (a product of two bf16 values is exact in
float32); P and dS are rounded to the input type before their
products, as the dense path rounds its probabilities to the value
type. Softmax statistics, LSE, delta and the accumulators are float32
whatever the input; float32 inputs keep float32 products throughout.
A hidden score is ``_MASKED`` whatever it was (the bias swallows it),
so its ``exp`` is exactly 0.

Block sizes come from the shape (``_choose_blocks``) unless the caller
names them. Every tile builds its bias, once for the group: building
it only on the tiles that cross the diagonal or the true length
measured slower in the kernel's earlier form (PERF.md §6, PR 26).

TPU alignment (Mosaic): dynamic VMEM loads must sit at provably
8-aligned rows and block shapes must tile to (8, 128), so sequences
are PADDED to block multiples outside the kernels and padded rows are
masked by the true lengths — no data-dependent clamping inside the
kernel (a clamped start index cannot be statically proven aligned),
and the LSE/delta vectors carry a singleton middle axis so their
blocks satisfy the tiling rule.

Layout everywhere: [B, S, heads, H].
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl


def mha_reference(q, k, v, *, causal: bool = True,
                  sm_scale: Optional[float] = None,
                  q_offset: int = 0, kv_offset: int = 0,
                  window: Optional[int] = None):
    """Dense attention, [B,S,N,H]. Offsets shift absolute positions for
    cross-shard causal masking (ring/ulysses callers). ``window`` (with
    ``causal``) hides keys ``window`` or more positions before their
    query."""
    _check_window(window, causal)
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bqnh,bknh->bnqk", q, k,
                        preferred_element_type=jnp.float32) * sm_scale
    if causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        q_pos = q_offset + jnp.arange(s_q)
        k_pos = kv_offset + jnp.arange(s_k)
        mask = q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            mask &= q_pos[:, None] - k_pos[None, :] < window
        logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bnqk,bknh->bqnh", probs.astype(v.dtype), v)


def repeat_kv(k, v, n_heads: int):
    """K and V ``[B, S, G, H]`` copied up to ``n_heads`` query heads
    (head ``n`` reads KV head ``n // (n_heads / G)``), for the paths
    that need equal head counts."""
    rep = n_heads // k.shape[2]
    if rep == 1:
        return k, v
    return jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)


def _check_window(window: Optional[int], causal: bool) -> None:
    if window is not None and (not causal or window < 1):
        raise ValueError(f"a sliding window needs causal attention and "
                         f"at least one visible key, got window={window} "
                         f"causal={causal}")


_LANE = 128


# --------------------------------------------------------------------------
# Block sizes
# --------------------------------------------------------------------------

# What a kernel may hold in VMEM without asking: Mosaic scopes 16 MiB to
# a kernel on a v5e unless told otherwise, and the budget leaves a
# quarter of that to what ``_vmem_bytes`` does not count (spills,
# relayouts, semaphores).
_VMEM_BUDGET = 12 * 2 ** 20
# No tile side beyond this: the diagonal's tiles are computed whole, so
# a causal call does (n + 1) / n of its work at n blocks a side, and
# past 512 that cost more than the longer tiles gave in all three
# kernels (PERF.md §6, PR 26: the block table).
_MAX_BLOCK = 512


class _Blocks(NamedTuple):
    """The blocks all three kernels take, and the padded lengths they
    divide."""
    block_q: int
    block_k: int
    sqp: int
    skp: int


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _vmem_bytes(block: int, tile: int, whole: int, hp: int,
                itemsize: int, heads: int = 1) -> int:
    """VMEM one grid step needs at ``block`` rows a grid block and
    ``tile`` rows a loop tile: two operands of ``whole`` rows and at
    most four blocked operands and results, each double-buffered by the
    pipeline, two float32 accumulators, and three float32 score tiles
    live at once (s or p, dp, ds; in the forward s, p and the bias).
    The forward's blocked operands and accumulators are ``heads`` wide,
    the query heads of the KV group a step serves."""
    return (2 * 2 * whole * hp * itemsize
            + heads * (4 * 2 * block * hp * itemsize + 2 * block * hp * 4)
            + 3 * block * tile * 4)


def _choose_blocks(s_q: int, s_k: int) -> _Blocks:
    """Block sizes from the shape. Sequences pad to a lane multiple and
    no further; every kernel takes the largest multiples of 128 that
    divide the padded lengths and stay at or under ``_MAX_BLOCK``.
    Where that needs more VMEM than Mosaic scopes to a kernel by
    default, the kernels ask for it (``_compiler_params``) and keep
    their blocks: a 256-row tile measured 30 % slower than a 512-row
    one (PERF.md §6, PR 26), a larger scope costs nothing."""
    sqp, skp = _round_up(s_q, _LANE), _round_up(s_k, _LANE)
    bq, bk = (max(b for b in range(_LANE, min(n, _MAX_BLOCK) + 1, _LANE)
                  if n % b == 0) for n in (sqp, skp))
    return _Blocks(bq, bk, sqp, skp)


def _compiler_params(block: int, tile: int, whole: int, hp: int,
                     itemsize: int, heads: int = 1) -> dict:
    """Nothing where the kernel fits ``_VMEM_BUDGET`` (bf16 up to
    6,144 x 128 at the largest blocks and one head a step); past it, a
    VMEM limit a third over what ``_vmem_bytes`` counts. A v5e core has
    128 MiB."""
    need = _vmem_bytes(block, tile, whole, hp, itemsize, heads)
    if need <= _VMEM_BUDGET:
        return {}
    from jax.experimental.pallas import tpu as pltpu
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=need * 4 // 3)}


def _blocks_for(s_q: int, s_k: int, block_q: Optional[int],
                block_k: Optional[int]) -> _Blocks:
    """The chosen blocks, with a size the caller named taken as given
    by every kernel (and the sequence padded to it)."""
    chosen = _choose_blocks(s_q, s_k)
    return _Blocks(block_q or chosen.block_q, block_k or chosen.block_k,
                   sqp=_round_up(s_q, block_q) if block_q else chosen.sqp,
                   skp=_round_up(s_k, block_k) if block_k else chosen.skp)


# --------------------------------------------------------------------------
# Pallas forward kernel
# --------------------------------------------------------------------------

# Heads of a KV group the forward serves in one run of straight-line
# code; a group of more is a loop over such runs.
_HEAD_RUN = 2

_NT = (((1,), (1,)), ((), ()))      # a · bᵀ
_NN = (((1,), (0,)), ((), ()))      # a · b
_TN = (((0,), (0,)), ((), ()))      # aᵀ · b
_MASKED = -1e30


def _dot(a, b, dims):
    """Operands as they are, float32 accumulation."""
    return jax.lax.dot_general(a, b, dimension_numbers=dims,
                               preferred_element_type=jnp.float32)


def _visible(q0, k0, shape, q_axis: int, causal: bool,
             true_sk: Optional[int], window: Optional[int] = None):
    """Which entries of a score tile count: keys inside the true length
    (``true_sk``; None where padded keys need no mask) and, if causal,
    at or before their query and, under a window, fewer than ``window``
    positions before it. ``q_axis`` is the tile's query axis."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    mask = None if true_sk is None else k_pos < true_sk
    if causal:
        mask = q_pos >= k_pos if mask is None else mask & (q_pos >= k_pos)
    if window is not None:
        mask = mask & (q_pos - k_pos < window)
    return mask


def _kv_tiles(qi, block_q: int, block_k: int, seq_k: int, causal: bool,
              window: Optional[int] = None):
    """(first, end) of the KV tiles query block ``qi`` loops over: all
    of them, if causal those up to the diagonal, and under a window
    only from the tile that holds the oldest key the block's first
    query sees. Tiles outside are skipped, not masked."""
    n_kv = seq_k // block_k
    if not causal:
        return 0, n_kv
    end = jnp.minimum(n_kv, pl.cdiv((qi + 1) * block_q, block_k))
    if window is None:
        return 0, end
    return jnp.maximum(0, qi * block_q - (window - 1)) // block_k, end


def _q_tiles(ki, block_q: int, block_k: int, seq_q: int, causal: bool,
             window: Optional[int] = None):
    """(first, end) of the query tiles KV block ``ki`` loops over: the
    mirror of ``_kv_tiles``."""
    n_q = seq_q // block_q
    if not causal:
        return 0, n_q
    first = (ki * block_k) // block_q
    if window is None:
        return first, n_q
    return first, jnp.minimum(
        n_q, pl.cdiv((ki + 1) * block_k - 1 + window, block_q))


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                      l_ref, *, causal: bool, sm_scale: float, block_k: int,
                      true_sk: int, window: Optional[int]):
    # One KV group's query block. q_ref, o_ref: [1, heads, block_q, H];
    # k_ref, v_ref: [1, 1, S_k_padded, H], the group's, whole; lse_ref:
    # [1, 1, heads, block_q]. Scratch, kept over the walk: acc_ref
    # [heads, H, block_q], m_ref and l_ref [heads, 1, block_q] float32.
    # Score tiles are [block_k, block_q]. ``true_sk`` hides KV rows
    # that exist only as block padding.
    heads, head_dim, block_q = acc_ref.shape
    qi = pl.program_id(2)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _MASKED)
    l_ref[...] = jnp.zeros_like(l_ref)
    span = max(u for u in range(1, _HEAD_RUN + 1) if heads % u == 0)
    shape = (block_k, block_q)
    k_row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    # a query's distance past a key, were both tiles to start at 0
    ahead = jax.lax.broadcasted_iota(jnp.int32, shape, 1) - k_row

    def tile(j, carry):
        start = pl.multiple_of(j * block_k, block_k)
        k_blk = k_ref[0, 0, pl.ds(start, block_k), :]
        v_blk = v_ref[0, 0, pl.ds(start, block_k), :]
        # what the group's heads share: which entries of the tile count
        visible = k_row + start < true_sk
        if causal:
            d = ahead + (qi * block_q - start)
            visible &= d >= 0
            if window is not None:
                visible &= d < window
        bias = jnp.where(visible, 0.0, _MASKED)

        def weights(e):
            """Head ``e``'s softmax over this tile: updates its m and l,
            -> (p in the value type, the factor its sums shrink by)."""
            # [block_k, block_q]
            s = _dot(k_blk, q_ref[0, e], _NT) * sm_scale + bias
            # Key 0 is visible to every query, so m is finite from the
            # first tile on and a masked score's exp is exactly 0. Under
            # a window a later query of the block may see nothing in the
            # first tiles: it gathers exp(0) there, and alpha is exactly
            # 0 at its first visible key (the diagonal at the latest),
            # which wipes that.
            m = m_ref[e]
            m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            m_ref[e] = m_new
            l_ref[e] = l_ref[e] * alpha + jnp.sum(p, axis=0, keepdims=True)
            return p.astype(v_blk.dtype), alpha

        def gather(e, p, alpha):
            acc_ref[e] = acc_ref[e] * alpha + _dot(v_blk, p, _TN)

        def run(i, carry):
            # ``span`` heads in straight-line code, and a head's second
            # product written after the next head's softmax: the
            # compiler keeps that order, so the MXU weighs one head's
            # values while the vector unit is on the next head's
            # exponentials. A longer run overlaps more, and Mosaic
            # compiles that much more code each time a program is
            # loaded (PERF.md §6, PR 34).
            first = i * span
            waiting = weights(first)
            for e in range(1, span):
                ready = weights(first + e)
                gather(first + e - 1, *waiting)
                waiting = ready
            gather(first + span - 1, *waiting)
            return carry

        if heads == span:
            return run(0, carry)
        return jax.lax.fori_loop(0, heads // span, run, carry)

    jax.lax.fori_loop(
        *_kv_tiles(qi, block_q, block_k, k_ref.shape[2], causal, window),
        tile, 0)

    def write(e, carry):        # no order to keep here: a loop will do
        l_safe = jnp.maximum(l_ref[e], 1e-30)
        o_ref[0, e] = (acc_ref[e] / l_safe).T.astype(o_ref.dtype)
        lse_ref[0, 0, pl.ds(e, 1), :] = m_ref[e] + jnp.log(l_safe)
        return carry

    jax.lax.fori_loop(0, heads, write, 0)


def _check_blocks(block_q: int, block_k: int, sqp: int,
                  interpret: bool) -> None:
    """Compiled-lowering constraints (interpret mode has no tiling):
    in-kernel dynamic-slice starts (j·block) must be provably
    8-aligned, and the LSE block's lane dim (block_q) must divide 128
    unless it covers the whole padded sequence. Blocks are NEVER
    shrunk to the sequence length — a non-tile seq would break the
    alignment proof; short sequences pad up to one block instead."""
    if interpret:
        return
    if block_q % 8 or block_k % 8:
        raise ValueError(
            f"flash attention blocks must be multiples of 8 for TPU "
            f"lowering, got ({block_q}, {block_k})")
    if sqp != block_q and block_q % 128:
        raise ValueError(
            f"block_q={block_q} must be a multiple of 128 (or cover "
            f"the whole padded sequence {sqp}) for TPU lowering")


def _lanes(h: int) -> int:
    """The width the forward gives a head in HBM (the backward keeps
    lane multiples): a lane multiple, but a width past one lane that is
    a whole number of half-lanes stays as it is (192: a block as wide as
    the array is a block Mosaic takes, and it pads the tile to its own
    tiling in VMEM, not in memory)."""
    if h > _LANE and h % (_LANE // 2) == 0:
        return h
    return _round_up(h, _LANE)


def _fold(x, seq: int, width: Optional[int] = None):
    """[B, S, N, H] -> [B·N, seq, H padded to ``width``, a lane multiple
    unless the forward names its own]: batch and heads fold into the
    grid, the sequence pads to ``seq`` (masked by the true lengths
    inside the kernels)."""
    b, s, n, h = x.shape
    x = x.transpose(0, 2, 1, 3).reshape(b * n, s, h)
    return jnp.pad(x, ((0, 0), (0, seq - s),
                       (0, (width or _round_up(h, _LANE)) - h)))


def _unfold(x, like):
    """The inverse of ``_fold`` for an array shaped like ``like``."""
    b, s, n, h = like.shape
    return x[:, :s, :h].reshape(b, n, s, h).transpose(0, 2, 1, 3)


# Traced once for each set of shapes and settings, and its operations
# laid into the caller's program as they are (``inline``: no call, no
# name, the same kernel): every ``pallas_call`` traces and lowers its
# body anew, a model makes one in every kind of layer and once more for
# the interpreter's branch, and a process pays that before it can look
# a program up in the compile cache (PERF.md §6, PR 34: the lowering of
# the Trinity cell's four programs).
@functools.partial(jax.jit, inline=True, static_argnames=(
    "causal", "sm_scale", "block_q", "block_k", "window", "interpret"))
def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, window,
               interpret):
    from jax.experimental.pallas import tpu as pltpu
    b, s_q, n, h = q.shape
    s_k, g = k.shape[1], k.shape[2]
    heads = n // g
    # q and k score over their own width, v and the result have v's
    hp, hv = _lanes(h), _lanes(v.shape[-1])
    block_q, block_k, sqp, skp = _blocks_for(s_q, s_k, block_q, block_k)
    _check_blocks(block_q, block_k, sqp, interpret)
    kernel = functools.partial(_flash_fwd_kernel, causal=causal,
                               sm_scale=sm_scale, block_k=block_k,
                               true_sk=s_k, window=window)

    def group(width):
        return pl.BlockSpec((1, heads, block_q, width),
                            lambda bi, gi, i: (bi, gi, i, 0))

    def whole(width):
        return pl.BlockSpec((1, 1, skp, width),
                            lambda bi, gi, i: (bi, gi, 0, 0))

    out, lse = pl.pallas_call(
        kernel,
        grid=(b, g, sqp // block_q),
        in_specs=[group(hp), whole(hp), whole(hv)],
        out_specs=[
            group(hv),
            pl.BlockSpec((1, 1, heads, block_q),
                         lambda bi, gi, i: (bi, gi, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n, sqp, hv), q.dtype),
            jax.ShapeDtypeStruct((b, g, heads, sqp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((heads, hv, block_q), jnp.float32),
                        pltpu.VMEM((heads, 1, block_q), jnp.float32),
                        pltpu.VMEM((heads, 1, block_q), jnp.float32)],
        interpret=interpret,
        # the tiles as VMEM holds them, K's and V's widths averaged
        **_compiler_params(block_q, block_k, skp,
                           (_round_up(hp, _LANE) + hv) // 2,
                           q.dtype.itemsize, heads),
    )(_fold(q, sqp, hp).reshape(b, n, sqp, hp),
      _fold(k, skp, hp).reshape(b, g, skp, hp),
      _fold(v, skp, hv).reshape(b, g, skp, hv))
    # lse stays PADDED [BN, sqp]: the only consumer (_flash_bwd, which
    # pads to the same lengths) needs it padded anyway — slicing here
    # would just be re-padded there.
    like = jax.ShapeDtypeStruct((b, s_q, n, v.shape[-1]), q.dtype)
    return (_unfold(out.reshape(b * n, sqp, hv), like),
            lse.reshape(b * n, sqp))


# Pallas BlockSpec blocks carry the leading singleton; squeeze inside.
def _squeeze_kernel(kernel):
    @functools.wraps(kernel)
    def wrapped(*refs, **kw):
        return kernel(*[r.at[0] for r in refs], **kw)
    return wrapped


# --------------------------------------------------------------------------
# Pallas backward kernels (FlashAttention-2 style)
# --------------------------------------------------------------------------
#
# Residuals are O and the per-row log-sum-exp L; probabilities are
# recomputed blockwise from them, so the backward — like the forward —
# never materializes an S×S matrix in HBM:
#   D_i  = rowsum(dO_i ∘ O_i)
#   P_ij = exp(q_i k_j^T · scale − L_i)
#   dV_j = Σ_i P_ij^T dO_i
#   dS_ij = P_ij ∘ (dO_i V_j^T − D_i) · scale
#   dQ_i = Σ_j dS_ij K_j ;  dK_j = Σ_i dS_ij^T Q_i
# The factor ``scale`` of dS is applied once, to the summed dQ and dK.


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, causal: bool, sm_scale: float,
                         block_k: int, true_sk: int,
                         window: Optional[int]):
    # q/do/dq: [block_q, H]; k/v: [S_k_padded, H]; lse/delta: [1, block_q]
    block_q, head_dim = q_ref.shape
    qi = pl.program_id(1)
    q = q_ref[:]
    do = do_ref[:]
    lse = lse_ref[0, :][:, None]
    delta = delta_ref[0, :][:, None]

    def tile(j, dq):
        start = pl.multiple_of(j * block_k, block_k)
        k_blk = k_ref[pl.ds(start, block_k), :]
        v_blk = v_ref[pl.ds(start, block_k), :]
        p = jnp.exp(_dot(q, k_blk, _NT) * sm_scale - lse)
        p = jnp.where(_visible(qi * block_q, j * block_k, p.shape, 0,
                               causal, true_sk, window), p, 0.0)
        ds = p * (_dot(do, v_blk, _NT) - delta)
        return dq + _dot(ds.astype(k_blk.dtype), k_blk, _NN)

    dq = jax.lax.fori_loop(
        *_kv_tiles(qi, block_q, block_k, k_ref.shape[0], causal, window),
        tile,
        jnp.zeros((block_q, head_dim), jnp.float32))
    dq_ref[:] = (dq * sm_scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, causal: bool, sm_scale: float,
                          block_q: int, window: Optional[int]):
    # k/v/dk/dv: [block_k, H]; q/do: [S_q_padded, H]; lse/delta: [1, S_q]
    # Score tiles are held transposed, [block_k, block_q]: every product
    # is then a · bᵀ or a · b as the MXU takes them, and lse and delta
    # broadcast along sublanes as they lie. Padded query rows are zero
    # rows of q and dO with delta 0 and a finite lse, so they add exact
    # zeros to dk and dv and need no mask.
    block_k, head_dim = k_ref.shape
    ki = pl.program_id(1)
    k = k_ref[:]
    v = v_ref[:]

    def tile(i, carry):
        dk, dv = carry
        start = pl.multiple_of(i * block_q, block_q)
        q_blk = q_ref[pl.ds(start, block_q), :]
        do_blk = do_ref[pl.ds(start, block_q), :]
        lse_blk = lse_ref[:, pl.ds(start, block_q)]         # [1, block_q]
        delta_blk = delta_ref[:, pl.ds(start, block_q)]
        p = jnp.exp(_dot(k, q_blk, _NT) * sm_scale - lse_blk)
        if causal:
            p = jnp.where(_visible(i * block_q, ki * block_k, p.shape, 1,
                                   True, None, window), p, 0.0)
        dv = dv + _dot(p.astype(do_blk.dtype), do_blk, _NN)
        ds = p * (_dot(v, do_blk, _NT) - delta_blk)
        dk = dk + _dot(ds.astype(q_blk.dtype), q_blk, _NN)
        return dk, dv

    # causal: query blocks before this one cannot see this kv block,
    # nor, under a window, those wholly past it
    dk, dv = jax.lax.fori_loop(
        *_q_tiles(ki, block_q, block_k, q_ref.shape[0], causal, window),
        tile,
        (jnp.zeros((block_k, head_dim), jnp.float32),
         jnp.zeros((block_k, head_dim), jnp.float32)))
    dk_ref[:] = (dk * sm_scale).astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


_flash_bwd_dq_kernel = _squeeze_kernel(_flash_bwd_dq_kernel)
_flash_bwd_dkv_kernel = _squeeze_kernel(_flash_bwd_dkv_kernel)


def _flash_bwd(q, k, v, out, lse, g, causal, sm_scale, block_q, block_k,
               window, interpret):
    b, s_q, n, h = q.shape
    s_k = k.shape[1]
    hp = _round_up(h, _LANE)
    block_q, block_k, sqp, skp = _blocks_for(s_q, s_k, block_q, block_k)
    # the two kernels take a K and a V for every query head: the
    # group's, repeated; the repeat's own transpose sums dk and dv over
    # a group below
    (kr, vr), sum_groups = jax.vjp(
        functools.partial(repeat_kv, n_heads=n), k, v)
    qt, kt, vt = _fold(q, sqp), _fold(kr, skp), _fold(vr, skp)
    dot, ot = _fold(g, sqp), _fold(out, sqp)
    # delta = rowsum(dO ∘ O): cheap elementwise outside the kernels
    delta = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32),
                    axis=-1)                              # [BN, S_q_pad]
    # Singleton middle axis: TPU blocks over the last two dims must
    # divide (8, 128) or equal the array dims — (1, block) over a 2-D
    # (BN, S) array does neither. lse arrives already padded to sqp
    # (the forward pads to the same lengths).
    assert lse.shape == (b * n, sqp), (lse.shape, sqp)
    lse3 = lse.reshape(b * n, 1, sqp)
    delta3 = delta.reshape(b * n, 1, sqp)

    _check_blocks(block_q, block_k, sqp, interpret)
    dq_kernel = functools.partial(_flash_bwd_dq_kernel, causal=causal,
                                  sm_scale=sm_scale, block_k=block_k,
                                  true_sk=s_k, window=window)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b * n, sqp // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, hp), lambda bn, i: (bn, i, 0)),
            pl.BlockSpec((1, skp, hp), lambda bn, i: (bn, 0, 0)),
            pl.BlockSpec((1, skp, hp), lambda bn, i: (bn, 0, 0)),
            pl.BlockSpec((1, block_q, hp), lambda bn, i: (bn, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bn, i: (bn, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda bn, i: (bn, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hp), lambda bn, i: (bn, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * n, sqp, hp), q.dtype),
        interpret=interpret,
        **_compiler_params(block_q, block_k, skp, hp, q.dtype.itemsize),
    )(qt, kt, vt, dot, lse3, delta3)

    dkv_kernel = functools.partial(_flash_bwd_dkv_kernel, causal=causal,
                                   sm_scale=sm_scale, block_q=block_q,
                                   window=window)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b * n, skp // block_k),
        in_specs=[
            pl.BlockSpec((1, sqp, hp), lambda bn, j: (bn, 0, 0)),
            pl.BlockSpec((1, block_k, hp), lambda bn, j: (bn, j, 0)),
            pl.BlockSpec((1, block_k, hp), lambda bn, j: (bn, j, 0)),
            pl.BlockSpec((1, sqp, hp), lambda bn, j: (bn, 0, 0)),
            pl.BlockSpec((1, 1, sqp), lambda bn, j: (bn, 0, 0)),
            pl.BlockSpec((1, 1, sqp), lambda bn, j: (bn, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, hp), lambda bn, j: (bn, j, 0)),
            pl.BlockSpec((1, block_k, hp), lambda bn, j: (bn, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * n, skp, hp), k.dtype),
            jax.ShapeDtypeStruct((b * n, skp, hp), v.dtype),
        ],
        interpret=interpret,
        **_compiler_params(block_k, block_q, sqp, hp, q.dtype.itemsize),
    )(qt, kt, vt, dot, lse3, delta3)

    return _unfold(dq, q), *sum_groups((_unfold(dk, kr), _unfold(dv, vr)))


def _for_lowering_platform(fn, interpret: Optional[bool], *arrays):
    """``fn(*arrays, interpret=...)`` with the flag chosen from the
    platform the arrays are LOWERED for — not the process's default
    backend, which an AOT compile for a described TPU does not have:
    the Pallas interpreter on CPU, the Mosaic kernel everywhere else,
    so a TPU program never carries the interpreter. An explicit
    ``interpret`` (the CPU tests) is taken as given."""
    if interpret is not None:
        return fn(*arrays, interpret=interpret)
    return jax.lax.platform_dependent(
        *arrays,
        cpu=functools.partial(fn, interpret=True),
        default=functools.partial(fn, interpret=False))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None):
    """Fused attention. ``q [B,S,N,H]``, ``k, v [B,S,G,H]`` with ``G``
    dividing ``N`` (grouped-query attention; ``G == N`` is plain
    multi-head) -> ``[B,S,N,H]``. Block sizes left at None are chosen
    from the shape (``_choose_blocks``). ``window`` (static, with
    ``causal``): key ``j`` counts for query ``i`` iff ``0 <= i - j <
    window``; the tiles wholly outside are skipped."""
    out, _res = _flash_vjp_fwd(q, k, v, causal, sm_scale, block_q,
                               block_k, interpret, window)
    return out


def _flash_vjp_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                   window):
    _check_window(window, causal)
    if (q.shape[2] % k.shape[2] or k.shape[:3] != v.shape[:3]
            or q.shape[-1] != k.shape[-1]):
        raise ValueError(f"the KV heads must divide the query heads, k "
                         f"and v agree in all but their width and q and k "
                         f"in theirs, got q {q.shape} k {k.shape} "
                         f"v {v.shape}")
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    out, lse = _for_lowering_platform(
        functools.partial(_flash_fwd, causal=causal, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k, window=window),
        interpret, q, k, v)
    # a remat policy that keeps these two (and q, k, v, which the layer
    # names) has every residual, so the backward never runs the
    # forward kernel again (models/transformer.py::remat_plan)
    out = checkpoint_name(out, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, sm_scale, block_q, block_k, interpret, window,
                   residuals, g):
    q, k, v = residuals[:3]
    if k.shape != v.shape:
        raise NotImplementedError(
            f"ray_tpu.ops.flash_attention's backward kernels take k and v "
            f"of one shape, got k {k.shape} v {v.shape}: keys wider than "
            f"values run forward only (serving)")
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    return _for_lowering_platform(
        functools.partial(_flash_bwd, causal=causal, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k, window=window),
        interpret, *residuals, g)


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)
