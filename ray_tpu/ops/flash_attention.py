"""Flash attention as a Pallas TPU kernel.

The reference has no attention kernels — attention enters via torch in
workloads hosted on it [SURVEY.md §2.5]. Here the fused blockwise
kernel is first-class: the MXU does the two matmuls per block, online
softmax keeps running (max, normalizer) so the S×S score matrix never
materializes in HBM (HBM bandwidth is the bottleneck, not FLOPs).

Forward is the Pallas kernel (grid over [batch×heads, query blocks],
KV streamed through VMEM in blocks, saving only (O, LSE) residuals);
backward is a Pallas FlashAttention-2 backward — blockwise dq/dk/dv
recomputed from (O, LSE), so no S×S probability matrix ever touches
HBM in either direction. Gradients are exact (grad-checked against the
dense reference in tests/test_attention.py, on real TPU lowering too).

TPU alignment (Mosaic): dynamic VMEM loads must sit at provably
8-aligned rows and block shapes must tile to (8, 128), so sequences
are PADDED to block multiples outside the kernels and padded rows are
masked by the true lengths — no data-dependent clamping inside the
kernel (a clamped start index cannot be statically proven aligned),
and the LSE/delta vectors carry a singleton middle axis so their
blocks satisfy the tiling rule.

Layout everywhere: [B, S, N, H].
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def mha_reference(q, k, v, *, causal: bool = True,
                  sm_scale: Optional[float] = None,
                  q_offset: int = 0, kv_offset: int = 0):
    """Dense attention, [B,S,N,H]. Offsets shift absolute positions for
    cross-shard causal masking (ring/ulysses callers)."""
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bqnh,bknh->bnqk", q, k,
                        preferred_element_type=jnp.float32) * sm_scale
    if causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        q_pos = q_offset + jnp.arange(s_q)
        k_pos = kv_offset + jnp.arange(s_k)
        mask = q_pos[:, None] >= k_pos[None, :]
        logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bnqk,bknh->bqnh", probs.astype(v.dtype), v)


def _pad_seq(x, block: int):
    """Pad axis 1 ([BN, S, H]) up to a multiple of ``block``."""
    pad = (-x.shape[1]) % block
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0)))


_LANE = 128


def _pad_head(x):
    """Pad the head dim ([BN, S, H]) to a lane multiple: Mosaic slices
    inside the kernel must be 128-aligned along lanes. Zero lanes are
    inert — q·kᵀ and p·v are unchanged, and their output/grad columns
    are zero (sliced away)."""
    pad = (-x.shape[2]) % _LANE
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad)))


# --------------------------------------------------------------------------
# Pallas forward kernel
# --------------------------------------------------------------------------

def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                      causal: bool, sm_scale: float, block_k: int,
                      true_sk: int):
    # q_ref: [block_q, H]; k_ref/v_ref: [S_k_padded, H];
    # o_ref: [block_q, H]; lse_ref: [1, block_q].
    # ``true_sk`` masks KV rows that exist only as block padding.
    block_q, head_dim = q_ref.shape
    seq_k = k_ref.shape[0]
    qi = pl.program_id(1)

    q = q_ref[:].astype(jnp.float32) * sm_scale
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    n_kv = seq_k // block_k

    def body(j, carry):
        o, m, l = carry
        start = pl.multiple_of(j * block_k, block_k)
        k_blk = k_ref[pl.ds(start, block_k), :]
        v_blk = v_ref[pl.ds(start, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk.astype(jnp.float32),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # [block_q, block_k]
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < true_sk
        if causal:
            mask = mask & (q_pos >= k_pos)
        s = jnp.where(mask, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1)
        pv = jax.lax.dot_general(
            p, v_blk.astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_new = o * alpha[:, None] + pv
        return o_new, m_new, l_new

    o = jnp.zeros((block_q, head_dim), jnp.float32)
    m = jnp.full((block_q,), -1e30, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    if causal:
        # only blocks at or before the diagonal contribute
        n_iter = jnp.minimum(n_kv, pl.cdiv((qi + 1) * block_q, block_k))
    else:
        n_iter = n_kv
    o, m, l = jax.lax.fori_loop(0, n_iter, body, (o, m, l))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[:] = (o / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0, :] = m + jnp.log(l_safe)


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


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    b, s_q, n, h = q.shape
    s_k = k.shape[1]
    # fold batch and heads into the grid; [BN, S, H] layout per head;
    # pad sequences to block multiples (masked by true lengths inside)
    qt = _pad_head(_pad_seq(
        q.transpose(0, 2, 1, 3).reshape(b * n, s_q, h), block_q))
    kt = _pad_head(_pad_seq(
        k.transpose(0, 2, 1, 3).reshape(b * n, s_k, h), block_k))
    vt = _pad_head(_pad_seq(
        v.transpose(0, 2, 1, 3).reshape(b * n, s_k, h), block_k))
    sqp, skp, hp = qt.shape[1], kt.shape[1], qt.shape[2]
    _check_blocks(block_q, block_k, sqp, interpret)
    grid = (b * n, sqp // block_q)
    kernel = functools.partial(_flash_fwd_kernel, causal=causal,
                               sm_scale=sm_scale, block_k=block_k,
                               true_sk=s_k)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hp), lambda bn, i: (bn, i, 0)),
            pl.BlockSpec((1, skp, hp), lambda bn, i: (bn, 0, 0)),
            pl.BlockSpec((1, skp, hp), lambda bn, i: (bn, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, hp), lambda bn, i: (bn, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bn, i: (bn, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * n, sqp, hp), q.dtype),
            jax.ShapeDtypeStruct((b * n, 1, sqp), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    out = out[:, :s_q, :h].reshape(b, n, s_q, h).transpose(0, 2, 1, 3)
    # lse stays PADDED [BN, sqp]: the only consumer (_flash_bwd, same
    # block sizes) needs it padded anyway — slicing here would just be
    # re-padded there.
    return out, lse.reshape(b * n, sqp)


# Pallas BlockSpec blocks carry the leading singleton; squeeze inside.
def _squeeze_kernel(kernel):
    @functools.wraps(kernel)
    def wrapped(*refs, **kw):
        return kernel(*[r.at[0] for r in refs], **kw)
    return wrapped


_flash_fwd_kernel = _squeeze_kernel(_flash_fwd_kernel)


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


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, causal: bool, sm_scale: float,
                         block_k: int, true_sk: int):
    # q/do/dq: [block_q, H]; k/v: [S_k_padded, H]; lse/delta: [1, block_q]
    block_q, head_dim = q_ref.shape
    seq_k = k_ref.shape[0]
    qi = pl.program_id(1)

    q = q_ref[:].astype(jnp.float32)
    do = do_ref[:].astype(jnp.float32)
    lse = lse_ref[0, :]
    delta = delta_ref[0, :]
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    n_kv = seq_k // block_k

    def body(j, dq):
        start = pl.multiple_of(j * block_k, block_k)
        k_blk = k_ref[pl.ds(start, block_k), :].astype(jnp.float32)
        v_blk = v_ref[pl.ds(start, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < true_sk
        if causal:
            mask = mask & (q_pos >= k_pos)
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(
            do, v_blk, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        return dq + jax.lax.dot_general(
            ds, k_blk, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        n_iter = jnp.minimum(n_kv, pl.cdiv((qi + 1) * block_q, block_k))
    else:
        n_iter = n_kv
    dq = jax.lax.fori_loop(
        0, n_iter, body, jnp.zeros((block_q, head_dim), jnp.float32))
    dq_ref[:] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, causal: bool, sm_scale: float,
                          block_q: int, true_sq: int):
    # k/v/dk/dv: [block_k, H]; q/do: [S_q_padded, H]; lse/delta: [1, S_q]
    block_k, head_dim = k_ref.shape
    seq_q = q_ref.shape[0]
    ki = pl.program_id(1)

    k = k_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    n_q = seq_q // block_q

    def body(i, carry):
        dk, dv = carry
        start = pl.multiple_of(i * block_q, block_q)
        q_blk = q_ref[pl.ds(start, block_q), :].astype(jnp.float32)
        do_blk = do_ref[pl.ds(start, block_q), :].astype(jnp.float32)
        lse_blk = lse_ref[0, pl.ds(start, block_q)]
        delta_blk = delta_ref[0, pl.ds(start, block_q)]
        s = jax.lax.dot_general(
            q_blk, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        q_pos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        mask = q_pos < true_sq          # padded query rows contribute 0
        if causal:
            mask = mask & (q_pos >= k_pos)
        p = jnp.where(mask, jnp.exp(s - lse_blk[:, None]), 0.0)
        dv = dv + jax.lax.dot_general(
            p, do_blk, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do_blk, v, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_blk[:, None]) * sm_scale
        dk = dk + jax.lax.dot_general(
            ds, q_blk, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk, dv

    if causal:
        # first query block whose rows can attend to this kv block
        i0 = (ki * block_k) // block_q
    else:
        i0 = 0
    dk, dv = jax.lax.fori_loop(
        i0, n_q, body,
        (jnp.zeros((block_k, head_dim), jnp.float32),
         jnp.zeros((block_k, head_dim), jnp.float32)))
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


_flash_bwd_dq_kernel = _squeeze_kernel(_flash_bwd_dq_kernel)
_flash_bwd_dkv_kernel = _squeeze_kernel(_flash_bwd_dkv_kernel)


def _flash_bwd(q, k, v, out, lse, g, causal, sm_scale, block_q, block_k,
               interpret):
    b, s_q, n, h = q.shape
    s_k = k.shape[1]
    qt = _pad_head(_pad_seq(
        q.transpose(0, 2, 1, 3).reshape(b * n, s_q, h), block_q))
    kt = _pad_head(_pad_seq(
        k.transpose(0, 2, 1, 3).reshape(b * n, s_k, h), block_k))
    vt = _pad_head(_pad_seq(
        v.transpose(0, 2, 1, 3).reshape(b * n, s_k, h), block_k))
    dot = _pad_head(_pad_seq(
        g.transpose(0, 2, 1, 3).reshape(b * n, s_q, h), block_q))
    ot = _pad_head(_pad_seq(
        out.transpose(0, 2, 1, 3).reshape(b * n, s_q, h), block_q))
    sqp, skp, hp = qt.shape[1], kt.shape[1], qt.shape[2]
    _check_blocks(block_q, block_k, sqp, interpret)
    # delta = rowsum(dO ∘ O): cheap elementwise outside the kernels
    delta = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32),
                    axis=-1)                              # [BN, S_q_pad]
    # Singleton middle axis: TPU blocks over the last two dims must
    # divide (8, 128) or equal the array dims — (1, block) over a 2-D
    # (BN, S) array does neither. lse arrives already padded to sqp
    # (same block sizes as the forward).
    assert lse.shape == (b * n, sqp), (lse.shape, sqp)
    lse3 = lse.reshape(b * n, 1, sqp)
    delta3 = delta.reshape(b * n, 1, sqp)

    dq_kernel = functools.partial(_flash_bwd_dq_kernel, causal=causal,
                                  sm_scale=sm_scale, block_k=block_k,
                                  true_sk=s_k)
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
    )(qt, kt, vt, dot, lse3, delta3)

    dkv_kernel = functools.partial(_flash_bwd_dkv_kernel, causal=causal,
                                   sm_scale=sm_scale, block_q=block_q,
                                   true_sq=s_q)
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
    )(qt, kt, vt, dot, lse3, delta3)

    unfold = lambda x, s: x[:, :s, :h].reshape(b, n, s, h).transpose(
        0, 2, 1, 3)
    return unfold(dq, s_q), unfold(dk, s_k), unfold(dv, s_k)


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None):
    """Fused attention. [B,S,N,H] -> [B,S,N,H]."""
    out, _res = _flash_vjp_fwd(q, k, v, causal, sm_scale, block_q,
                               block_k, interpret)
    return out


def _flash_vjp_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    out, lse = _for_lowering_platform(
        functools.partial(_flash_fwd, causal=causal, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k),
        interpret, q, k, v)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, sm_scale, block_q, block_k, interpret,
                   residuals, g):
    q = residuals[0]
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    return _for_lowering_platform(
        functools.partial(_flash_bwd, causal=causal, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k),
        interpret, *residuals, g)


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)
