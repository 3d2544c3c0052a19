"""Sequence/context parallelism: ring attention and Ulysses.

ABSENT from the reference [SURVEY.md §5 "Long-context"]: royf/ray
scales sequence length only by hosting external frameworks. Here it is
first-class, built on the ICI torus:

- **Ring attention** (blockwise attention + ``ppermute`` KV rotation):
  each device keeps its Q shard resident and sees every KV shard once
  as they rotate around the ``sp`` ring; online softmax (running max +
  normalizer) accumulates exactly, so the result is bit-comparable to
  dense attention without ever materializing the full S×S scores. KV
  rotation overlaps with block compute (XLA schedules the ppermute DMA
  against the matmuls).
- **Ulysses**: all-to-all re-shard — heads scatter over ``sp`` while
  the sequence gathers, attention runs dense per head, then the
  inverse all-to-all. Cheaper at moderate S (2 all-to-alls vs sp-1
  permutes) but caps sp at the head count; ring has no such cap.

Both are per-shard functions closed over a mesh via ``jax.shard_map``
(``make_attention_fn``), differentiable end-to-end (scan + ppermute
have transpose rules), so the same code path serves train and serve.

Layout: [B, S, N, H]; ``sp`` shards S; ``tp`` shards N.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.flash_attention import (
    flash_attention, mha_reference, repeat_kv)


def ring_attention_shard(q, k, v, *, axis_name: str = "sp",
                         causal: bool = True,
                         sm_scale: Optional[float] = None):
    """Per-shard ring attention body (call inside shard_map).

    q, k, v: [B, S_local, N, H] — this device's sequence shard.
    """
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    sp = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, s_loc, n, h = q.shape

    q32 = q.astype(jnp.float32)
    q_pos = idx * s_loc + jnp.arange(s_loc)          # global query positions
    fwd_perm = [(r, (r + 1) % sp) for r in range(sp)]

    def step(carry, j):
        o, m, l, k_blk, v_blk = carry
        src = (idx - j) % sp                          # origin shard of k_blk
        logits = jnp.einsum("bqnh,bknh->bnqk", q32,
                            k_blk.astype(jnp.float32),
                            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            k_pos = src * s_loc + jnp.arange(s_loc)
            mask = q_pos[:, None] >= k_pos[None, :]   # [Sq, Sk]
            logits = jnp.where(mask[None, None], logits, -1e30)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))   # [B,N,Sq]
        p = jnp.exp(logits - m_new[..., None])
        if causal:
            p = jnp.where(mask[None, None], p, 0.0)
        alpha = jnp.exp(m - m_new)                    # [B,N,Sq]
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bnqk,bknh->bqnh", p,
                        v_blk.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        o_new = o * alpha.transpose(0, 2, 1)[..., None] + pv
        # rotate KV one hop around the ring (overlaps with next block)
        k_next = lax.ppermute(k_blk, axis_name, fwd_perm)
        v_next = lax.ppermute(v_blk, axis_name, fwd_perm)
        return (o_new, m_new, l_new, k_next, v_next), None

    o0 = jnp.zeros((b, s_loc, n, h), jnp.float32)
    m0 = jnp.full((b, n, s_loc), -1e30, jnp.float32)
    l0 = jnp.zeros((b, n, s_loc), jnp.float32)
    (o, _m, l, _k, _v), _ = lax.scan(step, (o0, m0, l0, k, v),
                                     jnp.arange(sp))
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ulysses_attention_shard(q, k, v, *, axis_name: str = "sp",
                            causal: bool = True,
                            sm_scale: Optional[float] = None,
                            inner: str = "reference"):
    """Per-shard Ulysses body (call inside shard_map).

    all-to-all: [B, S/sp, N, H] -> [B, S, N/sp, H], dense attention
    over the full sequence for this device's head subset, inverse
    all-to-all back. Requires local head count divisible by sp.
    """
    sp = lax.axis_size(axis_name)
    n = q.shape[2]
    if n % sp != 0:
        raise ValueError(f"ulysses needs heads ({n}) divisible by "
                         f"sp ({sp})")
    def scatter_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)
    def gather_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)
    qg, kg, vg = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    if inner == "flash":
        out = flash_attention(qg, kg, vg, causal, sm_scale)
    else:
        out = mha_reference(qg, kg, vg, causal=causal, sm_scale=sm_scale)
    return gather_heads(out)


def make_attention_fn(mesh: Optional[Mesh] = None, *,
                      impl: str = "auto", causal: bool = True,
                      batch_axes=("dp", "fsdp"), sp_axis: str = "sp",
                      tp_axis: str = "tp"):
    """Build the attn_fn the transformer block calls: q [B,S,N,H], k
    and v at their KV heads [B,S,G,H] (globally sharded) -> attention
    output. The flash kernel takes the groups as they are; ring,
    ulysses and the dense reference need equal head counts (their
    all-to-all and einsums split heads) and repeat K and V first.

    impl: "auto" | "ring" | "ulysses" | "flash" | "reference".
    With a mesh whose ``sp`` axis > 1, "auto" = ring. Without, "auto"
    = flash (pallas on TPU, interpreter on CPU); given a mesh, flash
    runs per device on its batch/head shard, because the compiler
    cannot partition a Mosaic kernel by itself.
    """
    sp = (mesh.shape.get(sp_axis, 1) if mesh is not None else 1)
    if impl == "auto":
        impl = "ring" if sp > 1 else "flash"
    if impl in ("ring", "ulysses") and (mesh is None or sp <= 1):
        raise ValueError(f"impl={impl!r} needs a mesh with {sp_axis}>1")

    def at_query_heads(fn):
        return lambda q, k, v: fn(q, *repeat_kv(k, v, q.shape[2]))

    if impl == "reference":
        return at_query_heads(functools.partial(mha_reference,
                                                causal=causal))
    if impl == "flash":
        body = lambda q, k, v: flash_attention(q, k, v, causal)  # noqa: E731
        if mesh is None:
            return body
        spec = P(batch_axes, None, tp_axis, None)   # whole sequences
    else:
        spec = P(batch_axes, sp_axis, tp_axis, None)
        body = functools.partial(
            ring_attention_shard if impl == "ring"
            else ulysses_attention_shard,
            axis_name=sp_axis, causal=causal)
    sharded = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=spec, check_vma=False)
    repeated = at_query_heads(sharded)
    if impl != "flash":
        return repeated
    tp = mesh.shape.get(tp_axis, 1)
    # a head shard holds whole groups where tp divides the KV heads
    return lambda q, k, v: (
        sharded if k.shape[2] % tp == 0 else repeated)(q, k, v)
