"""Pipeline parallelism: staged transformer over the ``pp`` mesh axis.

The reference delegates PP to frameworks hosted on it (vLLM/DeepSpeed
actor pipelines, aDAG as transport — SURVEY.md §2.5 [UNVERIFIED —
mount empty]). The TPU-native design runs the WHOLE pipeline as one
jitted SPMD program: ``shard_map`` over the ``pp`` axis, each device
holding its stage's layer stack, activations crossing stages via
``ppermute`` inside a ``lax.scan`` over the microbatch schedule — no
per-hop host involvement, XLA overlaps the collective with compute.

Schedule: synchronous fill/drain (GPipe) — step t has stage s working
on microbatch t−s; after S−1 warmup steps every stage is busy each
step (the same steady-state occupancy 1F1B reaches). Peak activation
memory is bounded by rematerializing each stage's forward around the
scan (``jax.checkpoint``), so the backward re-derives block internals
instead of stashing them per microbatch.

Works with any per-stage function; the transformer integration stages
``models.transformer._block_forward`` stacks.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def stack_pipeline_blocks(blocks: List[Dict], num_stages: int):
    """[layer-list of block pytrees] -> stacked pytree with leading
    [num_stages, layers_per_stage] axes (leading axis sharded over pp).
    """
    n_layers = len(blocks)
    if n_layers % num_stages != 0:
        raise ValueError(f"{n_layers} layers not divisible by "
                         f"{num_stages} stages")
    per = n_layers // num_stages
    stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *blocks)
    return jax.tree.map(
        lambda a: a.reshape(num_stages, per, *a.shape[1:]), stacked)


def pipeline_apply(mesh: Mesh, stacked_blocks, x: jax.Array,
                   positions: jax.Array, cfg, num_microbatches: int,
                   attn_fn=None) -> jax.Array:
    """Apply the staged block stack to ``x`` [B, S, D] with a GPipe
    microbatch schedule over the mesh's ``pp`` axis.

    ``positions`` must be identical across microbatches (the standard
    [B, S] arange layout) — they ride replicated, not through the
    rotation.
    """
    from ray_tpu.models.transformer import _block_forward

    num_stages = mesh.shape["pp"]
    batch = x.shape[0]
    if batch % num_microbatches != 0:
        raise ValueError(f"batch {batch} not divisible by "
                         f"{num_microbatches} microbatches")
    mb = batch // num_microbatches
    xm = x.reshape(num_microbatches, mb, *x.shape[1:])
    pos0 = positions[:mb]

    block_specs = jax.tree.map(lambda _: P("pp"), stacked_blocks)
    other_axes = tuple(a for a in mesh.axis_names if a != "pp")

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(block_specs, P(), P()),
        out_specs=P(), check_vma=False)
    def run(blocks, xm, pos):
        # local stage slab: [1, per, ...] -> [per, ...]
        blocks = jax.tree.map(lambda a: a[0], blocks)
        stage = jax.lax.axis_index("pp")
        M = xm.shape[0]
        T = M + num_stages - 1

        def stage_fn(x_mb):
            def layer(h, blk):
                return _block_forward(blk, h, pos, cfg,
                                      attn_fn=attn_fn), None
            y, _ = jax.lax.scan(layer, x_mb, blocks)
            return y

        if cfg.remat:
            stage_fn = jax.checkpoint(stage_fn)

        def step(carry, t):
            state, outputs = carry
            in_idx = jnp.clip(t, 0, M - 1)
            x_in = jnp.where(stage == 0, xm[in_idx], state)
            y = stage_fn(x_in)
            out_t = t - (num_stages - 1)
            out_idx = jnp.clip(out_t, 0, M - 1)
            is_out = (out_t >= 0) & (stage == num_stages - 1)
            outputs = outputs.at[out_idx].set(
                jnp.where(is_out, y, outputs[out_idx]))
            # rotate activations one stage forward around the ring
            state = jax.lax.ppermute(
                y, "pp",
                [(i, (i + 1) % num_stages) for i in range(num_stages)])
            return (state, outputs), None

        init = (jnp.zeros_like(xm[0]), jnp.zeros_like(xm))
        (_, outputs), _ = jax.lax.scan(step, init, jnp.arange(T))
        # outputs live on the last stage; replicate for the caller
        outputs = jax.lax.psum(
            jnp.where(stage == num_stages - 1, outputs, 0.0), "pp")
        return outputs

    out = run(stacked_blocks, xm, pos0)
    return out.reshape(batch, *out.shape[2:])


def forward_pipelined(params, tokens: jax.Array, cfg, mesh: Mesh,
                      num_microbatches: int,
                      positions: Optional[jax.Array] = None,
                      attn_fn=None) -> jax.Array:
    """Pipelined twin of ``models.transformer.forward``: embed ->
    staged blocks over pp -> final norm + unembed. tokens [B, S] ->
    logits [B, S, V]."""
    from ray_tpu.models.transformer import rms_norm

    num_stages = mesh.shape["pp"]
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :],
            tokens.shape)
    x = params["embed"].astype(cfg.dtype)[tokens]
    stacked = stack_pipeline_blocks(params["blocks"], num_stages)
    x = pipeline_apply(mesh, stacked, x, positions, cfg,
                       num_microbatches, attn_fn=attn_fn)
    x = rms_norm(x, params["final_norm"])
    return (x @ params["unembed"].astype(cfg.dtype)).astype(jnp.float32)
