"""Sharded training step for the flagship transformer.

One jitted SPMD program: loss → grads → optax update, with params and
optimizer state laid out by ``param_specs`` over the mesh (fsdp/tp) and
the batch split over (dp, fsdp) × sp. Gradient reduction is whatever
XLA inserts for the sharding — psum over ICI — not an explicit
collective call; that is the TPU replacement for the reference's
torch-DDP-over-NCCL path in Ray Train (SURVEY.md §2.5).
"""

from __future__ import annotations

import functools
import math
import time
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models.transformer import (
    KEEP_LAYER, RematPlan, TransformerConfig, init_params, loss_fn,
    param_specs, remat_plan)
from ray_tpu.parallel.mesh import tree_shardings
from ray_tpu.util import tracing


class TrainState(NamedTuple):
    step: jax.Array
    params: Any
    opt_state: Any


def make_optimizer(lr: float = 3e-4, weight_decay: float = 0.1,
                   warmup_steps: int = 100,
                   total_steps: int = 10_000) -> optax.GradientTransformation:
    sched = optax.warmup_cosine_decay_schedule(
        0.0, lr, warmup_steps, max(total_steps, warmup_steps + 1))
    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(sched, b1=0.9, b2=0.95, weight_decay=weight_decay),
    )


def state_specs(cfg: TransformerConfig, tx: optax.GradientTransformation,
                params_like) -> TrainState:
    """PartitionSpec tree for the full TrainState: optimizer moments
    shard exactly like their params; scalars replicated."""
    pspecs = param_specs(cfg)
    opt_shape = jax.eval_shape(tx.init, params_like)

    # Adam's mu/nu mirror the param tree — give them the param specs;
    # every other optimizer leaf (counts etc.) is replicated.
    def map_opt(node):
        if isinstance(node, optax.ScaleByAdamState):
            return node._replace(count=P(), mu=pspecs, nu=pspecs)
        return node

    opt_specs = jax.tree.map(
        map_opt, opt_shape,
        is_leaf=lambda n: isinstance(n, optax.ScaleByAdamState))
    opt_specs = jax.tree.map(
        lambda leaf: leaf if isinstance(leaf, P) else P(),
        opt_specs,
        is_leaf=lambda leaf: isinstance(leaf, P))
    return TrainState(step=P(), params=pspecs, opt_state=opt_specs)


def init_state(key: jax.Array, cfg: TransformerConfig,
               tx: optax.GradientTransformation,
               mesh: Optional[Mesh] = None) -> TrainState:
    """Initialize params + optimizer state, sharded over the mesh (the
    init itself is jitted with output shardings so large models never
    materialize replicated)."""
    def _init(k):
        params = init_params(k, cfg)
        return TrainState(step=jnp.zeros((), jnp.int32),
                          params=params, opt_state=tx.init(params))

    if mesh is None:
        return _init(key)
    params_shape = jax.eval_shape(lambda k: init_params(k, cfg), key)
    specs = state_specs(cfg, tx, params_shape)
    shardings = tree_shardings(mesh, specs)
    return jax.jit(_init, out_shardings=shardings)(key)


def _memory_limit(device) -> Optional[int]:
    """The device's memory in bytes; None where it reports none (the
    CPU does not; a described device that is not attached raises)."""
    try:
        stats = device.memory_stats()
    except jax.errors.JaxRuntimeError:
        return None
    return (stats or {}).get("bytes_limit")


def _plan_remat(cfg: TransformerConfig, tx, mesh: Optional[Mesh],
                state: TrainState, tokens, flash_here: bool,
                levels: Optional[Tuple[int, ...]]) -> RematPlan:
    """``remat_plan`` for the step being traced, from what the trace can
    see: the batch's shape, the state's bytes on one device (laid out
    as ``state_specs`` lays it, which is how ``init_state`` places it)
    and the memory limit of the device the step runs on. A device that
    reports none (the CPU, a described topology) and attention that is
    not the flash kernel chosen here (ring, a caller's own) get the
    plan that recomputes every layer. Leaves one ``train.remat_plan``
    record in the span ring."""
    leaves = jax.tree.leaves(state)
    shapes = [leaf.shape for leaf in leaves]
    if mesh is not None:
        shardings = tree_shardings(mesh, state_specs(cfg, tx, state.params))
        shapes = [h.shard_shape(shape) for h, shape in
                  zip(jax.tree.leaves(shardings), shapes)]
    held = sum(math.prod(shape) * leaf.dtype.itemsize
               for shape, leaf in zip(shapes, leaves))
    devices = mesh.local_devices if mesh is not None else jax.devices()
    limit = _memory_limit(devices[0]) if flash_here and devices else None
    plan = remat_plan(cfg, *tokens.shape, held, limit,
                      dict(mesh.shape) if mesh is not None else None,
                      levels)
    now = time.perf_counter_ns()
    tracing.record(
        "train.remat_plan", now, now, layers=cfg.n_layers,
        layers_kept_whole=plan.levels.count(KEEP_LAYER),
        kept_bytes=plan.kept_bytes, budget_bytes=plan.budget_bytes,
        recompute_flops=plan.recompute_flops,
        layer_forward_flops=plan.layer_forward_flops)
    return plan


def make_train_step(cfg: TransformerConfig,
                    tx: optax.GradientTransformation,
                    mesh: Optional[Mesh] = None,
                    attn_fn=None,
                    donate: bool = True,
                    batch_keys: Tuple[str, ...] = ("tokens",),
                    remat_levels: Optional[Tuple[int, ...]] = None):
    """Returns jitted (state, batch) -> (state, metrics). ``batch_keys``
    must name every key of the batch dict (e.g. add "loss_mask") so the
    sharding pytree matches. With an sp>1 mesh and no explicit
    ``attn_fn``, attention runs as ring attention over the sp axis;
    with ``cfg.use_flash`` on any other mesh, as the flash kernel on
    each device's batch/head shard. Under ``cfg.remat`` each layer
    keeps for the backward pass what ``remat_plan`` finds room for when
    the step is traced; ``remat_levels`` given are taken as they are
    (tests)."""
    sp = mesh.shape.get("sp", 1) if mesh is not None else 1
    flash_here = attn_fn is None and cfg.use_flash and sp == 1
    if attn_fn is None and mesh is not None:
        from ray_tpu.ops import make_attention_fn
        if sp > 1:
            attn_fn = make_attention_fn(mesh, impl="ring")
        elif cfg.use_flash:
            attn_fn = make_attention_fn(mesh, impl="flash")

    def train_step(state: TrainState, batch: Dict[str, jax.Array]):
        levels = _plan_remat(cfg, tx, mesh, state, batch["tokens"],
                             flash_here, remat_levels).levels \
            if cfg.remat else None
        loss, grads = jax.value_and_grad(loss_fn)(
            state.params, batch, cfg, attn_fn, levels)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        metrics = {
            "loss": loss,
            "grad_norm": optax.global_norm(grads),
            "step": state.step,
        }
        return TrainState(state.step + 1, params, opt_state), metrics

    kwargs = {}
    if mesh is not None:
        batch_sharding = NamedSharding(mesh, P(("dp", "fsdp"), "sp"))
        kwargs["in_shardings"] = (None,
                                  {k: batch_sharding for k in batch_keys})
    if donate:
        kwargs["donate_argnums"] = (0,)
    return jax.jit(train_step, **kwargs)
