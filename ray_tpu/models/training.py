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
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models.transformer import (
    TransformerConfig, init_params, loss_fn, param_specs)
from ray_tpu.parallel.mesh import tree_shardings


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


def make_train_step(cfg: TransformerConfig,
                    tx: optax.GradientTransformation,
                    mesh: Optional[Mesh] = None,
                    attn_fn=None,
                    donate: bool = True,
                    batch_keys: Tuple[str, ...] = ("tokens",)):
    """Returns jitted (state, batch) -> (state, metrics). ``batch_keys``
    must name every key of the batch dict (e.g. add "loss_mask") so the
    sharding pytree matches. With an sp>1 mesh and no explicit
    ``attn_fn``, attention runs as ring attention over the sp axis;
    with ``cfg.use_flash`` on any other mesh, as the flash kernel on
    each device's batch/head shard."""
    if attn_fn is None and mesh is not None:
        from ray_tpu.ops import make_attention_fn
        if mesh.shape.get("sp", 1) > 1:
            attn_fn = make_attention_fn(mesh, impl="ring")
        elif cfg.use_flash:
            attn_fn = make_attention_fn(mesh, impl="flash")

    def train_step(state: TrainState, batch: Dict[str, jax.Array]):
        loss, grads = jax.value_and_grad(loss_fn)(
            state.params, batch, cfg, attn_fn)
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
