"""Flagship transformer: sharded train step, ring-attention parity,
a routed layer in the pattern, and the driver entry hooks."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models import (
    TransformerConfig,
    forward,
    init_params,
    init_state,
    loss_fn,
    make_optimizer,
    make_train_step,
)
from ray_tpu.parallel.mesh import MeshSpec, make_mesh


def _cfg(**kw):
    kw.setdefault("vocab_size", 128)
    kw.setdefault("d_model", 64)
    kw.setdefault("n_layers", 2)
    kw.setdefault("n_heads", 4)
    kw.setdefault("n_kv_heads", 2)
    kw.setdefault("d_ff", 128)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("dtype", jnp.float32)
    return TransformerConfig(**kw)


def _tokens(b=4, s=64, vocab=128, seed=0):
    return jnp.asarray(
        np.random.RandomState(seed).randint(0, vocab, (b, s)), jnp.int32)


def test_sp_mesh_loss_matches_single_device():
    cfg = _cfg()
    tokens = _tokens()
    params = init_params(jax.random.PRNGKey(0), cfg)
    dense = float(loss_fn(params, {"tokens": tokens}, cfg))

    mesh = make_mesh(MeshSpec.auto(8, sp=4), jax.devices()[:8])
    from ray_tpu.ops import make_attention_fn
    attn = make_attention_fn(mesh, impl="ring")
    sharding = NamedSharding(mesh, P(("dp", "fsdp"), "sp"))
    toks = jax.device_put(tokens, sharding)
    with mesh:
        ring = float(jax.jit(
            lambda p, b: loss_fn(p, b, cfg, attn))(params,
                                                   {"tokens": toks}))
    np.testing.assert_allclose(ring, dense, rtol=1e-4)


def test_train_step_learns_on_sp_mesh():
    cfg = _cfg()
    mesh = make_mesh(MeshSpec.auto(8, tp=2, sp=2), jax.devices()[:8])
    tx = make_optimizer(lr=1e-2, total_steps=50)
    with mesh:
        state = init_state(jax.random.PRNGKey(0), cfg, tx, mesh)
        step = make_train_step(cfg, tx, mesh)
        sharding = NamedSharding(mesh, P(("dp", "fsdp"), "sp"))
        tokens = jax.device_put(_tokens(), sharding)
        losses = []
        for _ in range(8):
            state, metrics = step(state, {"tokens": tokens})
            losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses
    assert np.isfinite(losses).all()


def _moe_cfg(**kw):
    from ray_tpu.models import LayerSpec
    return _cfg(layers=(LayerSpec(), LayerSpec(experts=True)), n_experts=4,
                experts_held=(0, 4), expert_top_k=2, d_ff_expert=64,
                n_shared_experts=1, **kw)


def test_moe_forward_and_grads():
    """A routed layer in the pattern: the forward is finite and counts
    every (token, expert) pair once; a gradient through the routed
    layer names what is missing (the grouped matmul's backward)."""
    import pytest

    from ray_tpu.models import forward_with_stats
    cfg = _moe_cfg()
    tokens = _tokens(b=2, s=32)
    params = init_params(jax.random.PRNGKey(1), cfg)
    assert "router" in params["blocks"][1] and "wi" in params["blocks"][0]
    logits, stats = forward_with_stats(params, tokens, cfg)
    assert np.isfinite(np.asarray(logits)).all()
    assert stats["moe_rows"].shape == (1, 4)
    assert int(stats["moe_rows"].sum()) == 2 * 32 * 2
    with pytest.raises(NotImplementedError, match="gmm has no backward"):
        jax.grad(loss_fn)(params, {"tokens": tokens}, cfg)


def test_mistral_forward_is_bit_equal_to_the_parents():
    """The default pattern is the dense block repeated: its logits on
    one seed are those of the forward before the layer pattern (the
    digest was taken at commit 2e83d2e with this test's inputs)."""
    import hashlib
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    logits = np.asarray(jax.jit(lambda p, t: forward(p, t, cfg))(
        params, _tokens()))
    assert hashlib.sha256(logits.tobytes()).hexdigest() == PARENT_DIGEST


PARENT_DIGEST = "f7bf2612c21c94304fa53efc70b2448d84ff1daaa3d8f791102e6e593c598931"


def test_layers_of_one_kind_are_traced_once():
    """The layers that share a spec share one checkpointed function, so
    jax traces the block once for all of them (a function made anew a
    layer is traced anew: a 12-layer serve program then took 3.6 s a
    shape to set up where it had taken 0.7, PERF.md §6 PR 28)."""
    from ray_tpu.models import LayerSpec
    from ray_tpu.models.transformer import _attention
    calls = []

    def counting(q, k, v, **kw):
        calls.append(kw)
        return _attention(q, k, v, **kw)

    cfg = _cfg(n_layers=4, remat=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    jax.make_jaxpr(lambda p, t: forward(p, t, cfg, attn_fn=counting))(
        params, _tokens())
    assert calls == [{}]
    del calls[:]
    mixed = _cfg(n_layers=4, remat=True, layers=(
        LayerSpec(window=8), LayerSpec(), LayerSpec(window=8), LayerSpec()))
    jax.make_jaxpr(lambda p, t: forward(p, t, mixed, attn_fn=counting))(
        params, _tokens())
    assert calls == [{"window": 8}, {}]


def test_graft_entry_hooks():
    import __graft_entry__ as ge
    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[0] == 2
    ge.dryrun_multichip(8)


def test_use_flash_matches_dense_forward():
    """cfg.use_flash routes attention through the Pallas kernel; logits
    match the dense path."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig, init_params
    from ray_tpu.models.transformer import forward

    # f32 compute isolates algorithmic equality from bf16
    # rounding-order differences (flash keeps P in f32 for the PV
    # accumulate; dense casts probs to bf16 first).
    base = dict(vocab_size=128, d_model=64, n_layers=2, n_heads=2,
                n_kv_heads=2, d_ff=128, max_seq_len=128,
                dtype=jnp.float32)
    cfg_d = TransformerConfig(**base)
    cfg_f = TransformerConfig(**base, use_flash=True)
    params = init_params(jax.random.PRNGKey(0), cfg_d)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 128)
    out_d = forward(params, tokens, cfg_d)
    out_f = forward(params, tokens, cfg_f)
    assert float(jnp.max(jnp.abs(out_d - out_f))) < 2e-2
