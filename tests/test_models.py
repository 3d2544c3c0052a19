"""Flagship transformer: sharded train step, ring-attention parity,
a routed layer in the pattern, and the driver entry hooks."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
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


def test_mistral_forward_is_bit_equal_to_the_parents(monkeypatch):
    """The default pattern is the dense block repeated: its logits on
    one seed are held to a digest. Up to PR 35 it was that of the
    forward before the layer pattern (``f7bf2612...``, commit 2e83d2e);
    since PR 36 RoPE's pair swap is a product with a permutation, which
    XLA contracts into other fused multiply-adds than the sliced form's:
    one float32 rounding apart (2.98e-6 on logits that reach 3.98), so
    the digest is this form's, and the sliced form's logits stand within
    1e-5 of it."""
    import hashlib

    from ray_tpu.models import transformer
    from tests.test_rope import _sliced_rope
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    logits = np.asarray(jax.jit(lambda p, t: forward(p, t, cfg))(
        params, _tokens()))
    assert hashlib.sha256(logits.tobytes()).hexdigest() == PARENT_DIGEST
    monkeypatch.setattr(transformer, "rope", _sliced_rope)
    sliced = np.asarray(jax.jit(lambda p, t: forward(p, t, cfg))(
        params, _tokens()))
    assert np.max(np.abs(logits - sliced)) < 1e-5


PARENT_DIGEST = "000e95153468a34062c30d606d4920740124986e4881934416e729d160a53f7b"


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


@pytest.mark.parametrize("n_kv_heads", [2, 1])
def test_use_flash_matches_dense_forward(n_kv_heads):
    """cfg.use_flash routes attention through the Pallas kernel, K and
    V at their KV heads; logits match the dense path, which repeats
    them inside itself."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig, init_params
    from ray_tpu.models.transformer import forward

    # f32 compute isolates algorithmic equality from bf16
    # rounding-order differences (flash keeps P in f32 for the PV
    # accumulate; dense casts probs to bf16 first).
    base = dict(vocab_size=128, d_model=64, n_layers=2, n_heads=2,
                n_kv_heads=n_kv_heads, d_ff=128, max_seq_len=128,
                dtype=jnp.float32)
    cfg_d = TransformerConfig(**base)
    cfg_f = TransformerConfig(**base, use_flash=True)
    params = init_params(jax.random.PRNGKey(0), cfg_d)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 128)
    out_d = forward(params, tokens, cfg_d)
    out_f = forward(params, tokens, cfg_f)
    assert float(jnp.max(jnp.abs(out_d - out_f))) < 2e-2


# ---------------------------------------------------------------------------
# What ``remat=True`` keeps (``remat_plan``)
# ---------------------------------------------------------------------------

def _mistral(n_layers, **kw):
    """The benchmark's train configuration: published Mistral-7B widths."""
    return TransformerConfig(
        vocab_size=32_000, d_model=4096, n_layers=n_layers, n_heads=32,
        n_kv_heads=8, d_ff=14_336, max_seq_len=4096, dtype=jnp.bfloat16,
        remat=True, use_flash=True, **kw)


def _limit_with_room(room):
    """The smallest limit whose room (limit less the margin) is `room`."""
    from ray_tpu.models.transformer import REMAT_MARGIN
    limit = int(room / (1 - REMAT_MARGIN))
    while int(limit * (1 - REMAT_MARGIN)) < room:
        limit += 1
    return limit


@pytest.mark.parametrize("case", [
    "train_cell", "share_2x2", "no_limit", "dense_attention", "full_chip"])
def test_remat_plan_table(case):
    from ray_tpu.models.transformer import KEEP_LAYER, remat_plan
    chip = 16e9                                 # benchmark/costs.CHIP_PEAKS
    if case == "train_cell":
        # 3 layers, 1 x 4,096 tokens, 11.0 GB of f32 Adam state held
        plan = remat_plan(_mistral(3), 1, 4096, 10_997_827_072, chip)
        assert plan.levels == (KEEP_LAYER,) * 3
        assert plan.recompute_flops == 0
        assert plan.kept_bytes <= plan.budget_bytes
        assert 13.8e9 < plan.peak_bytes < 14.1e9        # AOT: 14.090
    elif case == "share_2x2":
        # 12 layers over fsdp=2 x tp=2: 8.64 GB a chip, 2 x 4,096 tokens
        args = (_mistral(12), 2, 4096, 8_639_415_808)
        shards = {"dp": 1, "fsdp": 2, "tp": 2, "sp": 1}
        plan = remat_plan(*args, chip, shards)
        whole = remat_plan(*args, None, shards, (KEEP_LAYER,) * 12)
        assert 0 < plan.kept_bytes < whole.kept_bytes
        assert plan.kept_bytes <= plan.budget_bytes
        assert plan.peak_bytes <= chip
        assert 0 < plan.recompute_flops < 12 * plan.layer_forward_flops
        assert len(set(plan.levels)) <= 2       # two functions a kind
    elif case == "no_limit":
        plan = remat_plan(_mistral(3), 1, 4096, 10_997_827_072, None)
        assert plan.levels == (0, 0, 0) and plan.budget_bytes == 0
        assert 12.9e9 < plan.peak_bytes < 13.2e9        # AOT: 13.010
    elif case == "dense_attention":
        cfg = dataclasses.replace(_mistral(3), use_flash=False)
        assert remat_plan(cfg, 1, 4096, 0, chip).levels == (0, 0, 0)
    else:
        # a chip the state fills gets exactly today's program
        plan = remat_plan(_mistral(3), 1, 4096, 13_500_000_000, chip)
        assert plan.levels == (0, 0, 0)


@pytest.mark.parametrize("levels", [
    (2, 1, 1), (2, 2, 1), (2, 2, 2), (3, 2, 2), (3, 3, 2), (3, 3, 3)])
def test_remat_plan_falls_a_level_one_byte_short(levels):
    """A limit with room for exactly this plan's counted peak gives it;
    one byte less gives the plan one raise below. (Up to (1, 1, 1) the
    peak is the backward pass's and a raise does not move it.)"""
    from ray_tpu.models.transformer import remat_plan
    args = (_mistral(3), 1, 4096, 10_997_827_072)
    peak = remat_plan(*args, None, None, levels).peak_bytes
    assert remat_plan(*args, _limit_with_room(peak)).levels == levels
    below = remat_plan(*args, _limit_with_room(peak - 1)).levels
    raised = [i for i in range(3) if below[i] != levels[i]]
    assert len(raised) == 1 and below[raised[0]] == levels[raised[0]] - 1


def _kernel_results(jaxpr):
    """The number of results of every ``pallas_call`` in the program.
    The forward and dk/dv kernels give two, the dq kernel one."""
    from jax._src import core
    found = [len(e.outvars) for e in jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    for sub in core.subjaxprs(jaxpr):
        found += _kernel_results(sub)
    return found


def _flash_cfg(**kw):
    return _cfg(n_layers=3, use_flash=True, **kw)


@pytest.mark.parametrize("levels", [
    (0, 0, 0), (1, 1, 1), (2, 2, 2), (3, 3, 3), (1, 2, 2), (2, 3, 3)])
def test_remat_levels_give_the_gradient_of_no_remat(levels):
    """Kept and recomputed values are the same numbers: loss and
    gradients in float32 equal those of ``remat=False`` to the last
    bit, whatever each layer keeps."""
    params = init_params(jax.random.PRNGKey(0), _flash_cfg())
    batch = {"tokens": _tokens(b=2)}
    want = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, batch, _flash_cfg(remat=False))))(params)
    got = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, batch, _flash_cfg(remat=True),
                          remat_levels=levels)))(params)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("levels,forwards", [
    ((0, 0, 0), 6), ((1, 1, 1), 3), ((2, 2, 2), 3), ((3, 3, 3), 3),
    ((0, 0, 1), 5), ((0, 1, 1), 4)])
def test_kept_kernel_results_spare_the_second_forward(levels, forwards):
    """The gradient's program calls the attention forward once a layer
    where the kernel's results are kept and twice where not, and the
    layers of one kind and level share one traced function."""
    from ray_tpu.ops.flash_attention import flash_attention
    calls = []

    def counting(q, k, v, **kw):
        calls.append(kw)
        return flash_attention(q, k, v, True, None, None, None, True, **kw)

    cfg = _flash_cfg(remat=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    program = jax.make_jaxpr(jax.grad(
        lambda p: loss_fn(p, {"tokens": _tokens(b=2)}, cfg, counting,
                          levels)))(params)
    assert len(calls) == len(set(levels))
    # the forward kernel alone gives two results (out, lse), dq one
    results = _kernel_results(program.jaxpr)
    assert results.count(2) - 3 == forwards and results.count(1) == 3


# sha256 of the lowered dense train step, function-name counters left
# out: the program of commit 00046a2 (the parent of the remat plan) with
# RoPE's pair swap as PR 36 has it (a product with a constant signed
# permutation where two strided slices and a stack were)
PARENT_DENSE_STEP_DIGEST = \
    "ac0987b269b6bccccc2828bec98e71061111e9a59ba27dc032fd838670454d36"
# the same of the flash train step as PR 34 left it (the forward kernel
# that serves a KV group a step, interpreted on the CPU), RoPE as above
FLASH_STEP_DIGEST = \
    "9763b0abf43fb21e3e4ebdcccb9317383fcd895adae57cc6a91b802057f34ea4"


@pytest.mark.parametrize("use_flash", [False, True])
def test_train_step_without_a_memory_figure_is_the_parents(use_flash):
    """The CPU reports no memory limit, so ``remat=True`` recomputes
    every layer as before: the dense step lowers to the parent's
    program (K and V repeated inside ``_attention`` now, the same
    operations), and the flash step, whose forward kernel is PR 34's,
    to the program that is told to recompute every layer, held to its
    own digest from here on."""
    import hashlib
    import re
    cfg = _cfg(remat=True, use_flash=use_flash)
    tx = make_optimizer(lr=1e-2, total_steps=50)
    state = jax.eval_shape(lambda k: init_state(k, cfg, tx),
                           jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((4, 64), jnp.int32)

    def lowered(**kw):
        text = make_train_step(cfg, tx, **kw).lower(
            state, {"tokens": tokens}).as_text()
        return re.sub(r"(@[A-Za-z_]\w*?)_\d+\b", r"\1", text)

    if use_flash:
        assert hashlib.sha256(lowered().encode()).hexdigest() == \
            FLASH_STEP_DIGEST
        assert lowered() == lowered(remat_levels=(0,) * cfg.n_layers)
        assert lowered() != lowered(remat_levels=(1,) * cfg.n_layers)
    else:
        assert hashlib.sha256(lowered().encode()).hexdigest() == \
            PARENT_DENSE_STEP_DIGEST


def test_train_step_records_its_remat_plan():
    """One ``train.remat_plan`` record when the step is traced, under a
    mesh too (the state's bytes through its shardings)."""
    from ray_tpu.util import tracing
    cfg = _cfg(remat=True, use_flash=True)
    mesh = make_mesh(MeshSpec(fsdp=2, tp=2), jax.devices()[:4])
    tx = make_optimizer(lr=1e-2, total_steps=50)
    tracing.clear()
    with mesh:
        state = init_state(jax.random.PRNGKey(0), cfg, tx, mesh)
        step = make_train_step(cfg, tx, mesh)
        tokens = jax.device_put(_tokens(), NamedSharding(
            mesh, P(("dp", "fsdp"), "sp")))
        state, metrics = step(state, {"tokens": tokens})
        state, metrics = step(state, {"tokens": tokens})
    assert np.isfinite(float(metrics["loss"]))
    records = [s.counts for s in tracing.spans()
               if s.name == "train.remat_plan"]
    assert len(records) == 1
    assert records[0]["layers"] == 2 and records[0]["layers_kept_whole"] == 0
    assert records[0]["recompute_flops"] > 0
    assert records[0]["budget_bytes"] == 0
