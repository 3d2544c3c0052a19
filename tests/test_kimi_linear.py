"""The `kimi_linear` family against the plain reference
(`benchmark/reference/kimi_linear.py`), on a tiny file with the
published key names: the delta-rule kernel against the recurrence, the
flash forward with keys wider than values, the model on the kernel path
and on the plain one against the reference, what `config_from_hf`
refuses, and the eight shares of a routed layer."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import kimi_linear as ref
from ray_tpu.models import (KdaSizes, LayerSpec, MlaSizes, config_from_hf,
                            forward_with_stats, init_params, param_specs)
from ray_tpu.models.transformer import _attention
from ray_tpu.ops import moe
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.ops.kda_attention import CHUNK, kda_attention, kda_reference

# the published key names; heads of the published sizes (a kda head's 128
# lanes are the kernel's tile), everything else tiny: a dense kda layer,
# two routed kda layers, a routed mla layer, a routed kda layer; 16
# experts of which this share holds 4 (not the first four), top-8
TINY = {
    "model_type": "kimi_linear", "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 5,
    "num_attention_heads": 2, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 96, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "torch_dtype": "float32", "first_k_dense_replace": 1,
    "kv_lora_rank": 32, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "q_lora_rank": None, "mla_use_nope": True,
    "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
    "num_expert_group": 1, "topk_group": 1, "num_experts": 4,
    "expert_parallel": {"size": 4, "rank": 1}, "num_experts_per_token": 8,
    "moe_intermediate_size": 32, "num_shared_experts": 1,
    "routed_scaling_factor": 2.446,
    "linear_attn_config": {
        "full_attn_layers": [4], "kda_layers": [1, 2, 3, 5],
        "head_dim": 128, "num_heads": 2, "short_conv_kernel_size": 4}}
# not a whole number of chunks, and more than two
SEQ = 2 * CHUNK + 22
# float32 program against the float32 reference: round-off of a few
# thousand float32 operations on logits of order 1
TOLERANCE = 2e-4


def _operands(seed, decay, step, s=SEQ, n=2, h=128):
    """q and k of unit length (q scaled), per-step log-decays from
    `-decay` down to a thousandth of it, steps near 0, near 1 or
    between."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(key, (1, s, n, h)) for key in ks[:3])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(h)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -decay * jnp.exp(jax.random.uniform(
        ks[3], (1, s, n, h), minval=np.log(1e-3), maxval=0.0))
    u = jax.random.uniform(ks[4], (1, s, n))
    beta = {"near 0": 0.02 * u, "near 1": 1.0 - 0.02 * u, "between": u}[step]
    return q, k, v, g, beta


@pytest.mark.parametrize("step", ["near 0", "near 1", "between"])
@pytest.mark.parametrize("decay", [0.01, 1.6, 8.0, 200.0])
def test_the_kernel_is_the_recurrence(decay, step):
    """Interpret mode against the token-by-token scan, float32: weak
    decays (a state that carries across the whole sequence), the
    strongest the assumed initialisation draws at a zero projection
    (-1.6 a step), and far beyond (exp(-200): a factorisation that
    formed exp(+G) would overflow inside one sub-block). Finite, and
    within float32 round-off of the recurrence."""
    ops = _operands(1, decay, step)
    want = kda_reference(*ops)
    got = kda_attention(*ops, True)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_the_kernel_rounds_like_its_operands():
    """bfloat16 q, k, v: the products round to bfloat16, the decays,
    the solve and the state stay float32; within 2 % of the output's
    largest value."""
    q, k, v, g, beta = _operands(2, 1.6, "between")
    want = kda_reference(q, k, v, g, beta)
    got = kda_attention(*(x.astype(jnp.bfloat16) for x in (q, k, v)), g,
                        beta, True)
    assert got.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) < \
        0.02 * float(jnp.max(jnp.abs(want)))


def test_the_kernel_has_no_backward_and_says_so():
    ops = _operands(3, 1.0, "between", s=CHUNK)
    with pytest.raises(NotImplementedError, match="kda_attention has no "
                                                  "backward"):
        jax.grad(lambda q: jnp.sum(kda_attention(q, *ops[1:], True)))(ops[0])
    with pytest.raises(ValueError, match="q, k, v and g alike"):
        kda_attention(ops[0], ops[1][:, :8], *ops[2:], True)


@pytest.mark.parametrize("seq,kv_heads", [(300, 4), (130, 2)])
def test_flash_takes_keys_wider_than_values(seq, kv_heads):
    """q and k 192 wide, v 128: the forward against plain attention (the
    scale from q's width), at equal heads and grouped; the backward
    keeps its equal-shape contract and says so by name."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, seq, 4, 192))
    k = jax.random.normal(ks[1], (1, seq, kv_heads, 192))
    v = jax.random.normal(ks[2], (1, seq, kv_heads, 128))
    got = flash_attention(q, k, v, True, None, None, None, True)
    assert got.shape == (1, seq, 4, 128)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_attention(q, k, v)), atol=2e-5)
    with pytest.raises(NotImplementedError, match="k and v of one shape"):
        jax.grad(lambda q: jnp.sum(flash_attention(
            q, k, v, True, None, None, None, True)))(q)
    with pytest.raises(ValueError, match="q and k in theirs"):
        flash_attention(q[..., :128], k, v, True, None, None, None, True)


def test_flash_backward_at_a_width_that_is_no_lane_multiple():
    """k and v both 192 wide: the forward leaves the width as it is, the
    backward pads it to lanes as it always has, and the gradients are
    plain attention's."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 130, 4, 192))
    k, v = (jax.random.normal(key, (1, 130, 2, 192)) for key in ks[1:])

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

    got = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, True, None, None, None, True)), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(_attention), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5)


def test_config_from_hf_reads_the_pattern():
    cfg = config_from_hf(TINY, SEQ)
    kda, mla = (LayerSpec(rope=False, mixer=m, experts=True)
                for m in ("kda", "mla"))
    assert cfg.layers == (dataclasses.replace(kda, experts=False), kda, kda,
                          mla, kda)
    assert cfg.head_dim == 128 and cfg.rms_norm_eps == 1e-5
    assert cfg.kda == KdaSizes(conv=4, rank=128)
    assert cfg.mla == MlaSizes(kv_rank=32, nope=128, shared=64, value=128)
    assert cfg.n_experts == 16 and cfg.experts_held == (4, 4)
    assert cfg.expert_top_k == 8 and cfg.route_scale == 2.446
    assert cfg.d_ff_expert == 32 and cfg.n_shared_experts == 1


@pytest.mark.parametrize("key,value", [
    ("num_expert_group", 2), ("topk_group", 2), ("q_lora_rank", 1536),
    ("mla_use_nope", False), ("moe_router_activation_func", "softmax"),
    ("moe_renormalize", False)])
def test_config_from_hf_refuses_what_is_not_written(key, value):
    with pytest.raises(ValueError, match=f"kimi_linear model whose {key}"):
        config_from_hf(dict(TINY, **{key: value}), SEQ)


def test_param_specs_match_init_params():
    cfg = config_from_hf(TINY, SEQ)
    params = init_params(jax.random.PRNGKey(0), cfg)
    specs = param_specs(cfg)
    assert [sorted(b) for b in params["blocks"]] == \
        [sorted(b) for b in specs["blocks"]]
    for block, spec in zip(params["blocks"], specs["blocks"]):
        for name, leaf in block.items():
            assert len(spec[name]) == leaf.ndim, name
    sz = ref.Sizes.from_config(TINY)        # and the reference's tree
    weights = ref.make_weights(ref.seed_key(1), sz)
    assert jax.tree.map(jnp.shape, weights) == jax.tree.map(jnp.shape, params)
    kda = params["blocks"][0]               # the decays as the checkpoint's
    rate = np.exp(np.asarray(kda["a_log"]))
    step = np.asarray(jax.nn.softplus(kda["dt_bias"]))
    assert 1.0 <= rate.min() and rate.max() <= 16.0
    assert 1e-3 <= step.min() * 1.001 and step.max() <= 0.1 * 1.001


def _setup(seed):
    sz = ref.Sizes.from_config(TINY)
    weights = ref.make_weights(ref.seed_key(seed), sz)
    tokens = np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], (1, SEQ)).astype(np.int32)
    return sz, weights, tokens


@pytest.mark.parametrize("seed", [3, 2_400_000_001])
def test_the_model_is_the_reference(seed):
    """The kernel path against the plain path, and both against the
    reference's recurrence and masked softmax, at the last position and
    at one inside the first chunk."""
    sz, weights, tokens = _setup(seed)
    cfg = dataclasses.replace(config_from_hf(TINY, SEQ), remat=False)
    for last in (SEQ - 1, 7):
        at = jnp.array([last], jnp.int32)
        want = ref.logits_at(weights, tokens, at, sz)
        plain, stats = forward_with_stats(weights, tokens, cfg,
                                          logit_positions=at)
        kernel, _ = forward_with_stats(
            weights, tokens, dataclasses.replace(cfg, use_flash=True),
            logit_positions=at)
        assert stats["moe_rows"].shape == (4, 4)
        np.testing.assert_allclose(np.asarray(kernel), np.asarray(plain),
                                   atol=TOLERANCE)
        np.testing.assert_allclose(np.asarray(plain), np.asarray(want),
                                   atol=TOLERANCE)


def test_the_control_is_told_from_the_reference():
    """`int8` moves the logits by far more than the program's
    round-off; an unknown mode raises."""
    sz, weights, tokens = _setup(5)
    at = jnp.array([SEQ - 1], jnp.int32)
    want = ref.logits_at(weights, tokens, at, sz)
    moved = float(jnp.max(jnp.abs(
        ref.logits_at(weights, tokens, at, sz, "int8") - want)))
    assert moved > 50 * TOLERANCE, moved
    with pytest.raises(ValueError, match="no mode"):
        ref.logits_at(weights, tokens, at, sz, "fp4")


def test_the_eight_shares_add_up():
    """On a routed kda layer at top-8 of 16: the routed parts that all
    eight shares give, plus the shared expert once, equal the uncut
    reference layer's MLP, the program's routed layer gives each
    share's part, and no pair is dropped or counted twice (beside
    `tests/test_afmoe.py::test_the_shares_add_up`)."""
    whole_config = dict(TINY, num_experts=16,
                        expert_parallel={"size": 1, "rank": 0})
    whole = ref.Sizes.from_config(whole_config)
    assert whole == ref.uncut(ref.Sizes.from_config(TINY))
    weights = ref.make_weights(ref.seed_key(3), whole)
    p = weights["blocks"][1]
    p["router_bias"] = p["router_bias"] * (0.3 / ref.ROUTER_BIAS_STD)
    rows_in = 48
    m = jax.random.normal(jax.random.PRNGKey(5), (rows_in, 64), jnp.float32)
    shared = ref._swiglu(m, p["shared_wg"], p["shared_wi"], p["shared_wo"],
                         "f32")
    uncut = ref.routed_part(p, m, whole, "f32") + shared
    total, every_row = shared, 0
    for rank in range(8):
        share_w, share_sz = ref.share_of(weights, whole, 2 * rank, 2)
        sp = share_w["blocks"][1]
        part = ref.routed_part(sp, m, share_sz, "f32")
        mine, rows = moe.routed_experts(
            m, sp["router"], sp["router_bias"], sp["experts_wg"],
            sp["experts_wi"], sp["experts_wo"], held=(2 * rank, 2),
            top_k=8, route_scale=2.446)
        np.testing.assert_allclose(np.asarray(mine), np.asarray(part),
                                   atol=2e-5)
        total = total + part
        every_row += int(np.sum(rows))
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=4e-5)
    assert every_row == rows_in * 8
    # and the layer whole: the uncut share of the model is the uncut layer
    x = jax.random.normal(jax.random.PRNGKey(6), (1, rows_in, 64))
    cfg = dataclasses.replace(config_from_hf(whole_config, rows_in),
                              remat=False)
    from ray_tpu.models.transformer import _layer_forward
    got = _layer_forward(p, x, None, cfg.layers[1], cfg, _attention)[0]
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.layer_out(weights, x, 1, whole)),
        atol=TOLERANCE)
