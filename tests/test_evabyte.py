"""EVA attention (exact keys inside a query's window, one learned
summary a chunk before it, one softmax over both) as kernels against a
dense masked softmax, and the `evabyte` family against the benchmark's
reference model, at tiny sizes on the CPU (the kernels in interpret
mode)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import evabyte as ref
from ray_tpu.models import (EvaSizes, LayerSpec, TransformerConfig,
                            config_from_hf, forward, forward_with_stats,
                            init_params, loss_fn, param_specs)
from ray_tpu.models.transformer import remat_plan
from ray_tpu.ops.eva_attention import (eva_attention, eva_reference, pairs,
                                       summaries_reference)
from ray_tpu.ops.flash_attention import mha_reference

W, C = 32, 4
TINY = {"model_type": "evabyte", "architecture": "evabyte",
        "attention_class": "eva", "hidden_size": 64,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "intermediate_size": 128, "vocab_size": 40, "num_pred_heads": 3,
        "num_hidden_layers": 2, "window_size": W, "chunk_size": C,
        "rope_theta": 100000, "rms_norm_eps": 1e-5,
        "norm_add_unit_offset": True, "fp32_skip_add": True,
        "fp32_logits": True, "torch_dtype": "float32"}


def operands(seq, heads=4, head_dim=16, seed=0, batch=2, spread=0.5):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(key, (batch, seq, heads, head_dim))
               for key in keys[:3])
    phi, mu = (jax.random.normal(key, (heads, head_dim)) * spread
               for key in keys[3:])
    return q, k, v, phi, mu


def dense(q, k, v, phi, mu, window, chunk, far=True):
    """A masked softmax over `[summaries ; bytes]` in float32, every
    query against every key."""
    s, h = q.shape[1], q.shape[-1]
    whole = s // chunk * chunk
    ks, vs = summaries_reference(k[:, :whole], v[:, :whole], phi, mu, chunk)
    keys = jnp.concatenate([ks, k], axis=1)
    values = jnp.concatenate([vs, v], axis=1)
    logits = jnp.einsum("bqnh,bknh->bnqk", q, keys,
                        precision="highest") / np.sqrt(h)
    t, j = jnp.arange(s), jnp.arange(whole // chunk)
    before = (j[None, :] * chunk // window < t[:, None] // window) & far
    own = (t[None, :] <= t[:, None]) & (
        t[None, :] // window == t[:, None] // window)
    seen = jnp.concatenate([before, own], axis=1)
    probs = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
    return jnp.einsum("bnqk,bknh->bqnh", probs, values, precision="highest")


# ---- the op ---------------------------------------------------------------

@pytest.mark.parametrize("seq", [32, 20, 128, 100])
@pytest.mark.parametrize("kernel", [False, True])
def test_eva_attention_is_the_dense_masked_softmax(seq, kernel):
    """One window, a part of one, several, and several with a part."""
    q, k, v, phi, mu = operands(seq)
    got = (eva_attention(q, k, v, phi, mu, W, C, True) if kernel
           else eva_reference(q, k, v, phi, mu, W, C))
    np.testing.assert_allclose(got, dense(q, k, v, phi, mu, W, C),
                               atol=2e-6)


@pytest.mark.parametrize("heads", [3, 4])
def test_the_kernel_serves_heads_in_runs_or_alone(heads):
    q, k, v, phi, mu = operands(96, heads=heads, seed=3)
    np.testing.assert_allclose(
        eva_attention(q, k, v, phi, mu, W, C, True),
        dense(q, k, v, phi, mu, W, C), atol=2e-6)


@pytest.mark.parametrize("seq", [32, 17])
def test_inside_one_window_it_is_causal_attention(seq):
    q, k, v, phi, mu = operands(seq, seed=1)
    want = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(eva_reference(q, k, v, phi, mu, W, C), want,
                               atol=2e-6)
    np.testing.assert_allclose(eva_attention(q, k, v, phi, mu, W, C, True),
                               want, atol=2e-6)


def test_the_summaries_matter_and_the_pooling_is_learned():
    """Past the first window the far part moves the result, and a plain
    mean is another summary than the learned one."""
    q, k, v, phi, mu = operands(128, seed=2)
    got = eva_reference(q, k, v, phi, mu, W, C)
    local = dense(q, k, v, phi, mu, W, C, far=False)
    np.testing.assert_allclose(got[:, :W], local[:, :W], atol=2e-6)
    assert float(jnp.max(jnp.abs(got[:, W:] - local[:, W:]))) > 0.05
    mean = eva_reference(q, k, v, jnp.zeros_like(phi), jnp.zeros_like(mu),
                         W, C)
    assert float(jnp.max(jnp.abs(got[:, W:] - mean[:, W:]))) > 0.05
    ks, vs = summaries_reference(k, v, jnp.zeros_like(phi),
                                 jnp.zeros_like(mu), C)
    np.testing.assert_allclose(ks[:, 3], k[:, 12:16].mean(axis=1), atol=1e-6)
    np.testing.assert_allclose(vs[:, 3], v[:, 12:16].mean(axis=1), atol=1e-6)


def test_pairs_from_shapes():
    assert pairs(32, W, C) == (32 * 33 // 2, 0)
    assert pairs(96, W, C) == (3 * 32 * 33 // 2, 32 * 8 * (0 + 1 + 2))
    assert pairs(40, W, C) == (32 * 33 // 2 + 8 * 9 // 2, 8 * 8)
    # the cell's lengths: 24 / 30 / 41 / 48 % of the pairs are summaries
    for seq, share in ((12288, 24), (16384, 30), (24576, 41), (32768, 48)):
        local, far = pairs(seq, 2048, 16)
        assert round(100 * far / (local + far)) == share


def test_eva_takes_heads_alike_and_has_no_backward():
    q, k, v, phi, mu = operands(64)
    with pytest.raises(ValueError, match="alike"):
        eva_attention(q, k[:, :, :2], v[:, :, :2], phi, mu, W, C, True)
    with pytest.raises(ValueError, match="whole chunks"):
        eva_reference(q, k, v, phi, mu, W, 5)
    with pytest.raises(NotImplementedError, match="eva_attention"):
        jax.grad(lambda q: eva_attention(q, k, v, phi, mu, W, C,
                                         True).sum())(q)


# ---- the family -----------------------------------------------------------

def program(config=TINY, seq=128, flash=False):
    return dataclasses.replace(config_from_hf(config, seq), use_flash=flash,
                               remat=False)


@pytest.mark.parametrize("flash", [False, True])
@pytest.mark.parametrize("seq", [32, 20, 128])
def test_forward_is_the_reference_model(flash, seq):
    """All three heads' logits at a whole window, a part of one and
    several, on seeded weights whose norm offsets, `phi` and `mu` are
    not idle."""
    sz = ref.Sizes.from_config(TINY)
    weights = ref.make_weights(ref.seed_key(2 ** 31 + 9), sz)
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 64))
    weights["final_norm"] = 0.3 * jax.random.normal(next(keys), (64,))
    for block in weights["blocks"]:
        for name in ("attn_norm", "mlp_norm"):
            block[name] = 0.3 * jax.random.normal(next(keys), (64,))
        for name in ("eva_phi", "eva_mu"):
            block[name] = 0.5 * jax.random.normal(next(keys), (4, 16))
    tokens = jax.random.randint(jax.random.PRNGKey(seq), (2, seq), 0, 40)
    last = jnp.array([seq - 1, seq // 2])
    want = ref.logits_at(weights, tokens, last, sz)
    got, stats = forward_with_stats(weights, tokens, program(flash=flash),
                                    logit_positions=last)
    assert got.shape == (2, 3 * 40) and got.dtype == jnp.float32
    assert stats["moe_rows"].shape == (0, 0)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(jnp.max(jnp.abs(want))) > 0.5
    everywhere = forward(weights, tokens, program(flash=flash))
    assert everywhere.shape == (2, seq, 3 * 40)
    np.testing.assert_allclose(everywhere[0, -1], want[0], atol=2e-5)


@pytest.mark.parametrize("flash", [False, True])
def test_bytes_after_the_prompt_do_not_move_its_last_logits(flash):
    """A prompt of 70 bytes padded to 96 and to 128 with other bytes:
    padding follows the prompt in whole windows, so no summary the last
    real position sees holds a padded byte."""
    cfg = program(flash=flash)
    params = init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 40, 70)
    last = jnp.array([69])

    def answer(padded, fill):
        row = np.full((1, padded), fill, np.int32)
        row[0, :70] = prompt
        return forward_with_stats(params, jnp.asarray(row), cfg,
                                  logit_positions=last)[0]

    want = answer(96, 0)
    np.testing.assert_allclose(answer(96, 7), want, atol=1e-6)
    np.testing.assert_allclose(answer(128, 7), want, atol=1e-6)


def test_the_planted_faults_are_not_no_ops():
    """`no_far` and `mean_pool` move the last position's logits past
    the first window and leave them alone inside it."""
    sz = ref.Sizes.from_config(TINY)
    weights = ref.make_weights(ref.seed_key(11), sz)
    for block in weights["blocks"]:     # a learned vector of some size
        block["eva_phi"] = block["eva_phi"] * 25
        block["eva_mu"] = block["eva_mu"] * 25
    logits = jax.jit(ref.logits_at, static_argnums=(3, 4))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 128), 0, 40)
    last = jnp.array([127])
    right = logits(weights, tokens, last, sz, "f32")
    for mode in ("no_far", "mean_pool", "int8"):
        gap = float(jnp.max(jnp.abs(logits(weights, tokens, last, sz, mode)
                                    - right)))
        assert gap > 0.01, (mode, gap)
    short, at = tokens[:, :32], jnp.array([31])
    for mode in ("no_far", "mean_pool"):
        np.testing.assert_array_equal(logits(weights, short, at, sz, mode),
                                      logits(weights, short, at, sz, "f32"))
    with pytest.raises(ValueError, match="no mode"):
        ref.logits_at(weights, short, at, sz, "bf16")


def test_config_from_hf_reads_the_family():
    cfg = config_from_hf(TINY, 128)
    assert cfg.layers == (LayerSpec(mixer="eva"),) * 2
    assert cfg.eva == EvaSizes(32, 4) and cfg.n_pred_heads == 3
    assert cfg.norm_unit_offset and cfg.residual_f32 and cfg.logits_f32
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.vocab_size,
            cfg.rope_theta, cfg.rms_norm_eps) == (4, 4, 16, 40, 1e5, 1e-5)
    with pytest.raises(ValueError, match="attention_class is 'eva', not "
                                         "'softmax'"):
        config_from_hf(dict(TINY, attention_class="softmax"), 128)
    with pytest.raises(ValueError, match="do not fit together"):
        EvaSizes(window=30, chunk=4)
    with pytest.raises(ValueError, match="no such layer"):
        TransformerConfig(n_layers=1, layers=(LayerSpec(mixer="eva",
                                                        window=8),))
    plain = TransformerConfig()
    assert (plain.n_pred_heads, plain.norm_unit_offset, plain.residual_f32,
            plain.logits_f32) == (1, False, False, False)


def test_parameters_are_the_references():
    cfg = config_from_hf(TINY, 128)
    params = init_params(jax.random.PRNGKey(1), cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        param_specs(cfg), is_leaf=lambda x: not isinstance(x, (dict, list)))
    sz = ref.Sizes.from_config(TINY)
    ours = {k: v.shape for k, v in params["blocks"][1].items()}
    theirs = {p[2]: shape for p, shape, _k in ref.leaf_table(sz)
              if p[0] == "blocks" and p[1] == 1}
    assert ours == theirs
    assert params["unembed"].shape == (64, 3 * 40)
    # a norm's scale of 1 is stored as 0; phi and mu are not idle
    assert not np.any(params["blocks"][0]["attn_norm"])
    assert not np.any(params["final_norm"])
    assert float(jnp.std(params["blocks"][0]["eva_phi"])) > 0.01


def test_a_gradient_through_the_layer_raises_by_name():
    cfg = dataclasses.replace(program(flash=True), remat=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.arange(64)[None] % 40}
    with pytest.raises(NotImplementedError, match="eva_attention"):
        jax.grad(lambda p: loss_fn(
            p, batch, dataclasses.replace(cfg, n_pred_heads=1)))(
            dict(params, unembed=params["unembed"][:, :40]))
    plan = remat_plan(dataclasses.replace(cfg, dtype=jnp.bfloat16), 1, 4096,
                      10 ** 6, 16 * 10 ** 9)
    assert plan.levels == (0, 0)        # nothing kept: it cannot train


def test_tracing_a_forward_records_the_eva_plan():
    from ray_tpu.util import tracing
    cfg = config_from_hf(TINY, 128)
    shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    before = len([s for s in tracing.spans() if s.name == "model.eva.plan"])
    for seq in (24, 96):
        jax.eval_shape(lambda p, t: forward(p, t, cfg), shapes,
                       jax.ShapeDtypeStruct((2, seq), jnp.int32))
    short, long_ = [s.counts for s in tracing.spans()
                    if s.name == "model.eva.plan"][before:]
    # two layers, four heads, two sequences; float32 here
    assert short == {
        "tokens": 48, "eva_layers": 2, "window": 32, "chunk": 4,
        "windows": 1, "summaries": 6, "local_pairs": 16 * 24 * 25 // 2,
        "far_pairs": 0, "state_bytes": 2 * (24 + 6) * 2 * 4 * 16 * 4}
    local, far = pairs(96, 32, 4)
    assert (long_["windows"], long_["summaries"], long_["local_pairs"],
            long_["far_pairs"]) == (3, 24, 16 * local, 16 * far)
    assert long_["state_bytes"] == 2 * (32 + 24) * 2 * 4 * 16 * 4
    # the other families record none (jax's own trace events may be
    # in the ring too, where a test before this one made it listen)
    def model_records():
        return [s for s in tracing.spans() if s.name.startswith("model.")]

    count = len(model_records())
    plain = TransformerConfig(n_layers=1, vocab_size=96, d_model=64)
    jax.eval_shape(lambda p, t: forward(p, t, plain),
                   jax.eval_shape(lambda k: init_params(k, plain),
                                  jax.random.PRNGKey(0)),
                   jax.ShapeDtypeStruct((1, 24), jnp.int32))
    assert len(model_records()) == count
