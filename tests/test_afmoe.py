"""The layer pattern and the routed layer against the plain reference
(`benchmark/reference/afmoe.py`), on a tiny afmoe preset with two
heads' worth of every mechanism: a dense layer, a sliding and a full
expert layer, a window shorter than the sequence, 8 experts of which
this share holds 2 (not the first two), a bias that changes the
selection."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import afmoe as ref
from ray_tpu.models import (LayerSpec, TransformerConfig, config_from_hf,
                            forward, forward_with_stats, init_params,
                            param_specs)
from ray_tpu.ops import moe

TINY = {
    "model_type": "afmoe", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 128,
    "moe_intermediate_size": 64, "num_hidden_layers": 3,
    "num_dense_layers": 1,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "full_attention"],
    "sliding_window": 8, "num_experts": 2,
    "expert_parallel": {"size": 4, "rank": 1}, "num_experts_per_tok": 2,
    "num_shared_experts": 1, "route_scale": 2.448, "mup_enabled": True,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "vocab_size": 96,
    "torch_dtype": "float32"}
SEQ = 24
# float32 program against the float32 reference: round-off of a few
# hundred float32 operations on logits of order 1
TOLERANCE = 2e-4


def _setup(config=TINY, seed=3, bias_std=0.3):
    sz = ref.Sizes.from_config(config)
    weights = ref.make_weights(ref.seed_key(seed), sz)
    for block in weights["blocks"]:     # a bias large enough to matter
        if "router_bias" in block:
            block["router_bias"] = block["router_bias"] * (
                bias_std / ref.ROUTER_BIAS_STD)
    tokens = np.random.default_rng(seed).integers(
        0, config["vocab_size"], (2, SEQ)).astype(np.int32)
    return sz, weights, tokens


def test_config_from_hf_reads_the_pattern():
    cfg = config_from_hf(TINY, SEQ)
    assert cfg.head_dim == 32 and cfg.rms_norm_eps == 1e-5
    assert cfg.layers == (
        LayerSpec(window=8, rope=True, experts=False),
        LayerSpec(window=8, rope=True, experts=True),
        LayerSpec(window=None, rope=False, experts=True))
    assert cfg.n_experts == 8 and cfg.experts_held == (2, 2)
    assert cfg.expert_top_k == 2 and cfg.n_shared_experts == 1
    assert cfg.embed_scale == 8.0 and cfg.qk_norm and cfg.attn_gate
    # a window that no sequence outgrows is causal attention
    assert config_from_hf(TINY, 8).layers[0].window is None
    with pytest.raises(ValueError, match="model types"):
        config_from_hf(dict(TINY, model_type="other"), SEQ)


def test_config_from_hf_reads_the_benchmarks_files():
    here = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                        "configs")
    with open(os.path.join(here, "mistral-7b-v0.1-l3.json")) as f:
        mistral = config_from_hf(json.load(f), 4096)
    # the nine keys benchmark/drivers/train_loop.py::program_config maps
    assert mistral == TransformerConfig(
        vocab_size=32000, d_model=4096, n_layers=3, n_heads=32,
        n_kv_heads=8, d_ff=14336, max_seq_len=4096, rope_theta=10000.0,
        dtype=jnp.dtype("bfloat16"))
    assert mistral.head_dim == 128 and mistral.rms_norm_eps == 1e-6
    with open(os.path.join(here, "trinity-large-preview-l5-ep8.json")) as f:
        trinity = config_from_hf(json.load(f), 16384)
    assert [s.window for s in trinity.layers] == [4096] * 4 + [None]
    assert [s.rope for s in trinity.layers] == [True] * 4 + [False]
    assert [s.experts for s in trinity.layers] == [False] + [True] * 4
    assert (trinity.n_experts, trinity.experts_held) == (256, (0, 32))
    assert (trinity.d_model, trinity.n_heads, trinity.head_dim,
            trinity.n_kv_heads, trinity.d_ff, trinity.d_ff_expert) == (
        3072, 48, 128, 8, 12288, 3072)


def test_init_params_and_specs_follow_the_pattern():
    cfg = config_from_hf(TINY, SEQ)
    params = init_params(jax.random.PRNGKey(0), cfg)
    specs = param_specs(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    sz, weights, _tokens = _setup()
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(
        jnp.shape, weights)
    assert "wg" in params["blocks"][0] and "router" in params["blocks"][1]


@pytest.mark.parametrize("use_flash", [False, True])
def test_forward_matches_the_reference(use_flash):
    sz, weights, tokens = _setup()
    cfg = dataclasses.replace(config_from_hf(TINY, SEQ),
                              use_flash=use_flash, remat=False)
    logits, stats = jax.jit(
        lambda w, t: forward_with_stats(w, t, cfg))(weights, tokens)
    last = np.full((2,), SEQ - 1, np.int32)
    want = np.asarray(jnp.einsum(
        "bsd,dv->bsv", ref.hidden(weights, tokens, sz), weights["unembed"],
        precision="highest"))
    np.testing.assert_allclose(
        want[:, -1], np.asarray(ref.logits_at(weights, tokens, last, sz)),
        atol=1e-5)
    assert float(np.max(np.abs(np.asarray(logits) - want))) < TOLERANCE
    # the last position alone, as a prefill asks for it
    only_last, _ = forward_with_stats(weights, tokens, cfg,
                                      logit_positions=jnp.asarray(last))
    np.testing.assert_allclose(np.asarray(only_last),
                               np.asarray(logits)[:, -1], atol=1e-5)
    # two routed layers, two held experts; some rows are routed here
    rows = np.asarray(stats["moe_rows"])
    assert rows.shape == (2, 2) and 0 < rows.sum() < 2 * 2 * SEQ * 2
    # the control: the same mathematics in int8 is outside the tolerance
    control = np.asarray(ref.logits_at(weights, tokens, last, sz, "int8"))
    assert float(np.max(np.abs(control - want[:, -1]))) > 10 * TOLERANCE


def test_every_mechanism_matters():
    """Dropping any one of the pattern's mechanisms moves the logits
    far outside the tolerance: the comparison sees each."""
    sz, weights, tokens = _setup()
    cfg = dataclasses.replace(config_from_hf(TINY, SEQ), remat=False)
    base = np.asarray(forward(weights, tokens, cfg))
    no_window = tuple(dataclasses.replace(s, window=None)
                      for s in cfg.layers)
    all_rope = tuple(dataclasses.replace(s, rope=True) for s in cfg.layers)
    no_bias = dict(weights, blocks=[
        {k: jnp.zeros_like(v) if k == "router_bias" else v
         for k, v in b.items()} for b in weights["blocks"]])
    variants = {
        "window": (weights, dataclasses.replace(cfg, layers=no_window)),
        "nope": (weights, dataclasses.replace(cfg, layers=all_rope)),
        "gate": (weights, dataclasses.replace(cfg, attn_gate=False)),
        "qk_norm": (weights, dataclasses.replace(cfg, qk_norm=False)),
        "sandwich": (weights, dataclasses.replace(cfg, sandwich_norm=False)),
        "embed_scale": (weights, dataclasses.replace(cfg, embed_scale=1.0)),
        "route_scale": (weights, dataclasses.replace(cfg, route_scale=1.0)),
        "other_share": (weights, dataclasses.replace(
            cfg, experts_held=(0, 2))),
        "bias": (no_bias, cfg),
    }
    for name, (w, c) in variants.items():
        moved = float(np.max(np.abs(np.asarray(forward(w, tokens, c))
                                    - base)))
        assert moved > 50 * TOLERANCE, (name, moved)


@pytest.mark.parametrize("sizes", [
    [10, 0, 20, 5],         # an empty group, a tail that is no group's
    [64, 0, 0, 0],          # one group holds every row
    [0, 0, 0, 0],           # nothing held: every tile skipped
    [3, 3, 3, 3], [16, 16, 16, 16], [1, 40, 2, 21]])
@pytest.mark.parametrize("tile_m", [8, 16, 64])
def test_gmm_matches_an_einsum_over_groups(sizes, tile_m):
    key = jax.random.PRNGKey(0)
    lhs = jax.random.normal(key, (64, 32))
    rhs = jax.random.normal(jax.random.fold_in(key, 1), (4, 32, 256))
    ends = jnp.cumsum(jnp.array(sizes))
    starts = ends - jnp.array(sizes)
    got = moe.gmm(lhs, rhs, starts, ends, tile_m, 128, True)
    want = moe.gmm_reference(lhs, rhs, starts, ends)
    held = int(ends[-1])    # rows past it are no group's: left unwritten
    np.testing.assert_allclose(np.asarray(got)[:held],
                               np.asarray(want)[:held], rtol=1e-5, atol=1e-5)
    # by hand: row r of group g is lhs[r] @ rhs[g]
    for g, (lo, hi) in enumerate(zip(np.asarray(starts), np.asarray(ends))):
        if hi > lo:
            np.testing.assert_allclose(
                np.asarray(got)[lo], np.asarray(lhs)[lo] @ np.asarray(rhs)[g],
                rtol=1e-4, atol=1e-4)


def test_gmm_refuses_rows_that_are_not_whole_tiles():
    lhs, rhs = jnp.zeros((60, 32)), jnp.zeros((2, 32, 256))
    rows = jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="whole"):
        moe.gmm(lhs, rhs, rows, rows, 16, 128, True)
    # the column tile left to the kernel: the widest that divides
    assert moe.gmm(jnp.zeros((64, 32)), jnp.zeros((2, 32, 384)), rows, rows,
                   16, None, True).shape == (64, 384)


def test_the_shares_add_up():
    """The routed parts that all four shares give, plus the shared
    expert once, equal the uncut reference layer's MLP; and the program's
    routed layer gives each share's part."""
    whole_config = dict(TINY, num_experts=8,
                        expert_parallel={"size": 1, "rank": 0})
    whole, weights, _tokens = _setup(whole_config)
    assert whole == ref.uncut(ref.Sizes.from_config(TINY))
    p = weights["blocks"][1]
    m = jax.random.normal(jax.random.PRNGKey(5), (2 * SEQ, 64), jnp.float32)
    shared = ref._swiglu(m, p["shared_wg"], p["shared_wi"], p["shared_wo"],
                         "f32")
    uncut = ref.routed_part(p, m, whole, "f32") + shared
    total = shared
    every_row = 0
    for rank in range(4):
        share_w, share_sz = ref.share_of(weights, whole, 2 * rank, 2)
        sp = share_w["blocks"][1]
        part = ref.routed_part(sp, m, share_sz, "f32")
        mine, rows = moe.routed_experts(
            m, sp["router"], sp["router_bias"], sp["experts_wg"],
            sp["experts_wi"], sp["experts_wo"], held=(2 * rank, 2),
            top_k=2, route_scale=2.448)
        np.testing.assert_allclose(np.asarray(mine), np.asarray(part),
                                   atol=2e-5)
        total = total + part
        every_row += int(np.sum(rows))
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=2e-5)
    assert every_row == 2 * SEQ * 2     # no pair dropped, none counted twice


def test_padding_rows_leave_real_rows_unchanged():
    """No token is dropped, so the rows that pad a prompt to its
    program's length are routed like any other and change no real
    token's result (attention is causal: they come after)."""
    sz, weights, tokens = _setup()
    cfg = dataclasses.replace(config_from_hf(TINY, 2 * SEQ), remat=False)
    short = np.asarray(forward(weights, tokens, cfg))
    padded = np.concatenate(
        [tokens, np.full((2, SEQ), 7, np.int32)], axis=1)
    long = np.asarray(forward(weights, padded, cfg))[:, :SEQ]
    np.testing.assert_allclose(long, short, atol=2e-5)


def test_training_through_experts_says_what_is_missing():
    _sz, weights, tokens = _setup()
    cfg = config_from_hf(TINY, SEQ)
    with pytest.raises(NotImplementedError, match="gmm has no backward"):
        jax.grad(lambda w: jnp.sum(forward(w, tokens, cfg)))(weights)


def test_bf16_program_stays_near_the_reference():
    """The precision the cell runs: bfloat16-resident weights and
    compute. The logits stay within a few hundredths of the reference's
    on the same (bfloat16-rounded) weights; int8 does not."""
    config = dict(TINY, torch_dtype="bfloat16")
    sz, weights, tokens = _setup(config, bias_std=ref.ROUTER_BIAS_STD)
    cfg = dataclasses.replace(config_from_hf(config, SEQ), remat=False)
    last = np.full((2,), SEQ - 1, np.int32)
    got = np.asarray(forward_with_stats(
        weights, tokens, cfg, logit_positions=jnp.asarray(last))[0])
    want = np.asarray(ref.logits_at(weights, tokens, last, sz))
    assert got.dtype == np.float32
    assert float(np.max(np.abs(got - want))) < 0.1
