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
from jax.sharding import PartitionSpec as P

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


# tokens x top_k = 96 pairs in row tiles of 8, each share 2 of 8 experts:
# the ladder is (48, 96). `boost` is added to the bias of share 1's two
# experts: 1.5 gives it more than the lower rung's rows, 20 every pair.
@pytest.mark.parametrize("boost,tile_m,rungs", [
    (0.0, None, [96] * 4),      # one row tile: one rung, as before PR 31
    (0.0, 8, [48, 48, 96, 48]),    # the reference bias skews share 2
    (1.5, 8, [48, 96, 48, 48]),
    (20.0, 8, [48, 96, 48, 48])])
def test_the_shares_add_up(boost, tile_m, rungs):
    """The routed parts that all four shares give, plus the shared
    expert once, equal the uncut reference layer's MLP; and the program's
    routed layer gives each share's part, at whatever rung of its ladder
    the router's skew puts a share: no rung drops a pair."""
    whole_config = dict(TINY, num_experts=8,
                        expert_parallel={"size": 1, "rank": 0})
    whole, weights, _tokens = _setup(whole_config)
    assert whole == ref.uncut(ref.Sizes.from_config(TINY))
    p = weights["blocks"][1]
    p["router_bias"] = p["router_bias"].at[2:4].add(boost)
    m = jax.random.normal(jax.random.PRNGKey(5), (2 * SEQ, 64), jnp.float32)
    shared = ref._swiglu(m, p["shared_wg"], p["shared_wi"], p["shared_wo"],
                         "f32")
    uncut = ref.routed_part(p, m, whole, "f32") + shared
    total = shared
    every_row, taken = 0, []
    for rank in range(4):
        share_w, share_sz = ref.share_of(weights, whole, 2 * rank, 2)
        sp = share_w["blocks"][1]
        part = ref.routed_part(sp, m, share_sz, "f32")
        mine, rows = moe.routed_experts(
            m, sp["router"], sp["router_bias"], sp["experts_wg"],
            sp["experts_wi"], sp["experts_wo"], held=(2 * rank, 2),
            top_k=2, route_scale=2.448, tile_m=tile_m)
        np.testing.assert_allclose(np.asarray(mine), np.asarray(part),
                                   atol=2e-5)
        total = total + part
        every_row += int(np.sum(rows))
        taken.append(moe.route_counts(np.asarray(rows)[None], 2 * SEQ,
                                      2)["rows_computed"])
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=2e-5)
    assert every_row == 2 * SEQ * 2     # no pair dropped, none counted twice
    assert taken == rungs


@pytest.mark.parametrize("boost,rungs", [
    (0.0, [256] * 4), (0.5, [256, 512, 256, 256]),
    (20.0, [256, 512, 256, 256])])
def test_the_shares_add_up_over_a_mesh_axis(boost, rungs):
    """`make_moe_fn` on four CPU devices, 2 of 8 experts a shard, 512
    pairs in row tiles of 256 (ladder 256, 512): each shard takes its
    own rung, the branch holds no collective, and the summed parts
    equal the layer that holds every expert (one rung, no branch)."""
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh
    rng = np.random.RandomState(0)
    T, D, F, E, K = 256, 16, 128, 8, 2
    h = jnp.asarray(rng.randn(T, D), jnp.float32)
    router = jnp.asarray(rng.randn(D, E) * 0.5, jnp.float32)
    bias = jnp.asarray(rng.randn(E) * 0.2, jnp.float32).at[2:4].add(boost)
    wg, wi = (jnp.asarray(rng.randn(E, D, F) * 0.1, jnp.float32)
              for _ in range(2))
    wo = jnp.asarray(rng.randn(E, F, D) * 0.1, jnp.float32)
    local, rows = moe.routed_experts(h, router, bias, wg, wi, wo,
                                     held=(0, E), top_k=K, route_scale=2.0)
    mesh = make_mesh(MeshSpec(tp=4), jax.devices()[:4])
    with mesh:
        dist, dist_rows = jax.jit(moe.make_moe_fn(
            mesh, top_k=K, route_scale=2.0))(h, router, bias, wg, wi, wo)
    np.testing.assert_allclose(np.asarray(dist), np.asarray(local),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(dist_rows), np.asarray(rows))
    assert int(dist_rows.sum()) == T * K
    by_shard = np.asarray(dist_rows).reshape(4, 1, 2)
    assert [moe.route_counts(r, T, K)["rows_computed"]
            for r in by_shard] == rungs


# The cell's proportions at a small size: 32 of 256 experts held (not the
# first 32), top-4, 64 tokens = 256 pairs in row tiles of 8: the even
# share is 32 rows and the ladder (64, 128, 256).
CELL = dict(T=64, D=32, F=64, E=256, first=64, count=32, K=4, tile_m=8)


def _cell_layer(bias_on_held, seed=0):
    """-> the layer's arguments; `bias_on_held [count]` is added to the
    held experts' selection bias."""
    c = CELL
    key = jax.random.split(jax.random.PRNGKey(seed), 5)
    m = jax.random.normal(key[0], (c["T"], c["D"]), jnp.float32)
    router = jax.random.normal(key[1], (c["D"], c["E"])) * c["D"] ** -0.5
    bias = jnp.zeros((c["E"],)).at[c["first"]:c["first"] + c["count"]].add(
        jnp.asarray(bias_on_held, jnp.float32))
    wg, wi = (jax.random.normal(k, (c["count"], c["D"], c["F"])) * 0.2
              for k in key[2:4])
    wo = jax.random.normal(key[4], (c["count"], c["F"], c["D"])) * 0.2
    return m, router, bias, wg, wi, wo


def _cell_call(args, **more):
    c = CELL
    return moe.routed_experts(
        *args, held=(c["first"], c["count"]), top_k=c["K"], route_scale=2.448,
        tile_m=c["tile_m"], interpret=True, **more)


@pytest.fixture
def own_traces():
    """The rungs are traced inside a jitted function, which keeps its
    traces by shape: a test that swaps one of the module's functions
    under it starts from none and leaves none behind."""
    moe._held_part.clear_cache()
    yield
    moe._held_part.clear_cache()


ROUTERS = {     # bias on the held experts -> the rows they are given
    "no_row_held": np.full(32, -20.0),
    "even": np.zeros(32),
    # one held expert draws ten times the mean, as in the cell
    "one_hot_expert": np.r_[0.25, np.zeros(31)],
    "past_the_first_rung": np.full(32, 0.07),       # 90 rows
    "past_the_second_rung": np.full(32, 0.12),      # 143 rows
    # every token's four choices are held: only the top rung fits
    "every_pair_held": np.r_[np.full(4, 20.0), np.zeros(28)],
}


@pytest.mark.parametrize("name,smallest", [
    ("no_row_held", 64), ("even", 64), ("one_hot_expert", 64),
    ("past_the_first_rung", 128), ("past_the_second_rung", 256),
    ("every_pair_held", 256)])
def test_every_rung_gives_the_top_rungs_result(monkeypatch, own_traces, name,
                                               smallest):
    """Forced onto each rung that holds the held rows, the layer gives
    the top rung's result (the parent's: every row) bit for bit; left
    alone it takes the smallest of them."""
    args = _cell_layer(ROUTERS[name])
    ladder = moe._ladder(256, 32, 256, 8)
    assert ladder == (64, 128, 256)
    alone, rows = _cell_call(args)
    held = int(np.sum(rows))
    if name == "one_hot_expert":
        assert 8 < np.max(rows) / np.mean(rows) < 14
    assert held == {"no_row_held": 0, "every_pair_held": 256}.get(name, held)
    assert min(rung for rung in ladder if rung >= held) == smallest
    monkeypatch.setattr(moe, "_rung", lambda _held, _ladder: 2)
    moe._held_part.clear_cache()
    top, _rows = _cell_call(args)
    np.testing.assert_array_equal(np.asarray(alone), np.asarray(top))
    for index, rung in enumerate(ladder[:-1]):
        if rung >= held:
            monkeypatch.setattr(moe, "_rung", lambda _held, _ladder: index)
            moe._held_part.clear_cache()
            forced, forced_rows = _cell_call(args)
            np.testing.assert_array_equal(np.asarray(forced),
                                          np.asarray(top))
            np.testing.assert_array_equal(np.asarray(forced_rows),
                                          np.asarray(rows))


@pytest.mark.parametrize("name", sorted(ROUTERS))
def test_route_counts_names_the_rung_the_jitted_layer_took(
        monkeypatch, own_traces, name):
    """The host's `rows_computed` against the device's own choice: the
    row count of the operands that reached the kernel in the branch
    that ran."""
    seen, kernel = [], moe.gmm

    def watched(lhs, *rest, **more):
        jax.debug.callback(lambda: seen.append(lhs.shape[0]))
        return kernel(lhs, *rest, **more)

    monkeypatch.setattr(moe, "gmm", watched)
    _out, rows = jax.jit(lambda args: _cell_call(args))(
        _cell_layer(ROUTERS[name]))
    jax.effects_barrier()
    counts = moe.route_counts(np.asarray(rows)[None], CELL["T"], CELL["K"])
    assert len(seen) == 3 and set(seen) == {counts["rows_computed"]}
    assert counts["rows_total"] == 256
    assert counts["rows_held"] <= counts["rows_computed"] <= 256


@pytest.mark.parametrize("rows,count,n_experts,tile_m,ladder", [
    # the cell's four padded lengths, 32 of 256 experts, top-4
    (24576, 32, 256, 256, (6144, 12288, 24576)),
    (32768, 32, 256, 256, (8192, 16384, 32768)),
    (49152, 32, 256, 256, (12288, 24576, 49152)),
    (65536, 32, 256, 256, (16384, 32768, 65536)),
    (65536, 128, 256, 256, (65536,)),       # half the experts: one rung
    (65536, 256, 256, 256, (65536,)),       # all of them
    (1024, 3, 8, 256, (768, 1024)),         # no power of two
    (96, 2, 8, 8, (48, 96)), (96, 2, 8, 96, (96,))])
def test_the_ladder_follows_from_shapes(rows, count, n_experts, tile_m,
                                        ladder):
    assert moe._ladder(rows, count, n_experts, tile_m) == ladder
    assert all(rung % tile_m == 0 for rung in ladder)
    for held in (0, 1, ladder[0], min(ladder[0] + 1, rows), rows):
        taken = ladder[moe._rung(held, ladder)]
        assert taken >= held and taken == min(r for r in ladder if r >= held)


def _conditionals(jaxpr) -> int:
    """`cond` equations of a jaxpr, outside its Pallas kernels (whose
    `pl.when` is one)."""
    found = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        found += eqn.primitive.name == "cond"
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _conditionals(sub)
    return found


@pytest.mark.parametrize("count,branches", [(8, 0), (4, 0), (2, 1), (1, 1)])
def test_a_call_that_holds_half_the_experts_traces_no_conditional(
        count, branches):
    """With every expert held (`count == n_experts`) or half of them
    the layer is the program it was: one rung, no branch."""
    rng = np.random.RandomState(0)
    args = (rng.randn(64, 16), rng.randn(16, 8), np.zeros(8),
            rng.randn(count, 16, 32), rng.randn(count, 16, 32),
            rng.randn(count, 32, 16))
    traced = jax.make_jaxpr(lambda *a: moe.routed_experts(
        *a, held=(0, count), top_k=2, route_scale=1.0, tile_m=8,
        interpret=True))(*map(jnp.float32, args))
    assert _conditionals(traced.jaxpr) == branches


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


# --------------------------------------------------------------------------
# The sort: the sorts and counts against the gathers, the scatter and the
# binary searches they replaced, bit for bit
# --------------------------------------------------------------------------

def _sorted_by_gather(experts, n_experts, held, rows):
    """`_sorted_by_expert` as it was: argsort, the keys gathered in
    order, the positions scattered, the bounds by binary search."""
    first, count = held
    t, k = experts.shape
    key = ((experts - first) % n_experts).reshape(-1)
    key = jnp.pad(key, (0, rows - t * k), constant_values=n_experts)
    order = jnp.argsort(key, stable=True)
    position = jnp.zeros((rows,), jnp.int32).at[order].set(
        jnp.arange(rows, dtype=jnp.int32))[:t * k].reshape(t, k)
    sorted_key = key[order]
    groups = jnp.arange(count, dtype=key.dtype)
    starts = jnp.searchsorted(sorted_key, groups, side="left")
    ends = jnp.searchsorted(sorted_key, groups, side="right")
    token = jnp.minimum(order // k, t - 1).astype(jnp.int32)
    return token, position, starts.astype(jnp.int32), ends.astype(jnp.int32)


def _rows_of(t, k):
    """The sorted list's length as `routed_experts` pads it."""
    tile_m = min(moe._TILE_M, moe._round_up(t * k, 8))
    return moe._round_up(t * k, tile_m)


# Both routed cells in small: 256 published experts, 32 held, top-4
# (Trinity) or top-8 (Kimi-Linear); bias on the held experts as ROUTERS
# names it, or ties planted by repeating each router column (and bias).
PLUMBING = {
    # name: (tokens, first, bias on the held experts, repeats a column)
    "even": (64, 0, np.zeros(32), None),
    "rotated_first": (64, 64, np.zeros(32), None),
    "last_share": (64, 224, np.zeros(32), None),
    # each column thirteen times: every top-k is ties
    "tied": (64, 0, np.zeros(32), 13),
    # columns in threes: the k-th and the next place tie on every token
    "tied_at_the_kth_place": (64, 64, np.zeros(32), 3),
    "one_expert_takes_most": (64, 64, np.r_[0, 0, 0, 5.0, np.zeros(28)],
                              None),
    "no_pair_held": (64, 64, np.full(32, -20.0), None),
    "every_pair_held": (64, 64, np.r_[np.full(8, 20.0), np.zeros(24)], None),
    # T x k is no whole tile: the sorted list is padded past the pairs
    "padded": (61, 64, np.zeros(32), None),
    "padded_tied": (99, 0, np.zeros(32), 13),
}


def _plumbing_inputs(name, seed=0, d=32):
    t, first, on_held, repeats = PLUMBING[name]
    key = jax.random.split(jax.random.PRNGKey(seed), 3)
    m = jax.random.normal(key[0], (t, d), jnp.float32)
    router = jax.random.normal(key[1], (d, 256)) * d ** -0.5
    bias = jax.random.normal(key[2], (256,)) * 0.02
    if repeats:
        column = np.arange(256) // repeats
        router, bias = router[:, column], bias[column]
    bias = bias.at[first:first + 32].add(jnp.asarray(on_held, jnp.float32))
    return m, router, bias, first


@pytest.mark.parametrize("top_k", [4, 8])
@pytest.mark.parametrize("name", sorted(PLUMBING))
def test_the_plumbing_is_the_gathers_to_the_bit(name, top_k):
    """Each sorted row's token, each pair's position and the held
    experts' bounds are what the gathers, the scatter and the binary
    searches gave, element for element and type for type."""
    m, router, bias, first = _plumbing_inputs(name)
    t = m.shape[0]
    rows = _rows_of(t, top_k)
    experts, _weights = jax.jit(lambda *a: moe.route(
        *a, top_k=top_k, route_scale=2.446))(m, router, bias)
    got = jax.jit(moe._sorted_by_expert, static_argnums=(1, 2, 3))(
        experts, 256, (first, 32), rows)
    want = jax.jit(_sorted_by_gather, static_argnums=(1, 2, 3))(
        experts, 256, (first, 32), rows)
    for what, g, w in zip(("token", "position", "starts", "ends"), got,
                          want):
        assert (g.dtype, g.shape) == (w.dtype, w.shape), what
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), what)
    given = np.asarray(got[3] - got[2])
    scores = np.sort(np.asarray(jax.nn.sigmoid(
        m @ router) + bias), axis=1)[:, ::-1]
    ties_at_kth = np.sum(scores[:, top_k - 1] == scores[:, top_k])
    if name.startswith("tied"):
        assert ties_at_kth > 0
    if name == "tied_at_the_kth_place":
        assert ties_at_kth == t
    if name == "one_expert_takes_most":
        assert given[3] == t and given[3] > given.sum() / 2
    if name == "no_pair_held":
        assert given.sum() == 0 and not np.any(np.asarray(got[3]))
    if name == "every_pair_held":
        assert given.sum() == t * top_k
    if name.startswith("padded"):
        assert rows > t * top_k


@pytest.mark.parametrize("name", ["even", "tied", "one_expert_takes_most",
                                  "padded"])
def test_the_layer_is_the_one_the_gathers_made(monkeypatch, own_traces,
                                               name):
    """The whole routed layer at the Kimi-Linear cell's top-8, small:
    its result and row counts are bit for bit those of the layer whose
    sort gathers, scatters and searches."""
    m, router, bias, first = _plumbing_inputs(name)
    key = jax.random.split(jax.random.PRNGKey(1), 3)
    wg, wi = (jax.random.normal(k, (32, 32, 64)) * 0.2 for k in key[:2])
    wo = jax.random.normal(key[2], (32, 64, 32)) * 0.2

    def layer():
        return jax.jit(lambda *a: moe.routed_experts(
            *a, held=(first, 32), top_k=8, route_scale=2.446, tile_m=8,
            interpret=True))(m, router, bias, wg, wi, wo)

    out, rows = layer()
    monkeypatch.setattr(moe, "_sorted_by_expert", _sorted_by_gather)
    moe._held_part.clear_cache()
    was_out, was_rows = layer()
    np.testing.assert_array_equal(np.asarray(out), np.asarray(was_out))
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(was_rows))


@pytest.mark.parametrize("top_k", [4, 8])
def test_the_plumbing_under_a_traced_first(top_k):
    """Under `shard_map`, as `make_moe_fn` calls it, `first` is the
    axis index times the held count: each of four shards' sort is the
    gathers' at its own share."""
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh
    m, router, bias, _first = _plumbing_inputs("tied")
    t = m.shape[0]
    rows = _rows_of(t, top_k)
    experts, _weights = moe.route(m, router, bias, top_k=top_k,
                                  route_scale=2.446)

    def body(experts):
        first = jax.lax.axis_index("tp") * 32
        return tuple(x[None] for x in moe._sorted_by_expert(
            experts, 256, (first, 32), rows))

    mesh = make_mesh(MeshSpec(tp=4), jax.devices()[:4])
    with mesh:
        got = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P(), out_specs=(P("tp"),) * 4,
            check_vma=False))(experts)
    for shard in range(4):
        want = _sorted_by_gather(experts, 256, (32 * shard, 32), rows)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g)[shard],
                                          np.asarray(w))


def test_the_routed_layer_sorts_with_no_gather_or_scatter_over_the_pairs():
    """The sort at the Kimi-Linear cell's shape in small (top-8 of 256,
    32 held) lowers with no gather and no scatter; the same check finds
    them in the form it replaced."""
    experts = jnp.zeros((64, 8), jnp.int32)
    rows = _rows_of(64, 8)

    def lowered(sort):
        return jax.jit(sort, static_argnums=(1, 2, 3)).lower(
            experts, 256, (64, 32), rows).as_text()

    text = lowered(moe._sorted_by_expert)
    assert "stablehlo.sort" in text
    assert "stablehlo.gather" not in text
    assert "stablehlo.scatter" not in text
    was = lowered(_sorted_by_gather)
    assert "stablehlo.gather" in was and "stablehlo.scatter" in was
