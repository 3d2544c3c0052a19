"""The two mixers MiniCPM-SALA brings (lightning linear attention,
block-sparse attention with a per-query choice) as kernels against their
plain references, and the layer pattern that holds them against the
benchmark's reference model, at tiny sizes on the CPU (the kernels in
interpret mode)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import minicpm_sala as ref
from ray_tpu.models import (LayerSpec, SparseSizes, TransformerConfig,
                            config_from_hf, forward, forward_with_stats,
                            init_params, param_specs)
from ray_tpu.models.transformer import remat_plan
from ray_tpu.ops.lightning_attention import (
    choose_chunk, decay_slopes, lightning_attention, lightning_reference)
from ray_tpu.ops.sparse_attention import (
    attend_mask, keys_counted, select_blocks_reference, select_mask,
    selected_attention, sparse_reference, units_visited)

SMALL = SparseSizes(kernel=8, stride=4, block=16, top_k=6, window=32,
                    init_blocks=1, dense_len=32)
ODD = SparseSizes(kernel=16, stride=8, block=8, top_k=5, window=16,
                  init_blocks=2, dense_len=32)


def select_blocks(q, k, sizes):
    """The kernel's choice as the references' lists: `[B, S, G, top_k]`
    int32, ascending, -1 where a query has fewer blocks."""
    mask = np.asarray(select_mask(q, k, sizes, True))[..., :q.shape[1]]
    lists = np.full((*np.moveaxis(mask, (1, 2), (2, 3)).shape[:3],
                     sizes.top_k), -1, np.int32)
    for b, g, t in np.ndindex(mask.shape[0], mask.shape[1], mask.shape[3]):
        taken = np.flatnonzero(mask[b, g, :, t])
        lists[b, t, g, :len(taken)] = taken
    return lists


def mask_of(blocks, seq, sizes):
    """Lists of blocks as the mask the attention kernel takes: `[B, G,
    blocks, S]` int32, both sizes padded to whole lanes."""
    rows = -(-(-(-seq // sizes.block)) // 128) * 128
    mask = np.any(np.asarray(blocks)[..., None] == np.arange(rows), axis=3)
    mask = np.moveaxis(mask, (2, 3), (1, 2)).astype(np.int32)
    return jnp.asarray(np.pad(mask, [(0, 0)] * 3 + [(0, -seq % 128)]))


def sparse_attention(q, k, v, blocks, sizes):
    return attend_mask(q, k, v, mask_of(blocks, q.shape[1], sizes), sizes,
                       True)


def qkv(seq, heads, kv_heads, head_dim, seed=0, batch=2):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (batch, seq, heads, head_dim)),
            jax.random.normal(keys[1], (batch, seq, kv_heads, head_dim)),
            jax.random.normal(keys[2], (batch, seq, kv_heads, head_dim)))


# ---- lightning attention --------------------------------------------------

@pytest.mark.parametrize("seq", [128, 200, 40])
def test_lightning_kernel_is_the_quadratic_form(seq):
    """Lengths that are and are not whole chunks of 64."""
    q, k, v = qkv(seq, 4, 4, 32)
    slopes = decay_slopes(4)
    got = lightning_attention(q, k, v, slopes, 64, True)
    want = lightning_reference(q, k, v, slopes)
    np.testing.assert_allclose(got, want, atol=2e-4 * float(
        jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("head", [0, 31])
def test_lightning_kernel_is_the_recurrence(head):
    """The steepest and the flattest of 32 slopes, token by token:
    M <- exp(-s) M + k^T v, o = q M / sqrt(H)."""
    slope = decay_slopes(32)[head]
    q, k, v = qkv(96, 1, 1, 16, seed=head, batch=1)

    def step(state, row):
        qt, kt, vt = row
        state = np.exp(-slope) * state + jnp.outer(kt, vt)
        return state, qt @ state / 4.0

    _, want = jax.lax.scan(step, jnp.zeros((16, 16)),
                           (q[0, :, 0], k[0, :, 0], v[0, :, 0]))
    got = lightning_attention(q, k, v, jnp.array([slope]), 32, True)
    np.testing.assert_allclose(got[0, :, 0], want, atol=1e-4)


def prologue_case(dtype):
    """Raw q, k and v of 200 tokens (no whole number of chunks) with what
    a layer's q/k norm and RoPE take, positions that start past 0, and
    `model(q, k, norm, rotate)`: the two as `jax.numpy` does them."""
    from ray_tpu.models.transformer import _norm, _rope_tables, rope
    cfg = TransformerConfig(n_layers=1, vocab_size=96, d_model=64,
                            dtype=dtype, rms_norm_eps=1e-5)
    q, k, v = (x.astype(dtype) for x in qkv(200, 4, 4, 32))
    scales = (1.0 + 0.5 * jax.random.normal(
        jax.random.PRNGKey(7), (2, 32))).astype(dtype)
    positions = jnp.arange(200)[None] + jnp.array([[3], [1000]])
    cos, sin, swap = _rope_tables(positions, 32, 10_000.0, dtype)
    tables = cos[:, :, 0], sin[:, :, 0], swap

    def model(q, k, norm=True, rotate=True):
        if norm:
            q, k = _norm(q, scales[0], cfg), _norm(k, scales[1], cfg)
        if rotate:
            q, k = (rope(x, positions, 10_000.0) for x in (q, k))
        return q, k

    return q * 3, k / 4, v, scales, tables, model     # a norm left out shows


@pytest.mark.parametrize("chunk", [64, 128])
@pytest.mark.parametrize("rotate", [False, True])
@pytest.mark.parametrize("norm", [False, True])
def test_lightning_kernel_norms_and_rotates_its_tiles(norm, rotate, chunk):
    """The layer's q/k norm and RoPE done by the kernel on raw q and k,
    against `_norm` + `rope` + the quadratic form: three chunks and a
    part, or one and a part."""
    q, k, v, scales, tables, model = prologue_case(jnp.float32)
    slopes = decay_slopes(4)
    want = lightning_reference(*model(q, k, norm, rotate), v, slopes)
    got = lightning_attention(
        q, k, v, slopes, chunk, True, qk_scales=scales if norm else None,
        norm_eps=1e-5, rope=tables if rotate else None)
    np.testing.assert_allclose(got, want, atol=2e-4 * float(
        jnp.max(jnp.abs(want))))


def test_lightning_prologue_rounds_where_the_model_rounds():
    """bfloat16: the kernel's norm and rotation against `_norm` and
    `rope` feeding the kernel without them. Equal but where the norm's
    sum over a head, added in another order, tips a rounding: under a
    thousandth of the elements, by one bfloat16 step."""
    q, k, v, scales, tables, model = prologue_case(jnp.bfloat16)
    slopes = decay_slopes(4)
    want = lightning_attention(*model(q, k), v, slopes, 64, True)
    got = lightning_attention(q, k, v, slopes, 64, True, qk_scales=scales,
                              norm_eps=1e-5, rope=tables)
    assert got.dtype == jnp.bfloat16
    assert float(jnp.mean(got != want)) < 1e-3
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), rtol=2 ** -7)


def test_lightning_slopes_and_chunk_from_the_shape():
    slopes = decay_slopes(32)
    assert slopes[0] == pytest.approx(2 ** -0.25)
    assert slopes[31] == pytest.approx(2 ** -8)
    assert np.all(np.diff(slopes) < 0)
    assert choose_chunk(16384) == choose_chunk(32768) == 256
    assert choose_chunk(12288) == 256 and choose_chunk(100) == 128
    assert choose_chunk(128 * 3) == 128


def test_lightning_takes_heads_alike_and_has_no_backward():
    q, k, v = qkv(64, 4, 2, 16)
    with pytest.raises(ValueError, match="alike"):
        lightning_attention(q, k, v, decay_slopes(4))
    q, k, v = qkv(64, 2, 2, 16)
    with pytest.raises(NotImplementedError, match="lightning_attention"):
        jax.grad(lambda q: lightning_attention(
            q, k, v, decay_slopes(2), 32, True).sum())(q)


# ---- the choice of blocks -------------------------------------------------

@pytest.mark.parametrize("sizes,seq", [(SMALL, 256), (SMALL, 200),
                                       (ODD, 256)])
def test_the_kernel_chooses_the_blocks_the_reference_ranks(sizes, seq):
    """More candidate blocks than `top_k` holds (16 and 32 of them),
    index for index, in float32."""
    q, k, _v = qkv(seq, 4, 2, 32)
    got = select_blocks(q, k, sizes)
    want = select_blocks_reference(q, k, sizes)
    np.testing.assert_array_equal(got, want)
    last = np.asarray(want[0, -1, 0])
    b_t = (seq - 1) // sizes.block
    assert (last >= 0).all() and len(set(last)) == sizes.top_k
    assert set(range(sizes.init_blocks)) <= set(last)
    assert set(range(b_t - sizes.window // sizes.block + 1, b_t + 1)) <= \
        set(last)
    # a query with few blocks before it takes them all
    early = np.asarray(want[0, sizes.block, 1])
    assert list(early[:2]) == [0, 1] and (early[2:] == -1).all()


def test_ties_go_to_the_lower_block():
    """Equal keys give every compressed key the same weight: the free
    blocks tie, and the choice takes the lowest."""
    q, _k, _v = qkv(256, 4, 2, 32)
    k = jnp.ones((2, 256, 2, 32))
    got = select_blocks(q, k, SMALL)
    np.testing.assert_array_equal(got, select_blocks_reference(q, k, SMALL))
    # b_t = 15: blocks 0, 14, 15 forced, then the three lowest free ones
    assert list(np.asarray(got[0, -1, 0])) == [0, 1, 2, 3, 14, 15]


# ---- attention over the chosen blocks -------------------------------------

@pytest.mark.parametrize("sizes,seq", [(SMALL, 256), (ODD, 200)])
def test_sparse_kernel_given_the_references_blocks(sizes, seq):
    q, k, v = qkv(seq, 4, 2, 32, seed=3)
    blocks = select_blocks_reference(q, k, sizes)
    got = sparse_attention(q, k, v, blocks, sizes)
    want = sparse_reference(q, k, v, blocks, sizes)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_sparse_kernels_end_to_end():
    """Choice and attention, the mask between them never a list."""
    q, k, v = qkv(200, 4, 2, 32, seed=4)
    got, visited = selected_attention(q, k, v, SMALL, True)
    want = sparse_reference(q, k, v, select_blocks_reference(q, k, SMALL),
                            SMALL)
    np.testing.assert_allclose(got, want, atol=2e-6)
    # two tiles of queries: one unit of 128 keys before the first, two
    # before the second, in each of two KV groups of two sequences
    assert int(visited) == 4 * (1 + 2)


def test_a_query_counts_only_its_own_blocks():
    """Two queries of one tile that choose different blocks: the block
    the first alone chose does not reach the second."""
    q, k, v = qkv(128, 2, 1, 16, seed=5, batch=1)
    sizes = SparseSizes(kernel=8, stride=4, block=16, top_k=3, window=16,
                        init_blocks=1, dense_len=0)
    t = np.arange(128)
    blocks = np.stack([np.zeros(128, int), np.where(t % 2, 2, 3),
                       t // 16], axis=1)
    blocks = np.where(blocks > (t // 16)[:, None], -1, blocks)
    blocks = jnp.asarray(np.sort(np.where(blocks < 0, 99, blocks), axis=1)
                         .astype(np.int32))
    blocks = jnp.where(blocks == 99, -1, blocks)[None, :, None, :]
    got = sparse_attention(q, k, v, blocks, sizes)
    want = sparse_reference(q, k, v, blocks, sizes)
    np.testing.assert_allclose(got, want, atol=2e-6)
    v2 = v.at[0, 32:48].add(100.0)          # block 2: the odd queries'
    moved = sparse_attention(q, k, v2, blocks, sizes) - got
    assert float(jnp.max(jnp.abs(moved[0, 64::2]))) == 0.0
    assert float(jnp.min(jnp.max(jnp.abs(moved[0, 65::2]),
                                 axis=(1, 2)))) > 0.0


def test_sparse_attention_has_no_backward_and_counts_its_keys():
    q, k, v = qkv(64, 4, 2, 16)
    with pytest.raises(NotImplementedError, match="sparse_attention"):
        jax.grad(lambda q: selected_attention(
            q, k, v, SMALL, True)[0].sum())(q)
    counted = keys_counted(300, SMALL)
    t = np.arange(300)
    assert counted["keys_causal"] == 300 * 301 // 2
    assert counted["keys_selected"] == sum(
        i + 1 if i // 16 < 6 else 5 * 16 + i % 16 + 1 for i in t)
    # three tiles of 128 queries with 1, 2 and 3 units of 128 keys
    assert (counted["units_before"], counted["visit_pairs"]) == (
        6, 128 * 128)


def test_the_units_counted_are_the_units_the_kernel_reads():
    """Queries that choose alike leave units of keys unvisited: the
    count says how many, and values that are not numbers in exactly
    those units reach no result (a unit the kernel visited would give
    0 x nan)."""
    seq, sizes = 1024, SparseSizes(kernel=8, stride=4, block=16, top_k=4,
                                   window=32, init_blocks=1, dense_len=0)
    q, k, v = qkv(seq, 2, 1, 16, seed=6, batch=1)
    t = np.arange(seq)
    # the first block, one more of the first unit, the window of two
    # (the mask takes them in any order, twice or not)
    blocks = jnp.asarray(np.stack(
        [0 * t, t // 128, np.maximum(t // 16 - 1, 0), t // 16],
        axis=1).astype(np.int32))[None, :, None, :]
    mask = mask_of(blocks, seq, sizes)
    visited = np.asarray(mask).reshape(1, 1, -1, 8, 8, 128).max(axis=(3, 5))
    assert int(units_visited(mask, sizes)) == visited.sum()
    # a tile visits the first unit, its own and the one before it
    assert visited.sum() == 1 + 2 + 6 * 3
    assert keys_counted(seq, sizes)["units_before"] == 36
    # units 1-5 are read by tiles 1-6 alone: spoil them for tile 7
    spoilt = (t >= 128) & (t < 768)
    v_nan = jnp.where(spoilt[None, :, None, None], jnp.nan, v)
    got = attend_mask(q, k, v_nan, mask, sizes, True)
    np.testing.assert_allclose(
        got[:, 896:], sparse_reference(q, k, v, blocks, sizes)[:, 896:],
        atol=2e-6)


def test_sparse_sizes_have_to_fit_together():
    assert SparseSizes() == SparseSizes(32, 16, 64, 64, 2048, 1, 8192)
    with pytest.raises(ValueError):
        SparseSizes(kernel=24)
    with pytest.raises(ValueError):
        SparseSizes(top_k=32)       # 1 + 32 forced blocks do not fit


# ---- the layer pattern ----------------------------------------------------

TINY = {"model_type": "minicpm_sala", "architecture": "minicpm_sala",
        "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "lightning_nh": 4,
        "lightning_nkv": 4, "lightning_head_dim": 16,
        "intermediate_size": 128, "vocab_size": 96,
        "torch_dtype": "float32", "attn_use_rope": False,
        "lightning_use_rope": True, "qk_norm": True, "rms_norm_eps": 1e-6,
        "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4,
        "dim_model_base": 256, "use_output_gate": True,
        "use_output_norm": True, "attn_use_output_gate": True,
        "published": {"num_hidden_layers": 32},
        "sparse_config": {"kernel_size": 8, "kernel_stride": 4,
                          "block_size": 8, "topk": 6, "window_size": 16,
                          "init_blocks": 1, "dense_len": 32}}


def tiny(mixers):
    return dict(TINY, mixer_types=list(mixers),
                num_hidden_layers=len(mixers))


PERIOD = ["minicpm4"] + ["lightning-attn"] * 3
SLICE = ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"]


@pytest.mark.parametrize("mixers,flash,lengths", [
    (PERIOD, False, (96,)), (PERIOD, True, (24, 96)), (SLICE, True, (96,))])
def test_forward_is_the_reference_model(mixers, flash, lengths):
    """One period (1 + 3) and the benchmark's 8-layer slice on seeded
    weights, a prompt under `dense_len` and one over it."""
    config = tiny(mixers)
    sz = ref.Sizes.from_config(config)
    weights = ref.make_weights(ref.seed_key(2 ** 31 + 9), sz)
    cfg = dataclasses.replace(config_from_hf(config, 128), use_flash=flash,
                              remat=False)
    for seq in lengths:
        tokens = jax.random.randint(jax.random.PRNGKey(seq), (2, seq), 0, 96)
        last = jnp.array([seq - 1, seq // 2])
        want = ref.logits_at(weights, tokens, last, sz)
        got, stats = forward_with_stats(weights, tokens, cfg,
                                        logit_positions=last)
        assert stats["moe_rows"].shape == (0, 0)
        np.testing.assert_allclose(got, want, atol=1e-4)
        assert float(jnp.max(jnp.abs(want))) > 1.0


def test_a_sparse_layer_under_dense_len_is_causal_attention():
    config = tiny(["minicpm4", "minicpm4"])
    cfg = dataclasses.replace(config_from_hf(config, 64), remat=False)
    plain = dataclasses.replace(
        cfg, layers=(LayerSpec(rope=False),) * 2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.arange(32)[None] % 96
    np.testing.assert_array_equal(forward(params, tokens, cfg),
                                  forward(params, tokens, plain))
    longer = jnp.arange(64)[None] % 96      # eight blocks, six taken
    assert float(jnp.max(jnp.abs(forward(params, longer, cfg)
                                 - forward(params, longer, plain)))) > 1e-4


def test_config_from_hf_reads_the_family():
    cfg = config_from_hf(tiny(SLICE), 128)
    sparse = LayerSpec(mixer="sparse", rope=False)
    lightning = LayerSpec(mixer="lightning", rope=True, kv_heads=4)
    assert cfg.layers == (sparse,) + (lightning,) * 6 + (sparse,)
    assert cfg.residual_scale == pytest.approx(1.4 / np.sqrt(32))
    assert cfg.logit_scale == 4.0 and cfg.embed_scale == 12.0
    assert cfg.qk_norm and cfg.attn_gate and cfg.mixer_out_norm
    assert cfg.sparse == SparseSizes(8, 4, 8, 6, 16, 1, 32)
    assert config_from_hf(dict(tiny(SLICE), sparse_config={}),
                          128).sparse == SparseSizes()
    with pytest.raises(ValueError, match="'mistral', 'afmoe', "
                                         "'minicpm_sala', 'evabyte' and "
                                         "'kimi_linear'"):
        config_from_hf(dict(tiny(SLICE), model_type="other"), 128)
    with pytest.raises(ValueError, match="no such layer"):
        TransformerConfig(n_layers=1, layers=(LayerSpec(mixer="scan"),))


def test_the_specs_of_the_other_families_compare_as_before():
    assert LayerSpec() == LayerSpec(None, True, False)
    assert hash(LayerSpec(window=8)) == hash(LayerSpec(8, True, False,
                                                       "softmax", None))
    assert LayerSpec().mixer == "softmax" and LayerSpec().kv_heads is None
    plain = TransformerConfig()
    assert (plain.residual_scale, plain.logit_scale,
            plain.mixer_out_norm) == (1.0, 1.0, False)


def test_parameters_follow_each_layers_kind():
    cfg = config_from_hf(tiny(PERIOD), 128)
    params = init_params(jax.random.PRNGKey(1), cfg)
    specs = param_specs(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda x: not isinstance(x, (dict, list)))
    sparse, lightning = params["blocks"][0], params["blocks"][1]
    assert sparse["wk"].shape == (64, 2, 16)
    assert lightning["wk"].shape == lightning["wq"].shape == (64, 4, 16)
    assert lightning["out_norm"].shape == (64,)
    assert "out_norm" not in sparse and "wgate" in sparse
    sz = ref.Sizes.from_config(tiny(PERIOD))
    ours = {k: v.shape for k, v in lightning.items()}
    theirs = {p[2]: shape for p, shape, _k in ref.leaf_table(sz)
              if p[0] == "blocks" and p[1] == 1}
    assert ours == theirs


def test_a_mixer_that_cannot_train_keeps_nothing_under_remat():
    cfg = dataclasses.replace(
        config_from_hf(tiny(PERIOD + ["lightning-attn"]), 4096),
        use_flash=True, dtype=jnp.bfloat16,
        layers=(LayerSpec(),) + config_from_hf(tiny(PERIOD), 4096).layers)
    plan = remat_plan(cfg, 1, 4096, 10 ** 6, 16 * 10 ** 9)
    assert plan.levels[0] > 0 and plan.levels[1:] == (0, 0, 0, 0)


def test_tracing_a_forward_records_the_mixers_plan():
    from ray_tpu.util import tracing
    cfg = config_from_hf(tiny(SLICE), 128)
    shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    before = len([s for s in tracing.spans()
                  if s.name == "model.mixers.plan"])
    for seq in (24, 96):
        jax.eval_shape(lambda p, t: forward(p, t, cfg), shapes,
                       jax.ShapeDtypeStruct((1, seq), jnp.int32))
    short, long_ = [s.counts for s in tracing.spans()
                    if s.name == "model.mixers.plan"][before:]
    assert short == {
        "tokens": 24, "linear_layers": 6, "sparse_layers": 2,
        "sparse_mode": 0, "chunk": 128, "prologue_layers": 0,
        "state_bytes": 6 * 4 * 16 * 16 * 4,
        "keys_selected": 0, "keys_causal": 0}
    # on the kernel path the six lightning layers' norm and rotation
    # are the kernel's
    jax.eval_shape(lambda p, t: forward(
        p, t, dataclasses.replace(cfg, use_flash=True)), shapes,
        jax.ShapeDtypeStruct((1, 24), jnp.int32))
    assert [s.counts for s in tracing.spans()
            if s.name == "model.mixers.plan"][-1] == dict(
                short, prologue_layers=6)
    counted = keys_counted(96, cfg.sparse)
    assert long_["sparse_mode"] == 1 and long_["tokens"] == 96
    # two sparse layers of two KV groups each
    assert long_["keys_causal"] == 4 * 96 * 97 // 2
    assert long_["keys_selected"] == 4 * counted["keys_selected"]
    assert "keys_read" not in long_     # shapes cannot tell it
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


def test_a_forward_gives_back_the_units_it_visited():
    """`stats["sparse_units"]` beside the logits, and the record
    whoever reads them makes of them."""
    from ray_tpu.models import record_sparse_visits
    from ray_tpu.util import tracing
    cfg = dataclasses.replace(config_from_hf(tiny(PERIOD), 256),
                              use_flash=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    run = jax.jit(lambda p, t: forward_with_stats(p, t, cfg)[1])
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 256), 0, 96)
    units = np.asarray(run(params, tokens)["sparse_units"])
    counted = keys_counted(256, cfg.sparse)
    # one sparse layer of two KV groups
    assert units.shape == (1,) and 0 < units[0] <= 2 * counted[
        "units_before"]
    record_sparse_visits(units, cfg, 1, 256, "r1")
    record = [s for s in tracing.spans()
              if s.name == "model.sparse.visits"][-1]
    assert record.request == "r1" and record.counts == {
        "tokens": 256, "layers": 1, "units_visited": int(units[0]),
        "units_before": 2 * counted["units_before"],
        "keys_read": int(units[0]) * 128 * 128,
        "keys_selected": 2 * counted["keys_selected"],
        "keys_causal": 2 * 256 * 257 // 2}
    # under `dense_len`, and without the kernels, nothing is counted
    short = run(params, tokens[:, :24])
    plain = forward_with_stats(params, tokens, dataclasses.replace(
        cfg, use_flash=False))[1]
    assert "sparse_units" not in short and "sparse_units" not in plain
