"""Attention ops: pallas flash kernel vs dense reference; ring and
Ulysses sequence parallelism vs dense on the fake 8-device mesh."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from chip_smoke import _BWD_TOL, _FWD_TOL, _rel_err
from ray_tpu.ops import (
    flash_attention,
    make_attention_fn,
    mha_reference,
)
from ray_tpu.ops.flash_attention import (
    _VMEM_BUDGET,
    _blocks_for,
    _choose_blocks,
    _vmem_bytes,
)
from ray_tpu.parallel.mesh import MeshSpec, make_mesh


def _qkv(b=2, s=256, n=4, h=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, s, n, h)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_reference(causal):
    q, k, v = _qkv()
    ref = mha_reference(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal, None, 128, 128, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_gradients():
    q, k, v = _qkv(s=128)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None, 64, 64, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("s", [96, 160])   # not divisible by block 64
def test_flash_gradients_ragged_seq(causal, s):
    """Blockwise backward stays exact when seq % block != 0 (the
    clamped-tail de-dup mask on both dq and dkv loops)."""
    q, k, v = _qkv(s=s, n=2)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal, None, 64, 64, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def _out_and_grads(attend, q, k, v, w):
    def loss(q, k, v):
        out = attend(q, k, v)
        return jnp.sum(out.astype(jnp.float32) * w), out
    grads, out = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return (out, *grads)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("s,blocks", [
    (256, (128, 128)), (256, (None, None)), (160, (None, None)),
    (160, (64, 64)), (256, (128, 64)), (256, (64, 128))])
def test_flash_bf16_matches_reference(causal, s, blocks):
    """bf16 inputs go to the matmuls as bf16 (P and dS rounded to bf16,
    float32 accumulation): forward and gradients stay inside the
    tolerances chip_smoke.py holds them to on the chip, against the
    dense reference on the same inputs cast up."""
    q, k, v = _qkv(b=1, s=s, n=2, h=64, dtype=jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(7), q.shape, jnp.float32)
    got = _out_and_grads(
        lambda q, k, v: flash_attention(q, k, v, causal, None, *blocks,
                                        True), q, k, v, w)
    assert all(x.dtype == jnp.bfloat16 for x in got)
    want = _out_and_grads(
        lambda q, k, v: mha_reference(q, k, v, causal=causal),
        *(x.astype(jnp.float32) for x in (q, k, v)), w)
    assert _rel_err(got[0], want[0]) < _FWD_TOL
    for g, r in zip(got[1:], want[1:]):
        assert _rel_err(g, r) < _BWD_TOL


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("s", [96, 160, 256, 1024])
def test_flash_float32_with_chosen_blocks(causal, s):
    """float32 inputs keep float32 products at the blocks the chooser
    gives (block_q = block_k = None): the float32 tolerances hold."""
    q, k, v = _qkv(b=1, s=s, n=2, h=64)
    w = jax.random.normal(jax.random.PRNGKey(7), q.shape, jnp.float32)
    got = _out_and_grads(
        lambda q, k, v: flash_attention(q, k, v, causal, interpret=True),
        q, k, v, w)
    want = _out_and_grads(
        lambda q, k, v: mha_reference(q, k, v, causal=causal), q, k, v, w)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               atol=2e-5, rtol=2e-5)
    for g, r in zip(got[1:], want[1:]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=2e-4, rtol=2e-4)


def test_flash_float32_products_stay_float32():
    """The operand type follows the input: no bf16 appears in a
    float32 call's program, forward or backward."""
    q, k, v = _qkv(b=1, s=128, n=1, h=64)
    text = str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, interpret=True)),
        argnums=(0, 1, 2)))(q, k, v))
    assert "bf16" not in text


# (s_q, s_k, item size): what the cells run (S 4,096 in bf16, with and
# without gradients; prompts padded to 128, 256, 512), the ragged test
# lengths, float32, and lengths whose lane count is odd or prime.
_CHOOSER_SHAPES = [
    (4096, 4096, 2), (128, 128, 2), (256, 256, 2), (512, 512, 2),
    (96, 96, 4), (160, 160, 4), (4096, 4096, 4), (640, 640, 2),
    (1408, 1408, 2), (512, 4096, 2), (16384, 16384, 2)]


@pytest.mark.parametrize("s_q,s_k,itemsize", _CHOOSER_SHAPES)
def test_chosen_blocks(s_q, s_k, itemsize):
    hp = 128
    blocks = _choose_blocks(s_q, s_k, hp, itemsize)
    # padded no further than the 128 x 128 blocks of before
    assert blocks.sqp == -(-s_q // 128) * 128
    assert blocks.skp == -(-s_k // 128) * 128
    for bq, bk in (blocks.by_q, blocks.by_kv):
        assert bq % 128 == 0 and bk % 128 == 0
        assert blocks.sqp % bq == 0 and blocks.skp % bk == 0
    (bq, bk), (bq2, bk2) = blocks.by_q, blocks.by_kv
    fits = (_vmem_bytes(bq, bk, blocks.skp, hp, itemsize) <= _VMEM_BUDGET
            and _vmem_bytes(bk2, bq2, blocks.sqp, hp,
                            itemsize) <= _VMEM_BUDGET)
    if s_q <= 4096:
        assert fits
    else:       # whole-length K/V alone is over the budget: smallest tiles
        assert blocks.by_q == blocks.by_kv == (128, 128)


def test_chosen_blocks_at_the_cells_shapes():
    """One tile for a prompt padded to 128, 256 or 512; the train
    cell's S 4,096 takes what measured fastest there (PERF.md §6, PR
    26) in all three kernels."""
    for s in (128, 256, 512):
        assert _choose_blocks(s, s, 128, 2).by_q == (s, s)
    train = _choose_blocks(4096, 4096, 128, 2)
    assert train.by_q == train.by_kv == (512, 512)


def test_named_blocks_are_taken_as_given():
    blocks = _blocks_for(160, 160, 128, 4, 64, 64)
    assert blocks == ((64, 64), (64, 64), 192, 192)
    blocks = _blocks_for(4096, 4096, 128, 2, 128, None)
    assert blocks == ((128, 512), (128, 512), 4096, 4096)


def test_flash_backward_never_materializes_s2():
    """Training memory stays flat in S: no intermediate in the whole
    fwd+bwd program has an S×S (seq × seq) shape — the measured proxy
    for the blockwise backward's O(S) memory on any backend."""
    s = 512
    q, k, v = _qkv(b=1, s=s, n=1, h=32)

    def loss(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, True, None, 128, 128, True) ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    def all_avals(jxp, acc):
        for eqn in jxp.eqns:
            for var in eqn.outvars:
                acc.append(var.aval)
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    all_avals(sub.jaxpr, acc)
                if isinstance(sub, (list, tuple)):
                    for item in sub:
                        if hasattr(item, "jaxpr"):
                            all_avals(item.jaxpr, acc)
        return acc

    for aval in all_avals(jaxpr.jaxpr, []):
        shape = getattr(aval, "shape", ())
        assert sum(1 for d in shape if d == s) < 2, \
            f"S×S intermediate found: {shape}"


def _sp_mesh(sp):
    devs = jax.devices()[:8]
    spec = MeshSpec.auto(8, sp=sp)
    return make_mesh(spec, devs)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
@pytest.mark.parametrize("causal", [True, False])
def test_sequence_parallel_matches_dense(impl, causal):
    mesh = _sp_mesh(sp=4)
    q, k, v = _qkv(b=2, s=256, n=4, h=32)
    shard = NamedSharding(mesh, P(("dp", "fsdp"), "sp", "tp", None))
    qs, ks, vs = (jax.device_put(x, shard) for x in (q, k, v))
    attn = make_attention_fn(mesh, impl=impl, causal=causal)
    out = jax.jit(attn)(qs, ks, vs)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_gradients():
    mesh = _sp_mesh(sp=4)
    q, k, v = _qkv(b=2, s=128, n=4, h=32)
    shard = NamedSharding(mesh, P(("dp", "fsdp"), "sp", "tp", None))
    qs, ks, vs = (jax.device_put(x, shard) for x in (q, k, v))
    attn = make_attention_fn(mesh, impl="ring", causal=True)

    g1 = jax.jit(jax.grad(lambda q, k, v: jnp.sum(attn(q, k, v) ** 2),
                          argnums=(0, 1, 2)))(qs, ks, vs)
    g2 = jax.grad(
        lambda q, k, v: jnp.sum(mha_reference(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_ring_with_tp_axis():
    # heads sharded over tp while sequence shards over sp
    devs = jax.devices()[:8]
    mesh = make_mesh(MeshSpec.auto(8, tp=2, sp=4), devs)
    q, k, v = _qkv(b=2, s=128, n=4, h=32)
    shard = NamedSharding(mesh, P(("dp", "fsdp"), "sp", "tp", None))
    qs, ks, vs = (jax.device_put(x, shard) for x in (q, k, v))
    attn = make_attention_fn(mesh, impl="ring", causal=True)
    out = jax.jit(attn)(qs, ks, vs)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_moe_expert_parallel_matches_local():
    """EP dispatch over the mesh == same routing computed on one shard
    (high capacity so nothing drops)."""
    import jax
    from ray_tpu.ops.moe import moe_mlp_shard, make_moe_fn

    rng = np.random.RandomState(0)
    T, D, F, E, K = 64, 16, 32, 4, 2
    h = jnp.asarray(rng.randn(T, D), jnp.float32)
    router = jnp.asarray(rng.randn(D, E) * 0.1, jnp.float32)
    wi = jnp.asarray(rng.randn(E, D, F) * 0.1, jnp.float32)
    wg = jnp.asarray(rng.randn(E, D, F) * 0.1, jnp.float32)
    wo = jnp.asarray(rng.randn(E, F, D) * 0.1, jnp.float32)

    local = moe_mlp_shard(h, router, wi, wg, wo, axis_name=None,
                          n_experts=E, top_k=K, capacity_factor=float(E))

    mesh = make_mesh(MeshSpec.auto(4), jax.devices()[:4])
    moe_fn, ep = make_moe_fn(mesh, n_experts=E, top_k=K,
                             capacity_factor=float(E))
    assert ep == 4
    with mesh:
        dist = jax.jit(moe_fn)(h, router, wi, wg, wo)
    np.testing.assert_allclose(np.asarray(dist), np.asarray(local),
                               atol=1e-5, rtol=1e-5)
