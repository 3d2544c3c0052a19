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
    _compiler_params,
    _vmem_bytes,
    repeat_kv,
)
from ray_tpu.parallel.mesh import MeshSpec, make_mesh


def _qkv(b=2, s=256, n=4, h=64, dtype=jnp.float32, seed=0, g=None):
    """q at ``n`` heads, k and v at ``g`` KV heads (None: ``n``)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, s, heads, h), dtype)
                 for k, heads in zip(ks, (n, g or n, g or n)))


def _dense(q, k, v, **kw):
    """The dense reference given the repeated K and V: its gradients
    for k and v are the sums over a group."""
    return mha_reference(q, *repeat_kv(k, v, q.shape[2]), **kw)


# (query heads, KV heads): N / G of 1, 4 and 6
_HEADS = [(2, 2), (4, 1), (12, 2)]


@pytest.mark.parametrize("n,g", [(4, 4), (4, 1), (6, 1)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_reference(causal, n, g):
    q, k, v = _qkv(n=n, g=g)
    ref = _dense(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal, None, 128, 128, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("n,g", [(4, 4), (4, 1), (6, 1)])
def test_flash_gradients(n, g):
    q, k, v = _qkv(s=128, n=n, g=g)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None, 64, 64, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_dense(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("n,g", [(2, 2), (4, 1)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("s", [96, 160])   # not divisible by block 64
def test_flash_gradients_ragged_seq(causal, s, n, g):
    """Blockwise backward stays exact when seq % block != 0 (the
    clamped-tail de-dup mask on both dq and dkv loops)."""
    q, k, v = _qkv(s=s, n=n, g=g)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal, None, 64, 64, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_dense(q, k, v, causal=causal) ** 2)

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


@pytest.mark.parametrize("n,g", _HEADS)
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("s,blocks", [
    (256, (128, 128)), (256, (None, None)), (160, (None, None)),
    (160, (64, 64)), (256, (128, 64)), (256, (64, 128))])
def test_flash_bf16_matches_reference(causal, s, blocks, n, g):
    """bf16 inputs go to the matmuls as bf16 (P and dS rounded to bf16,
    float32 accumulation): forward and gradients stay inside the
    tolerances chip_smoke.py holds them to on the chip, against the
    dense reference on the same inputs cast up; K and V at their KV
    heads, dk and dv the dense reference's sums over a group."""
    q, k, v = _qkv(b=1, s=s, n=n, g=g, h=64, dtype=jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(7), q.shape, jnp.float32)
    got = _out_and_grads(
        lambda q, k, v: flash_attention(q, k, v, causal, None, *blocks,
                                        True), q, k, v, w)
    assert all(x.dtype == jnp.bfloat16 for x in got)
    want = _out_and_grads(
        lambda q, k, v: _dense(q, k, v, causal=causal),
        *(x.astype(jnp.float32) for x in (q, k, v)), w)
    assert _rel_err(got[0], want[0]) < _FWD_TOL
    for got_grad, r in zip(got[1:], want[1:]):
        assert _rel_err(got_grad, r) < _BWD_TOL


@pytest.mark.parametrize("n,g", [(2, 2), (6, 1)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("s", [96, 160, 256, 1024])
def test_flash_float32_with_chosen_blocks(causal, s, n, g):
    """float32 inputs keep float32 products at the blocks the chooser
    gives (block_q = block_k = None): the float32 tolerances hold."""
    q, k, v = _qkv(b=1, s=s, n=n, g=g, h=64)
    w = jax.random.normal(jax.random.PRNGKey(7), q.shape, jnp.float32)
    got = _out_and_grads(
        lambda q, k, v: flash_attention(q, k, v, causal, interpret=True),
        q, k, v, w)
    want = _out_and_grads(
        lambda q, k, v: _dense(q, k, v, causal=causal), q, k, v, w)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               atol=2e-5, rtol=2e-5)
    for got_grad, r in zip(got[1:], want[1:]):
        np.testing.assert_allclose(np.asarray(got_grad), np.asarray(r),
                                   atol=2e-4, rtol=2e-4)


# window under, equal to and over the length; a ragged length; blocks
# shorter and longer than the window, so that tiles are skipped at both
# ends of the loop in all three kernels
@pytest.mark.parametrize("n,g", _HEADS)
@pytest.mark.parametrize("s,window,blocks", [
    (256, 64, (64, 64)), (256, 100, (64, 64)), (256, 100, (128, 64)),
    (256, 100, (64, 128)), (256, 256, (64, 64)), (256, 1000, (64, 64)),
    (160, 48, (64, 64)), (160, 1, (64, 64)), (512, 130, (None, None))])
def test_flash_window_matches_reference(s, window, blocks, n, g):
    q, k, v = _qkv(b=1, s=s, n=n, g=g, h=64)
    w = jax.random.normal(jax.random.PRNGKey(7), q.shape, jnp.float32)
    got = _out_and_grads(
        lambda q, k, v: flash_attention(q, k, v, True, None, *blocks,
                                        True, window), q, k, v, w)
    want = _out_and_grads(
        lambda q, k, v: _dense(q, k, v, window=window), q, k, v, w)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               atol=2e-5, rtol=2e-5)
    for got_grad, r in zip(got[1:], want[1:]):
        np.testing.assert_allclose(np.asarray(got_grad), np.asarray(r),
                                   atol=2e-4, rtol=2e-4)
    if window >= s:     # a window that hides nothing is causal attention
        full = flash_attention(q, k, v, True, None, *blocks, True)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(full))


def test_window_reference_by_hand():
    """Query i sees keys i-window+1 .. i: with one-hot values the output
    row is the softmax's weights, zero outside that band."""
    s, window = 8, 3
    q = jnp.zeros((1, s, 1, s))
    out = np.asarray(mha_reference(q, q, jnp.eye(s)[None, :, None, :],
                                   window=window))[0, :, 0]
    for i in range(s):
        seen = range(max(0, i - window + 1), i + 1)
        want = np.zeros(s)
        want[list(seen)] = 1.0 / len(seen)
        np.testing.assert_allclose(out[i], want, atol=1e-6)


def test_window_needs_causal():
    q, k, v = _qkv(b=1, s=128, n=1, h=64)
    with pytest.raises(ValueError, match="sliding window"):
        flash_attention(q, k, v, False, None, 64, 64, True, 32)
    with pytest.raises(ValueError, match="sliding window"):
        mha_reference(q, k, v, causal=False, window=32)


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
    (1408, 1408, 2), (512, 4096, 2), (6144, 6144, 2), (8192, 8192, 2),
    (16384, 16384, 2)]


@pytest.mark.parametrize("s_q,s_k,itemsize", _CHOOSER_SHAPES)
def test_chosen_blocks(s_q, s_k, itemsize):
    hp = 128
    bq, bk, sqp, skp = _choose_blocks(s_q, s_k)
    # padded no further than the 128 x 128 blocks of before
    assert sqp == -(-s_q // 128) * 128 and skp == -(-s_k // 128) * 128
    # the largest blocks that divide the padded lengths, up to 512, in
    # every kernel; what they need past the default scope is asked for
    for n, block in ((sqp, bq), (skp, bk)):
        assert block % 128 == 0 and n % block == 0
        assert not any(n % b == 0 for b in range(block + 128, 513, 128))
    need = _vmem_bytes(bq, bk, skp, hp, itemsize)
    params = _compiler_params(bq, bk, skp, hp, itemsize)
    if need <= _VMEM_BUDGET:
        assert params == {}
    else:
        assert params["compiler_params"].vmem_limit_bytes > need
    assert (need <= _VMEM_BUDGET) == (s_k * itemsize <= 12288)


@pytest.mark.parametrize("heads", [1, 4, 6])
def test_forward_vmem_counts_the_groups_heads(heads):
    """The forward serves a KV group a step: its query and result
    blocks and its accumulators are ``heads`` wide, and what it asks
    Mosaic for covers them (16,384 keys whole, the Trinity cell's)."""
    one = _vmem_bytes(512, 512, 16384, 128, 2)
    need = _vmem_bytes(512, 512, 16384, 128, 2, heads)
    blocked = 4 * 2 * 512 * 128 * 2 + 2 * 512 * 128 * 4
    assert need - one == (heads - 1) * blocked
    # q and o double-buffered and the float32 accumulators alone
    assert need > 2 * 2 * 16384 * 128 * 2 + heads * 512 * 128 * (4 * 2 + 4)
    limit = _compiler_params(512, 512, 16384, 128, 2,
                             heads)["compiler_params"].vmem_limit_bytes
    assert need < limit < 100 * 2 ** 20


def test_chosen_blocks_at_the_cells_shapes():
    """One tile for a prompt padded to 128, 256 or 512; the train
    cell's S 4,096 takes what measured fastest there (PERF.md §6, PR
    26) in all three kernels."""
    for s in (128, 256, 512):
        assert _choose_blocks(s, s)[:2] == (s, s)
    assert _choose_blocks(4096, 4096)[:2] == (512, 512)


def test_named_blocks_are_taken_as_given():
    assert _blocks_for(160, 160, 64, 64) == (64, 64, 192, 192)
    assert _blocks_for(4096, 4096, 128, None) == (128, 512, 4096, 4096)


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


@pytest.mark.parametrize("g", [4, 2])   # K and V at the query heads, or fewer
@pytest.mark.parametrize("impl", ["ring", "ulysses"])
@pytest.mark.parametrize("causal", [True, False])
def test_sequence_parallel_matches_dense(impl, causal, g):
    mesh = _sp_mesh(sp=4)
    q, k, v = _qkv(b=2, s=256, n=4, h=32, g=g)
    shard = NamedSharding(mesh, P(("dp", "fsdp"), "sp", "tp", None))
    qs, ks, vs = (jax.device_put(x, shard) for x in (q, k, v))
    attn = make_attention_fn(mesh, impl=impl, causal=causal)
    out = jax.jit(attn)(qs, ks, vs)
    ref = _dense(q, k, v, causal=causal)
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


@pytest.mark.parametrize("g", [4, 2, 1])
def test_flash_under_a_mesh_takes_kv_heads(g):
    """Under a mesh the kernel runs on each device's head shard: a
    shard holds whole groups where tp divides the KV heads (4, 2), and
    K and V are repeated first where it does not (1)."""
    mesh = make_mesh(MeshSpec.auto(8, tp=2), jax.devices()[:8])
    q, k, v = _qkv(b=4, s=128, n=4, h=32, g=g)
    shard = NamedSharding(mesh, P(("dp", "fsdp"), None, "tp", None))
    qs = jax.device_put(q, shard)
    ks, vs = ((jax.device_put(x, shard) if g % 2 == 0 else x)
              for x in (k, v))
    out = jax.jit(make_attention_fn(mesh, impl="flash"))(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_dense(q, k, v, causal=True)),
                               atol=2e-5, rtol=2e-5)


def test_moe_expert_parallel_matches_local():
    """The experts divided over a mesh axis, each shard computing its
    own experts' part for all the tokens and the parts summed, equal
    the same layer holding every expert on one shard: no pair dropped,
    none counted twice."""
    import jax
    from ray_tpu.ops.moe import make_moe_fn, routed_experts

    rng = np.random.RandomState(0)
    T, D, F, E, K = 64, 16, 128, 8, 2
    h = jnp.asarray(rng.randn(T, D), jnp.float32)
    router = jnp.asarray(rng.randn(D, E) * 0.5, jnp.float32)
    bias = jnp.asarray(rng.randn(E) * 0.2, jnp.float32)
    wi = jnp.asarray(rng.randn(E, D, F) * 0.1, jnp.float32)
    wg = jnp.asarray(rng.randn(E, D, F) * 0.1, jnp.float32)
    wo = jnp.asarray(rng.randn(E, F, D) * 0.1, jnp.float32)

    local, rows = routed_experts(h, router, bias, wg, wi, wo, held=(0, E),
                                 top_k=K, route_scale=2.0)
    assert int(rows.sum()) == T * K

    mesh = make_mesh(MeshSpec(tp=4), jax.devices()[:4])
    moe_fn = make_moe_fn(mesh, top_k=K, route_scale=2.0)
    with mesh:
        dist, dist_rows = jax.jit(moe_fn)(h, router, bias, wg, wi, wo)
    np.testing.assert_allclose(np.asarray(dist), np.asarray(local),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(dist_rows), np.asarray(rows))
