"""``transformer.rope`` against an independent statement of the rotation
(complex multiplication of the pairs, float64 numpy), and its gradient
against the form it replaced (strided slices and a stack), kept here as
the reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.transformer import rope

THETA = 10000.0


def _positions(batch, seq):
    """Rows that start elsewhere than 0 and differ from one another."""
    return (np.arange(seq)[None, :] * (1 + np.arange(batch))[:, None]
            + 7 + 100 * np.arange(batch)[:, None]).astype(np.int32)


def _rotation(x, turn):
    """``(x[2i] + i x[2i+1]) * turn[i]`` in float64; ``turn [B, S, Hd/2]``."""
    x = np.asarray(x, np.float64)
    z = (x[..., 0::2] + 1j * x[..., 1::2]) * turn[:, :, None, :]
    return np.stack([z.real, z.imag], axis=-1).reshape(x.shape)


def _turn(positions, head_dim, theta):
    """``exp(i pos theta^(-2i/hd))`` in float64."""
    freqs = theta ** (-np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    return np.exp(1j * positions[..., None].astype(np.float64) * freqs)


def _sliced_rope(x, positions, theta):
    """The form before PR 36."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _operand(dtype, head_dim, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, 24, 3, head_dim),
                          jnp.float32)
    return x.astype(dtype), _positions(2, 24)


@pytest.mark.parametrize("head_dim", [16, 64, 128])
def test_float32_is_the_rotation_to_a_rounding(head_dim):
    """A product that rounded its operand to bfloat16 would miss by
    1e-2: the float32 rotation must stand within 1e-6 of the float64
    one wherever the angle itself is exact in float32."""
    x, positions = _operand(jnp.float32, head_dim)
    out = rope(x, jnp.asarray(positions), THETA)
    assert out.dtype == jnp.float32 and out.shape == x.shape
    # position x frequency in float32 carries the angle's own rounding
    # (3e-5 at angles near 300): hold the arithmetic alone to 1e-6 by
    # turning with float32's own angles ...
    freqs = 1.0 / (THETA ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                             / head_dim))
    angles = np.asarray(jnp.asarray(positions)[..., None].astype(jnp.float32)
                        * freqs)
    turn = (np.asarray(jnp.cos(angles), np.float64)
            + 1j * np.asarray(jnp.sin(angles), np.float64))
    want = _rotation(x, turn)
    assert np.max(np.abs(np.asarray(out, np.float64) - want)
                  / np.maximum(np.abs(want), 1.0)) <= 1e-6
    # ... and the whole statement in float64 to the angle's rounding
    np.testing.assert_allclose(
        np.asarray(out, np.float64),
        _rotation(x, _turn(positions, head_dim, THETA)), atol=2e-4)


@pytest.mark.parametrize("head_dim", [16, 64, 128])
def test_bfloat16_is_the_rotation_rounded_once(head_dim):
    x, positions = _operand(jnp.bfloat16, head_dim)
    out = rope(x, jnp.asarray(positions), THETA)
    assert out.dtype == jnp.bfloat16 and out.shape == x.shape
    want = _rotation(x.astype(jnp.float32), _turn(positions, head_dim, THETA))
    # one rounding to 8 bits of the float32 sum, plus the angle's own
    bound = 2.0 ** -8 * np.maximum(np.abs(want), 1.0) + 2e-4
    assert np.all(np.abs(np.asarray(out.astype(jnp.float32), np.float64)
                         - want) <= bound)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("head_dim", [16, 64, 128])
def test_agrees_with_the_sliced_form(dtype, head_dim):
    """Element by element the same sum; the two differ by how XLA
    contracts ``a c - b s`` and ``a c + (-b) s``: one float32 rounding
    before the cast."""
    x, positions = _operand(dtype, head_dim, seed=1)
    positions = jnp.asarray(positions)
    new = rope(x, positions, THETA).astype(jnp.float32)
    old = _sliced_rope(x, positions, THETA).astype(jnp.float32)
    step = 2.0 ** -7 if dtype == jnp.bfloat16 else 2.0 ** -22
    assert float(jnp.max(jnp.abs(new - old) / jnp.maximum(jnp.abs(old), 1.0))
                 ) <= step


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("head_dim", [16, 128])
def test_gradient_is_the_sliced_forms(dtype, head_dim):
    x, positions = _operand(dtype, head_dim, seed=2)
    positions = jnp.asarray(positions)
    weights = jax.random.normal(jax.random.PRNGKey(3), x.shape, jnp.float32)

    def loss(fn):
        return lambda x: jnp.sum(fn(x, positions, THETA).astype(jnp.float32)
                                 * weights)

    new = jax.grad(loss(rope))(x)
    old = jax.grad(loss(_sliced_rope))(x)
    assert new.dtype == x.dtype
    step = 2.0 ** -7 if dtype == jnp.bfloat16 else 2.0 ** -22
    assert float(jnp.max(jnp.abs(new.astype(jnp.float32)
                                 - old.astype(jnp.float32))
                         / jnp.maximum(jnp.abs(old.astype(jnp.float32)), 1.0))
                 ) <= step


def test_the_swap_does_not_round_a_float32_operand():
    """Position 0 turns nothing, so all 24 bits of a float32 come back;
    and the product asks for the precision under which a TPU, whose
    default rounds a float32 operand to bfloat16, returns a +-1
    permutation exactly (the CPU is exact either way, so the request
    itself is read from the lowered text)."""
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 3, 2, 64), jnp.float32)
    still = rope(x, jnp.zeros((1, 3), jnp.int32), THETA)
    assert jnp.array_equal(still, x)
    text = jax.jit(rope, static_argnums=2).lower(
        x, jnp.zeros((1, 3), jnp.int32), THETA).as_text()
    products = [line for line in text.splitlines() if "dot_general" in line]
    assert len(products) == 1 and "HIGHEST" in products[0], products
