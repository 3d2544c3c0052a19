"""The plain reference against `models/transformer.py` at a tiny size,
both in float32, where they have to agree closely; and the control,
which has to stand apart."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check
from benchmark.drivers import train_loop
from benchmark.reference import mistral as ref

CONFIG = {"architecture": "mistral", "hidden_size": 64,
          "intermediate_size": 160, "num_attention_heads": 4,
          "num_key_value_heads": 2, "num_hidden_layers": 2,
          "vocab_size": 256, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
          "torch_dtype": "float32"}
OPT = {"lr": 3e-4, "weight_decay": 0.1, "warmup_steps": 0,
       "total_steps": 10000, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
       "clip": 1.0}
SZ = ref.Sizes.from_config(CONFIG)


@pytest.fixture(scope="module")
def weights():
    return jax.jit(lambda k: ref.make_weights(k, SZ))(ref.seed_key(3))


def test_weights_are_a_function_of_the_seed_leaf_by_leaf(weights):
    again = ref.make_weights(ref.seed_key(3), SZ)
    other = ref.make_weights(ref.seed_key(2**31 + 3), SZ)
    for a, b, c in zip(*(jax.tree.leaves(t) for t in (weights, again, other))):
        # to the last digit or two: jit fuses the scaling another way
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-8)
        assert a.ndim == 1 or not np.allclose(a, c, atol=1e-3)
    norms = np.asarray(ref.leaf_norms(SZ, weights), np.float64)
    assert np.all(ref.change_norms(SZ, weights, ref.seed_key(3))
                  <= 1e-6 * norms)
    table = ref.leaf_table(SZ)
    assert sum(int(np.prod(s)) for _p, s, _k in table) == sum(
        x.size for x in jax.tree.leaves(weights))


def test_forward_and_loss_agree_with_the_program(weights):
    from ray_tpu.models import forward, loss_fn
    cfg = train_loop.program_config(CONFIG, 48)
    cfg = cfg.__class__(**{**cfg.__dict__, "use_flash": False})
    tokens = ref.make_tokens(3, 0, 2, 48, SZ.vocab)
    with jax.default_matmul_precision("highest"):
        want = forward(weights, jnp.asarray(tokens), cfg)
        want_loss = loss_fn(weights, {"tokens": jnp.asarray(tokens)}, cfg)
    last = np.array([47, 20], np.int32)
    got = ref.logits_at(weights, tokens, last, SZ)
    np.testing.assert_allclose(got, want[np.arange(2), last], atol=2e-5)
    assert float(ref.loss(weights, tokens, SZ)) == pytest.approx(
        float(want_loss), rel=1e-6)
    # padding behind a prompt cannot reach it
    padded = np.concatenate([tokens, np.zeros((2, 16), np.int32)], axis=1)
    np.testing.assert_allclose(ref.logits_at(weights, padded, last, SZ), got,
                               atol=2e-5)


def test_three_steps_follow_the_programs_optimizer():
    """`Program` in float32 against the reference: losses, the first
    gradient as Adam got it, and the three steps' move, leaf by leaf."""
    job = {"config": CONFIG, "seed": 11,
           "traffic": {"batch": 2, "seq": 32, "optimizer": OPT}}
    with jax.default_matmul_precision("highest"):
        program = train_loop.Program(job)
        got = program.first_steps()
    want = train_loop.reference_readings(ref, SZ, OPT, 11, 2, 32)
    numbers = check.train_numbers(got, want)
    assert max(numbers.values()) < 2e-4, numbers
    assert np.all(np.asarray(want["change_norms"]) > 0)


def test_control_and_planted_faults_stand_apart():
    want = train_loop.reference_readings(ref, SZ, OPT, 11, 2, 32)
    for variant in ({"mode": "int8"}, {"mode": "tp_partial"},
                    {"keep_rows": 0.5}):
        numbers = check.train_numbers(train_loop.reference_readings(
            ref, SZ, OPT, 11, 2, 32, **variant), want)
        assert max(numbers.values()) > 5e-3, (variant, numbers)
    unchanged = dict(want, change_norms=np.zeros_like(want["change_norms"]))
    assert check.train_numbers(unchanged, want)["change_gap"] == 1.0


def test_learning_rate_is_optax_warmup_cosine():
    import optax
    for warm in (0, 5):
        opt = dict(OPT, warmup_steps=warm, total_steps=50)
        sched = optax.warmup_cosine_decay_schedule(0.0, opt["lr"], warm, 50)
        for count in (0, 1, 4, 5, 20, 49, 60):
            assert float(ref.learning_rate(opt, count)) == pytest.approx(
                float(sched(count)), rel=1e-5, abs=1e-12)


def test_judge_holds_each_number_to_its_own_limit():
    ok = check.judge({"a": 0.1, "b": 0.5}, {"a": 0.2}, 10, 0)
    assert ok["correct"] and ok["checks"]["b"]["limit"] is None
    assert not check.judge({"a": 0.3}, {"a": 0.2}, 10, 0)["correct"]
    assert not check.judge({"a": float("nan")}, {"a": 0.2}, 10, 0)["correct"]
    assert not check.judge({"a": 0.1}, {"a": 0.2}, 10, 1)["correct"]
    assert not check.judge({"a": 0.1}, {"a": 0.2}, 0, 0)["correct"]
    with pytest.raises(KeyError):
        check.judge({"a": 0.1}, {"zz": 0.2}, 10, 0)
