"""The reader of `train_remat_kept_pct`: the program's
`train.remat_plan` record gives the share, no record gives `None`."""

import json
import os

import pytest

from benchmark import program_spans as ps
from benchmark import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
METRIC = "train_remat_kept_pct"


def read(monkeypatch, spans):
    monkeypatch.setattr(ps, "recorded", lambda: spans)
    return run.read_layer_metric(
        os.path.join(ROOT, "benchmark", "layer_metrics"), METRIC, {})


def plan(at, recompute, layers=3, forward=1_000, **more):
    return ps.Span("train.remat_plan", at, at, None, None, 1, 1, dict(
        layers=layers, recompute_flops=recompute,
        layer_forward_flops=forward, **more))


@pytest.mark.parametrize("recompute,share", [
    (0, 100.0), (750, 75.0), (2_520, 16.0), (3_000, 0.0)])
def test_a_record_gives_its_share(monkeypatch, recompute, share):
    other = ps.Span("train.report", 5, 9, None, None, 1, 1, {"seq": 1})
    assert read(monkeypatch, [other, plan(1, recompute, kept_bytes=7)]) \
        == pytest.approx(share)


def test_the_last_traced_step_counts(monkeypatch):
    assert read(monkeypatch, [plan(1, 3_000), plan(2, 0)]) == 100.0


@pytest.mark.parametrize("spans", [None, [], [
    ps.Span("train.report", 5, 9, None, None, 1, 1, {"seq": 1})]])
def test_no_record_gives_none(monkeypatch, spans):
    """The parent has no such record, and an older one no recorder."""
    assert read(monkeypatch, spans) is None


def test_the_manifest_names_the_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = manifest["per_layer"][-1]
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "model step",
        "moves": "train_tokens_per_s",
        "workloads": ["train_mistral7b_l3_s4096"]}
    assert METRIC in [m["name"] for m in run.metrics_of(
        manifest, "per_layer", "train_mistral7b_l3_s4096")]


def test_the_programs_own_record_is_read(monkeypatch):
    """A step traced here leaves the record in this process's ring,
    where the benchmark reads it after the window: on the CPU no memory
    figure is known, every layer is recomputed, and the share is the
    down projection's."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import (
        TransformerConfig, init_state, make_optimizer, make_train_step)
    from ray_tpu.util import tracing
    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=64, dtype=jnp.float32, remat=True)
    tx = make_optimizer(lr=1e-2, total_steps=50)
    state = jax.eval_shape(lambda k: init_state(k, cfg, tx),
                           jax.random.PRNGKey(0))
    tracing.clear()
    make_train_step(cfg, tx).lower(
        state, {"tokens": jax.ShapeDtypeStruct((4, 64), jnp.int32)})
    # as many later spans as a 50 s window records, and the ring's size
    for seq in range(2_000):
        tracing.record("train.report", seq, seq + 1, seq=seq)
    assert len(tracing.spans()) < tracing.RING_SPANS
    value = run.read_layer_metric(
        os.path.join(ROOT, "benchmark", "layer_metrics"), METRIC, {})
    down, forward = 2 * 64 * 128, 2 * 64 * (
        (4 + 2 * 2) * 16 + 4 * 16 + 3 * 128) + 2 * 2 * 4 * 16 * 32
    assert value == pytest.approx(100.0 * down / forward)
