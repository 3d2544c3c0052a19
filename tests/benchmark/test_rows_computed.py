"""The reader of `moe_rows_computed_share`: the program's
`model.moe.route` records give the share of the (token, expert) rows
the routed layers passed over; a record without the count (the parent's)
and no record give `None`."""

import json
import os

import numpy as np
import pytest

from benchmark import moe_route
from benchmark import program_spans as ps
from benchmark import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
METRIC = "moe_rows_computed_share"
CELL = "serve_trinity_large_l5_ep8_long"


def read(monkeypatch, spans, offered=3):
    monkeypatch.setattr(ps, "recorded", lambda: spans)
    return run.read_layer_metric(
        os.path.join(ROOT, "benchmark", "layer_metrics"), METRIC,
        {"facts": {"late_ms": np.zeros(offered)}})


def route(computed=None, total=1000, **more):
    counts = dict(layers=4, rows_total=total, rows_held=total // 8,
                  load_max=9, load_mean=2.0, **more)
    if computed is not None:
        counts["rows_computed"] = computed
    return ps.Span(moe_route.ROUTE, 0, 1, None, None, 1, 1, counts)


@pytest.mark.parametrize("computed,share", [
    ((250, 250, 250), 25.0),        # every layer on the lowest rung
    ((250, 312, 1000), 31.2),       # the median forward, not the mean
    ((1000, 1000, 1000), 100.0)])   # the ladder never engaged
def test_the_windows_records_give_the_median_share(monkeypatch, computed,
                                                   share):
    spans = [route(1000)]           # warm-up: before the window's three
    spans += [route(c) for c in computed]
    spans.insert(2, ps.Span("serve.request", 0, 1, None, None, 1, 1,
                            {"bytes_in": 1}))
    assert read(monkeypatch, spans) == pytest.approx(share)


@pytest.mark.parametrize("spans", [
    None, [], [route(), route(), route()],
    [ps.Span("serve.request", 0, 1, None, None, 1, 1, {"status": 200})],
    [route(0, total=0)]])
def test_a_program_without_the_count_gives_none(monkeypatch, spans):
    """The parent's record holds no `rows_computed`; an older program
    has no record, or no recorder."""
    assert read(monkeypatch, spans) is None


def test_the_manifest_names_the_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == METRIC]
    assert entry == {
        "name": METRIC, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "experts",
        "moves": "serve_ttft_p50_ms", "workloads": [CELL]}
    assert METRIC in [m["name"] for m in run.metrics_of(
        manifest, "per_layer", CELL)]


def test_the_programs_own_record_is_read(monkeypatch):
    """A routed layer traced here and the record made from its rows, as
    the cell's replica makes it: 1 of 8 experts held, 128 pairs in row
    tiles of 8, the lowest rung (32 of 128 rows) in both layers."""
    import jax.numpy as jnp

    from ray_tpu.ops import moe
    from ray_tpu.util import tracing
    rng = np.random.RandomState(0)
    args = [jnp.asarray(a, jnp.float32) for a in (
        rng.randn(64, 16), rng.randn(16, 8), np.zeros(8),
        rng.randn(1, 16, 32), rng.randn(1, 16, 32), rng.randn(1, 32, 16))]
    args[2] = args[2].at[2].add(-20.0)         # no row for the held one
    _out, rows = moe.routed_experts(
        *args, held=(2, 1), top_k=2, route_scale=1.0, tile_m=8,
        interpret=True)
    tracing.clear()
    for _forward in range(3):
        moe.record_route(np.stack([rows, rows]), 64, 2, 10, 20)
    monkeypatch.undo()
    assert run.read_layer_metric(
        os.path.join(ROOT, "benchmark", "layer_metrics"), METRIC,
        {"facts": {"late_ms": np.zeros(3)}}) == pytest.approx(25.0)
