"""The six `setup` readers (`benchmark/setup_spans.py` and their files
under `layer_metrics/`), on hand-made spans and no jax."""

import json
import os

import pytest

from benchmark import program_spans as ps
from benchmark import run, setup_spans

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
METRICS = ("setup_before_init_s", "setup_runtime_s", "setup_trace_lower_s",
           "setup_compile_s", "setup_compile_miss_s", "window_compiles")
CELLS = ["train_mistral7b_l3_s4096", "serve_mistral7b_l12_short",
         "serve_trinity_large_l5_ep8_long", "serve_mistral7b_l12_long",
         "serve_minicpm_sala_l8_long"]
S = 1_000_000_000
MAIN, PROXY = 200, 100
SERVE = {"window_s": 10.0, "late_ms": [0.0, 0.0]}
TRAIN = {"tokens_per_step": 4096}


def span(name, start_s, end_s, request=None, pid=MAIN, **counts):
    return ps.Span(name, int(start_s * S), int(end_s * S), request, None,
                   pid, 1, counts or None)


def read(monkeypatch, metric, spans, facts):
    monkeypatch.setattr(ps, "recorded", lambda: spans)
    return run.read_layer_metric(
        os.path.join(ROOT, "benchmark", "layer_metrics"), metric,
        {"facts": facts})


def every(monkeypatch, spans, facts):
    return {m: read(monkeypatch, m, spans, facts) for m in METRICS}


def build(name, at, trace=0.4, lower=0.1, compile_=1.0, hit=1):
    """One program's three spans, one after the other from `at`."""
    return [span("jax.trace", at, at + trace, name),
            span("jax.lower", at + trace, at + trace + lower, name),
            span("jax.compile", at + trace + lower,
                 at + trace + lower + compile_, name, cache_hit=hit)]


def serve_run(extra=()):
    """A serve process: up at 12 s, two programs, a warm-up request,
    the window's two requests at 40 and 45 s, the reference after."""
    return sorted([
        span("process.boot", 0.0, 0.3),
        span("process.boot", 13.0, 13.1, pid=PROXY),
        span("runtime.init", 12.0, 14.0),
        span("serve.start", 14.0, 15.5),
        *build("make_weights", 16.0, compile_=0.5, hit=0),
        *build("answer", 20.0, trace=2.0, lower=1.0, compile_=4.0),
        span("serve.request", 30.0, 30.5, "warm", pid=PROXY, status=200),
        span("serve.request", 40.0, 40.2, "a", pid=PROXY, status=200),
        span("serve.request", 45.0, 45.3, "b", pid=PROXY, status=200),
        *build("reference", 50.0, compile_=9.0, hit=0),
        *extra], key=lambda s: s.start_ns)


def test_a_serve_run_split_into_its_parts(monkeypatch):
    got = every(monkeypatch, serve_run(), SERVE)
    assert got == {
        "setup_before_init_s": pytest.approx(12.0),
        "setup_runtime_s": pytest.approx(2.0 + 1.5),
        "setup_trace_lower_s": pytest.approx(0.5 + 3.0),
        "setup_compile_s": pytest.approx(0.5 + 4.0),
        "setup_compile_miss_s": pytest.approx(0.5),
        "window_compiles": 0.0}


def test_nested_trace_intervals_count_once(monkeypatch):
    inner = [span("jax.trace", 20.1, 20.4, "rope"),
             span("jax.trace", 20.5, 21.9, "layer"),
             span("jax.trace", 20.6, 20.9, "rope")]
    assert read(monkeypatch, "setup_trace_lower_s", serve_run(inner),
                SERVE) == pytest.approx(0.5 + 3.0)


def test_jax_inside_the_runtime_spans_is_not_the_runtimes(monkeypatch):
    """A program built while `runtime.init` is open is counted under
    its own metrics and taken out of `setup_runtime_s`."""
    inside = build("probe", 12.5, trace=0.1, lower=0.1, compile_=0.3)
    got = every(monkeypatch, serve_run(inside), SERVE)
    assert got["setup_runtime_s"] == pytest.approx(3.5 - 0.5)
    assert got["setup_trace_lower_s"] == pytest.approx(3.5 + 0.2)
    assert got["setup_compile_s"] == pytest.approx(4.5 + 0.3)
    # whatever its thread: the parts share no second of the timeline
    elsewhere = [s._replace(thread=9) for s in inside]
    assert every(monkeypatch, serve_run(elsewhere), SERVE) == got


def test_a_compile_inside_the_window_is_counted_and_one_after_is_not(
        monkeypatch):
    late = build("answer", 42.0, trace=0.1, lower=0.1, compile_=2.0, hit=0)
    got = every(monkeypatch, serve_run(late), SERVE)
    assert got["window_compiles"] == 1.0
    # nor does it, or the reference's after the window, count as set-up
    assert got["setup_compile_s"] == pytest.approx(4.5)
    assert got["setup_compile_miss_s"] == pytest.approx(0.5)
    assert got["setup_trace_lower_s"] == pytest.approx(3.5)


def test_another_process_spans_are_not_the_chip_owners(monkeypatch):
    other = build("in_worker", 17.0, compile_=3.0, hit=0)
    other = [s._replace(pid=PROXY) for s in other]
    assert every(monkeypatch, serve_run(other), SERVE) == every(
        monkeypatch, serve_run(), SERVE)


def test_the_train_window_ends_before_the_outcomes_report(monkeypatch):
    spans = [
        span("process.boot", 0.0, 0.2),
        span("runtime.init", 10.0, 11.0),
        *build("make_state", 12.0, compile_=2.0),
        *build("step", 15.0, trace=3.0, lower=1.0, compile_=5.0, hit=0),
        span("train.report", 25.0, 25.002, seq=1),
        span("train.report", 25.2, 25.202, seq=2),
        span("train.report", 25.4, 25.402, seq=3),
        *build("gradient", 26.0, compile_=20.0, hit=0),    # the reference
        span("train.report", 60.0, 60.01, seq=4)]          # the outcome
    assert every(monkeypatch, spans, TRAIN) == {
        "setup_before_init_s": pytest.approx(10.0),
        "setup_runtime_s": pytest.approx(1.0),
        "setup_trace_lower_s": pytest.approx(0.5 + 4.0),
        "setup_compile_s": pytest.approx(2.0 + 5.0),
        "setup_compile_miss_s": pytest.approx(5.0),
        "window_compiles": 0.0}
    spans.insert(-3, span("jax.compile", 25.25, 25.3, "step", cache_hit=0))
    assert read(monkeypatch, "window_compiles", spans, TRAIN) == 1.0


def train_run(extra=()):
    """A train process: three steps of set-up that end at 24 s, the
    window's first step, its reports from 25 s on, the reference and
    the outcome's report."""
    return sorted([
        span("process.boot", 0.0, 0.2),
        span("runtime.init", 10.0, 11.0),
        *build("step", 15.0, trace=3.0, lower=1.0, compile_=5.0, hit=0),
        span("train.report", 25.0, 25.002, seq=1),
        span("train.report", 26.0, 26.002, seq=2),
        span("train.report", 27.0, 27.002, seq=3),
        *build("gradient", 28.0, compile_=20.0, hit=0),
        span("train.report", 60.0, 60.01, seq=4),
        *extra], key=lambda s: s.start_ns)


def test_the_train_windows_first_step_reads_as_set_up(monkeypatch):
    """The blind spot of the train cell (PERF.md section 7): the driver
    reports after a step, so the window's first step lies before its
    first `train.report`, and a program built in that step is counted
    with set-up and not as a compile inside the window. From the
    second step on it is seen."""
    plain = every(monkeypatch, train_run(), TRAIN)
    assert plain["window_compiles"] == 0.0
    assert plain["setup_compile_s"] == pytest.approx(5.0)
    first_step = [span("jax.compile", 24.2, 24.9, "step", cache_hit=0)]
    got = every(monkeypatch, train_run(first_step), TRAIN)
    assert got["window_compiles"] == 0.0
    assert got["setup_compile_s"] == pytest.approx(5.0 + 0.7)
    assert got["setup_compile_miss_s"] == pytest.approx(5.0 + 0.7)
    second_step = [span("jax.compile", 25.2, 25.9, "step", cache_hit=0)]
    got = every(monkeypatch, train_run(second_step), TRAIN)
    assert got == dict(plain, window_compiles=1.0)


def test_the_spans_say_which_kind_of_cell_it_is(monkeypatch):
    """Not the driver's facts: a serve cell's facts with other keys
    beside them, and a train cell's with none, read the same."""
    more = dict(SERVE, tokens_per_step=4096, answered=2)
    assert every(monkeypatch, serve_run(), more) == every(
        monkeypatch, serve_run(), SERVE)
    assert every(monkeypatch, train_run(), {}) == every(
        monkeypatch, train_run(), TRAIN)


@pytest.mark.parametrize("spans", [None, [], [
    span("serve.request", 40.0, 40.2, "a", pid=PROXY, status=200),
    span("serve.request", 45.0, 45.3, "b", pid=PROXY, status=200),
    span("train.report", 25.0, 25.002, seq=1)]])
@pytest.mark.parametrize("metric", METRICS)
def test_a_ring_without_the_spans_reads_none(monkeypatch, metric, spans):
    assert read(monkeypatch, metric, spans, SERVE) is None
    assert read(monkeypatch, metric, spans, TRAIN) is None


def test_each_reading_needs_only_its_own_spans(monkeypatch):
    """No `/proc`: no `process.boot`. No listener: no `jax.*`. No
    window found: nothing is before or inside it."""
    run_ = serve_run()
    no_boot = [s for s in run_ if s.name != "process.boot"]
    got = every(monkeypatch, no_boot, SERVE)
    assert got["setup_before_init_s"] is None
    assert got["setup_runtime_s"] == pytest.approx(3.5)
    assert got["setup_compile_s"] == pytest.approx(4.5)
    no_jax = [s for s in run_ if not s.name.startswith("jax.")]
    got = every(monkeypatch, no_jax, SERVE)
    assert got["setup_before_init_s"] == pytest.approx(12.0)
    assert got["setup_runtime_s"] == pytest.approx(3.5)
    assert [got[m] for m in METRICS[2:]] == [None] * 4
    no_window = [s for s in run_ if s.name != "serve.request"]
    got = every(monkeypatch, no_window, SERVE)
    assert got["setup_runtime_s"] == pytest.approx(3.5)
    assert [got[m] for m in METRICS[2:]] == [None] * 4


def test_the_manifest_names_the_six_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = {e["name"]: e for e in manifest["per_layer"]}
    units = dict.fromkeys(METRICS[:5], ("s", "program_span"))
    units["window_compiles"] = ("compiles", "program_counter")
    for name in METRICS:
        assert entries[name] == {
            "name": name, "unit": units[name][0], "better": "lower",
            "source": units[name][1], "layer": "setup", "moves": "setup_s",
            "workloads": CELLS}, name
        assert len(entries[name]) == 7
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
    assert set(setup_spans.split({}, None)) == set(METRICS)
    # run.py asks a reader for exactly the cells its entry lists
    for cell in CELLS:
        reported = {m["name"] for m in run.metrics_of(
            manifest, "per_layer", cell)}
        assert set(METRICS) <= reported
    assert not set(METRICS) & {m["name"] for m in run.metrics_of(
        manifest, "per_layer", "serve_evabyte_l16_long")}
