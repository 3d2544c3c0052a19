"""The reduction of the program's spans, on hand-made spans."""

import importlib.util
import os

import numpy as np
import pytest

from benchmark import program_spans as ps

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MS = 1_000_000
PROXY, DRIVER = 100, 200


def span(name, start_ms, end_ms, request=None, parent=None, pid=PROXY,
         thread=1, **counts):
    return ps.Span(name, int(start_ms * MS), int(end_ms * MS), request,
                   parent, pid, thread, counts or None)


def request(rid, at, *, wire=1.0, wait=0.0, forward=20.0, admission=0.0,
            reply_wait=0.8, ongoing=0):
    """One request's spans, `at` ms on the shared clock: 0.1 ms of
    parse, 0.3 of router, `wire + wait` to the replica, `admission`,
    `forward`, 0.2 of tail, `reply_wait`, then 0.5 of reply of which
    0.2 is the fetch and 0.2 the write."""
    t = at + 0.05
    out = [span(ps.PARSE, t, t + 0.1, rid)]
    t += 0.1
    out.append(span(ps.ASSIGN, t, t + 0.3, rid, inflight=ongoing, parked=0))
    t += 0.3 + wire + wait
    entered = t
    if admission:
        out.append(span(ps.ADMISSION, t, t + admission, rid, ps.REPLICA,
                        DRIVER, 7))
        t += admission
    out.append(span(ps.INVOKE, t, t + forward, rid, ps.REPLICA, DRIVER, 7))
    t += forward + 0.2
    out.append(span(ps.REPLICA, entered, t, rid, None, DRIVER, 7,
                    ongoing=ongoing))
    t += reply_wait
    out.append(span(ps.GET, t + 0.05, t + 0.25, rid, thread=2))
    out.append(span(ps.WRITE, t + 0.3, t + 0.5, rid))
    out.append(span(ps.REPLY, t, t + 0.5, rid, polled=1))
    out.append(span(ps.ROOT, at, t + 0.5, rid, status=200, bytes_in=600,
                    bytes_out=300))
    return out


def test_group_by_request_and_the_numbers_of_one():
    spans = request("a", 0.0, wait=3.0, admission=0.5, ongoing=2) \
        + request("b", 50.0)
    both = ps.by_request(spans)
    assert set(both) == {"a", "b"}
    a, b = both["a"], both["b"]
    assert ps.ingress_ms(a) == pytest.approx(0.1 + 0.5)
    assert ps.router_ms(a) == pytest.approx(0.3)
    assert ps.replica_wait_ms(a) == pytest.approx(1.0 + 3.0 + 0.5)
    assert ps.replica_wait_ms(b) == pytest.approx(1.0)
    assert ps.reply_wait_ms(a) == pytest.approx(0.8)
    assert ps.queue_depth(a) == 2.0 and ps.queue_depth(b) == 0.0


def test_self_time_takes_out_the_children_of_the_same_thread():
    spans = request("a", 0.0, admission=0.5)
    replica = next(s for s in spans if s.name == ps.REPLICA)
    # request less admission and invoke: the 0.2 ms tail
    assert ps.self_ms(replica, spans) == pytest.approx(0.2)
    # a span of another thread or process is no child
    stranger = span(ps.INVOKE, 2.0, 3.0, "z", ps.REPLICA, DRIVER, 8)
    assert ps.self_ms(replica, spans + [stranger]) == pytest.approx(0.2)
    report = span(ps.REPORT, 0.0, 5.0, seq=1)
    write = span("train.report.write", 0.5, 4.5, parent=ps.REPORT, bytes=90)
    assert ps.self_ms(report, [report, write]) == pytest.approx(1.0)


def test_a_request_without_a_span_it_needs_reads_none():
    spans = [s for s in request("a", 0.0)
             if s.name not in (ps.REPLICA, ps.PARSE)]
    (a,) = ps.by_request(spans).values()
    assert ps.replica_wait_ms(a) is None and ps.reply_wait_ms(a) is None
    assert ps.ingress_ms(a) is None and ps.queue_depth(a) is None
    assert ps.router_ms(a) == pytest.approx(0.3)
    assert ps.percentile([a], ps.reply_wait_ms, 50) is None
    assert ps.percentile([], ps.router_ms, 50) is None


def test_percentiles_over_requests():
    spans = []
    for i in range(10):
        spans += request(f"r{i}", 100.0 * i, wait=float(i), ongoing=i % 3)
    requests = ps.window_requests(spans, 10.0, 10)
    assert [r[ps.ROOT].request for r in requests] == [
        f"r{i}" for i in range(10)]
    waits = [1.0 + i for i in range(10)]
    assert ps.percentile(requests, ps.replica_wait_ms, 50) == pytest.approx(
        np.percentile(waits, 50))
    assert ps.percentile(requests, ps.replica_wait_ms, 90) == pytest.approx(
        np.percentile(waits, 90))
    assert ps.percentile(requests, ps.queue_depth, 90) == 2.0


def test_the_window_leaves_the_warm_up_out_by_time_and_count():
    spans = request("setup", -60_000.0) + request("warm", -30.0)
    for i in range(5):
        spans += request(f"r{i}", 200.0 * i)
    # a status route: a root with no request id
    spans.append(span(ps.ROOT, 500.0, 501.0, None, status=200))
    # by time alone the last warm-up request is still inside
    assert len(ps.window_requests(spans, 1.0, 99)) == 6
    kept = ps.window_requests(spans, 1.0, 5)
    assert [r[ps.ROOT].request for r in kept] == [f"r{i}" for i in range(5)]
    assert ps.window_requests([], 1.0, 5) == []
    assert ps.window_requests(None, 1.0, 5) == []


def test_align_finds_a_planted_offset():
    rng = np.random.default_rng(3)
    program = np.cumsum(rng.exponential(0.05, 400)) + 65_000.0
    planted = -64_990.123456
    inside = program[250:330] + planted + rng.uniform(2e-6, 9e-6, 80)
    offset, error = ps.align(inside, program)
    assert offset == pytest.approx(planted + 5.5e-6, abs=4e-6)
    assert error < 10e-6
    # the longer list may be the trace's: the offset changes its sign
    back, _ = ps.align(program, inside)
    assert back == pytest.approx(-offset, abs=1e-9)


def test_align_returns_none_on_jitter_or_too_few_events():
    rng = np.random.default_rng(4)
    program = np.cumsum(rng.exponential(0.05, 400))
    jittered = program[100:180] + 7.0 + rng.uniform(0, 1e-3, 80)
    assert ps.align(jittered, program) is None
    assert ps.align(jittered, program, max_error_s=2e-3) is not None
    assert ps.align(program[:2] + 7.0, program) is None
    # other events altogether: no shift agrees
    assert ps.align(np.cumsum(rng.exponential(0.05, 80)), program) is None


def hand_made_trace():
    """A traced window of 1 s, 10 s after the profile began. The
    program's clock reads 5,000 s more than the trace's. Three calls:
    the device runs 100 ms in each, 20 ms after `invoke` begins."""
    offset = -5_000.0
    window = (10.0, 11.0)
    spans, bench, ops = [], [], []
    for i, at in enumerate((10.1, 10.4, 10.8)):     # trace clock
        program_at = (at - offset) * 1e3            # ms, program clock
        spans += request(f"r{i}", program_at, wire=1.0, forward=130.0)
        invoke = next(s for s in spans
                      if s.name == ps.INVOKE and s.request == f"r{i}")
        begun = invoke.start_ns / 1e9 + offset
        bench.append(("replica_call", begun + 4e-6, 0.129))
        ops.append(("fusion.1", begun + 0.020, 0.100))
    bench.append(("trace_window", window[0], 1.0))
    ctx = {"trace": {"window": window, "spans": bench,
                     "inside": {0: ops, 1: []}}}
    return ctx, spans, offset


def test_idle_with_work_on_a_hand_made_trace():
    ctx, spans, planted = hand_made_trace()
    offset, error = ps.trace_offset(ctx, spans)
    assert offset == pytest.approx(planted + 4e-6, abs=1e-6)
    assert error < 1e-6
    # a request is open 132.95 ms (0.05 + 0.1 + 0.3 + 1 + 130 + 0.2 +
    # 0.8 + 0.5) and the device runs 100 ms of it
    per_request = 132.95 - 100.0
    assert ps.idle_with_work_pct(ctx, spans) == pytest.approx(
        100.0 * 3 * per_request / 1000.0, abs=0.01)
    # the device's idle share of the same window is far larger: the
    # rest is the traffic's doing
    assert ps.idle_with_work_pct(ctx, spans) < 100.0 * (1.0 - 0.3)


def test_idle_with_work_clips_to_the_window_and_merges_requests():
    ctx, spans, offset = hand_made_trace()
    # a request that came in before the window opened and one that is
    # open at the same time as another
    early = (9.95 - offset) * 1e3
    twin = (10.41 - offset) * 1e3
    more = spans + [span(ps.ROOT, early, early + 100.0, "early"),
                    span(ps.ROOT, twin, twin + 50.0, "twin")]
    base = ps.idle_with_work_pct(ctx, spans)
    # 50 ms of `early` lie inside the window and no device op runs
    # there; `twin` lies wholly inside an open request
    assert ps.idle_with_work_pct(ctx, more) == pytest.approx(
        base + 100.0 * 0.05, abs=0.01)


def test_idle_with_work_reads_none_without_an_anchor():
    ctx, spans, _ = hand_made_trace()
    assert ps.idle_with_work_pct(ctx, None) is None
    assert ps.idle_with_work_pct(ctx, []) is None
    no_invoke = [s for s in spans if s.name != ps.INVOKE]
    assert ps.idle_with_work_pct(ctx, no_invoke) is None


def test_overlap_seconds():
    a = [[0.0, 1.0], [2.0, 3.0]]
    b = [[0.5, 2.5], [2.75, 4.0]]
    assert ps.overlap_seconds(a, b) == pytest.approx(0.5 + 0.5 + 0.25)
    assert ps.overlap_seconds(a, []) == 0.0


def test_report_median():
    spans = [span(ps.REPORT, 300.0 * i, 300.0 * i + 4.0 + i, seq=i + 1)
             for i in range(5)]
    assert ps.report_ms_p50(spans) == pytest.approx(6.0)
    assert ps.report_ms_p50([]) is None and ps.report_ms_p50(None) is None


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_under_test",
        os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


SERVE_READERS = {
    "serve_ingress_ms_p50": 0.6, "serve_router_ms_p50": 0.3,
    "serve_replica_wait_ms_p50": 1.0, "serve_replica_wait_ms_p90": 1.0,
    "serve_queue_depth_p90": 0.0, "serve_reply_wait_ms_p50": 0.8}


@pytest.mark.parametrize("name", sorted(SERVE_READERS))
def test_serve_readers_read_the_recorder(monkeypatch, name):
    ctx, spans, _ = hand_made_trace()
    ctx["facts"] = {"window_s": 1.0, "late_ms": np.zeros(3)}
    monkeypatch.setattr(ps, "recorded", lambda: spans)
    assert reader(name)(ctx) == pytest.approx(SERVE_READERS[name], abs=1e-5)
    # a program without the recorder: nothing to read, nothing raised
    monkeypatch.setattr(ps, "recorded", lambda: None)
    assert reader(name)(ctx) is None


def test_idle_and_report_readers(monkeypatch):
    ctx, spans, _ = hand_made_trace()
    monkeypatch.setattr(ps, "recorded", lambda: spans)
    assert reader("serve_idle_with_work_pct")(ctx) == pytest.approx(
        ps.idle_with_work_pct(ctx, spans))
    assert reader("train_report_ms_p50")(ctx) is None   # no such span
    monkeypatch.setattr(ps, "recorded", lambda: [
        span(ps.REPORT, 0.0, 4.5, seq=1)])
    assert reader("train_report_ms_p50")(ctx) == pytest.approx(4.5)
    monkeypatch.setattr(ps, "recorded", lambda: None)
    assert reader("serve_idle_with_work_pct")(ctx) is None
    assert reader("train_report_ms_p50")(ctx) is None


def test_recorded_reads_the_programs_recorder():
    from ray_tpu.util import tracing
    tracing.clear()
    tracing.record("serve.request", 10, 20, "r", status=200)
    try:
        assert ps.recorded() == [ps.Span(
            "serve.request", 10, 20, "r", None, os.getpid(),
            ps.recorded()[0].thread, {"status": 200})]
    finally:
        tracing.clear()


def test_recorded_is_none_for_a_program_without_the_recorder(monkeypatch):
    from ray_tpu.util import tracing
    monkeypatch.delattr(tracing, "spans")
    assert ps.recorded() is None
