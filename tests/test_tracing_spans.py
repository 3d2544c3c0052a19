"""The span recorder of ``ray_tpu.util.tracing`` and the spans on the
serve request path and the train session (docs/tracing.md)."""

import contextlib
import json
import os
import signal
import threading
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu._private.config import get_config
from ray_tpu.util import tracing


@contextlib.contextmanager
def time_limit(seconds: int):
    """A test that starts a runtime answers for its own time."""
    def expired(_signum, _frame):
        raise TimeoutError(f"test passed its limit of {seconds} s")

    previous = signal.signal(signal.SIGALRM, expired)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, previous)


@pytest.fixture(autouse=True)
def _clean_recorder():
    tracing.clear()
    yield
    tracing.clear()


@pytest.fixture
def recorder_off():
    cfg = get_config()
    cfg.apply_system_config({"event_log_enabled": False})
    yield
    cfg.apply_system_config({"event_log_enabled": True})


def by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s.name, []).append(s)
    return out


# -- the recorder, no runtime ------------------------------------------------

def test_span_nesting_and_parent():
    with tracing.span("outer", "req-1", depth=3) as outer:
        with tracing.span("inner", "req-1"):
            time.sleep(0.001)
        outer.note(width=2)
    with tracing.span("later"):
        pass
    got = by_name(tracing.spans())
    outer, inner, later = got["outer"][0], got["inner"][0], got["later"][0]
    assert inner.parent == "outer" and outer.parent is None
    assert later.parent is None
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    assert inner.end_ns - inner.start_ns >= 1_000_000
    assert outer.counts == {"depth": 3, "width": 2}
    assert inner.counts is None and inner.request == "req-1"
    assert outer.pid == os.getpid()
    assert outer.thread == threading.get_ident()


def test_parent_is_per_thread():
    seen = {}

    def other():
        with tracing.span("in_thread"):
            pass
        seen["done"] = True

    with tracing.span("main_only"):
        t = threading.Thread(target=other)
        t.start()
        t.join()
    got = by_name(tracing.spans())
    assert seen and got["in_thread"][0].parent is None
    assert got["in_thread"][0].thread != got["main_only"][0].thread


def test_parent_is_per_asyncio_task():
    import asyncio

    async def request(name):
        with tracing.span(name):
            await asyncio.sleep(0.01)
            with tracing.span(name + ".child"):
                await asyncio.sleep(0)

    async def both():
        await asyncio.gather(request("a"), request("b"))

    asyncio.run(both())
    got = by_name(tracing.spans())
    assert got["a.child"][0].parent == "a"
    assert got["b.child"][0].parent == "b"
    assert got["a"][0].parent is None and got["b"][0].parent is None


def test_note_names_the_request_before_the_span_closes():
    with tracing.span("serve.router.assign") as span:
        span.note(request="abc", inflight=1)
    (got,) = tracing.spans()
    assert got.request == "abc" and got.counts == {"inflight": 1}


def test_record_takes_both_ends():
    a = time.perf_counter_ns()
    b = a + 5_000
    tracing.record("serve.request", a, b, "req-9", status=200, bytes_out=7)
    (got,) = tracing.spans()
    assert (got.name, got.start_ns, got.end_ns) == ("serve.request", a, b)
    assert got.request == "req-9"
    assert got.counts == {"status": 200, "bytes_out": 7}


def test_moe_route_record_counts_the_rows_a_forward_routed(recorder_off):
    """One `model.moe.route` record a forward, from the rows each held
    expert of each routed layer was given; nothing with the recorder
    off, nothing for a model without routed layers."""
    import numpy as np

    from ray_tpu.ops.moe import record_route, route_counts
    rows = np.array([[3, 0, 5], [1, 1, 2]])     # two layers, three held
    # (no layer of these shapes was traced here: the static worst case)
    assert route_counts(rows, tokens=16, top_k=2) == {
        "layers": 2, "rows_total": 64, "rows_held": 12, "rows_computed": 64,
        "load_max": 5, "load_mean": 2.0}
    record_route(rows, 16, 2, 10, 20)
    assert tracing.spans() == []
    get_config().apply_system_config({"event_log_enabled": True})
    record_route(np.zeros((0, 3), np.int32), 16, 2, 10, 20)
    assert tracing.spans() == []
    record_route(rows, 16, 2, 10, 20, "req-3")
    (got,) = tracing.spans()
    assert (got.name, got.start_ns, got.end_ns, got.request) == (
        "model.moe.route", 10, 20, "req-3")
    assert got.counts["rows_held"] == 12 and got.counts["load_max"] == 5
    assert got.counts["rows_computed"] == 64


def test_delta_plan_record_counts_a_traced_shape(recorder_off):
    """One `model.delta.plan` record for each shape `forward_with_stats`
    is traced at, from shapes alone, if the pattern holds a `kda` or an
    `mla` layer; nothing with the recorder off, nothing for a pattern
    without either."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import (LayerSpec, TransformerConfig,
                                forward_with_stats, init_params)
    from ray_tpu.models.transformer import KdaSizes, MlaSizes
    cfg = TransformerConfig(
        vocab_size=32, d_model=32, n_layers=3, n_heads=2, n_kv_heads=2,
        d_ff=64, head_dim=16, remat=False, dtype=jnp.float32,
        layers=(LayerSpec(mixer="kda", rope=False),) * 2
        + (LayerSpec(mixer="mla", rope=False),),
        kda=KdaSizes(conv=4, rank=8),
        mla=MlaSizes(kv_rank=8, nope=16, shared=8, value=16))
    shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))

    def trace(batch, seq):
        jax.eval_shape(lambda p, t: forward_with_stats(p, t, cfg), shapes,
                       jax.ShapeDtypeStruct((batch, seq), jnp.int32))

    trace(1, 100)
    assert tracing.spans() == []
    get_config().apply_system_config({"event_log_enabled": True})
    jax.eval_shape(
        lambda p, t: forward_with_stats(p, t, TransformerConfig(
            vocab_size=32, d_model=32, n_layers=1, n_heads=2, n_kv_heads=2,
            d_ff=64, remat=False)),
        jax.eval_shape(lambda k: init_params(k, TransformerConfig(
            vocab_size=32, d_model=32, n_layers=1, n_heads=2, n_kv_heads=2,
            d_ff=64)), jax.random.PRNGKey(0)),
        jax.ShapeDtypeStruct((1, 100), jnp.int32))
    assert tracing.spans() == []
    for batch, seq in ((1, 100), (2, 192)):
        trace(batch, seq)
    plans = [s.counts for s in tracing.spans()
             if s.name == "model.delta.plan"]
    assert [p["tokens"] for p in plans] == [100, 384]
    for (batch, seq), plan in zip(((1, 100), (2, 192)), plans):
        tokens = batch * seq
        assert plan == {
            "tokens": tokens, "kda_layers": 2, "mla_layers": 1, "chunk": 64,
            "chunks": -(-seq // 64),
            # a token and head: 7 x 16 x 16 on the state
            "kda_flops": 2 * tokens * 2 * 7 * 16 * 16,
            # causal pairs x heads x 2 x (24 lanes scored + 16 weighed)
            "mla_pair_flops": batch * seq * (seq + 1) // 2 * 2 * 2 * 40,
            # the state in float32 and three tails of three tokens
            "state_bytes": 2 * batch * (2 * 16 * 16 * 4 + 3 * 3 * 32 * 4),
            "latent_bytes": tokens * (8 + 8) * 4,
            "kv_bytes": tokens * 2 * 40 * 4}


def test_ring_is_bounded_and_drops_the_oldest():
    for i in range(tracing.RING_SPANS + 10):
        tracing.record("x", i, i + 1)
    got = tracing.spans()
    assert len(got) == tracing.RING_SPANS
    assert got[0].start_ns == 10


def test_recorder_off_is_a_shared_no_op(recorder_off):
    a = tracing.span("quiet", "r", n=1)
    b = tracing.span("quiet2")
    assert a is b is tracing.NO_SPAN
    with a as span:
        span.note(request="r2", n=2)
    tracing.record("quiet3", 1, 2)
    assert tracing.spans() == []
    assert not tracing.enabled()


def test_annotate_mirrors_only_where_jax_is_loaded(monkeypatch):
    import sys

    import jax  # noqa: F401 - the mirror needs it loaded
    assert type(tracing.annotate("task")).__name__ == "TraceAnnotation"
    with tracing.span("mirrored"):
        pass
    monkeypatch.delitem(sys.modules, "jax")
    monkeypatch.setattr(tracing, "_trace_annotation", None)
    assert isinstance(tracing.annotate("task"), contextlib.nullcontext)
    with tracing.span("plain"):
        pass
    assert {s.name for s in tracing.spans()} == {"mirrored", "plain"}


def test_drain_empties_the_ring_and_absorb_restores_it():
    tracing.record("w", 10, 20, "r", n=1)
    reply = tracing.drain()
    assert reply[0] == "spans" and reply[1] == os.getpid()
    assert tracing.spans() == []
    tracing.absorb(reply)
    (got,) = tracing.spans()
    assert got == tracing.Span("w", 10, 20, "r", None, os.getpid(),
                               threading.get_ident(), {"n": 1})


# -- with a runtime ----------------------------------------------------------

def test_worker_spans_are_readable_after_shutdown(tmp_path):
    with time_limit(120):
        ray_tpu.init(num_cpus=4, num_tpus=8, max_process_workers=2)
        try:
            @ray_tpu.remote
            def traced(i):
                from ray_tpu.util import tracing as t
                with t.span("in_worker", f"req-{i}", i=i):
                    return os.getpid()

            pids = set(ray_tpu.get([traced.remote(i) for i in range(4)]))
            with tracing.span("in_driver"):
                pass
        finally:
            ray_tpu.shutdown()
    got = by_name(tracing.spans())
    assert len(got["in_worker"]) == 4 and len(got["in_driver"]) == 1
    assert {s.pid for s in got["in_worker"]} == pids
    assert os.getpid() not in pids
    assert {s.request for s in got["in_worker"]} == {
        f"req-{i}" for i in range(4)}
    # no span a task: the annotation idiom records nothing in the ring
    # (each process worker's start and the runtime's are there since
    # the recorder covers set-up)
    assert set(got) == {"in_worker", "in_driver", "process.boot",
                        "runtime.init"}
    assert {s.pid for s in got["process.boot"]} >= pids

    # one exporter: tasks and spans in one Chrome trace, on the wall clock
    events = tracing.timeline(str(tmp_path / "tl.json"))
    with open(tmp_path / "tl.json") as f:
        assert len(json.load(f)) == len(events)
    cats = {e["cat"] for e in events}
    assert cats == {"task", "span"}
    span_events = [e for e in events if e["cat"] == "span"]
    # (a worker that ran no task still reports its `process.boot`)
    assert {e["pid"] for e in span_events
            if e["name"] in ("in_worker", "in_driver")} \
        == pids | {os.getpid()}
    assert all(abs(e["ts"] / 1e6 - time.time()) < 600 for e in span_events)
    assert {e["args"].get("request") for e in span_events
            if e["name"] == "in_worker"} == {f"req-{i}" for i in range(4)}


def test_collect_twice_gathers_each_span_once():
    with time_limit(120):
        ray_tpu.init(num_cpus=2, num_tpus=8, max_process_workers=1)
        try:
            @ray_tpu.remote
            def traced():
                from ray_tpu.util import tracing as t
                with t.span("once"):
                    return 1

            ray_tpu.get(traced.remote())
            first = [s for s in tracing.collect() if s.name == "once"]
            second = [s for s in tracing.collect() if s.name == "once"]
        finally:
            ray_tpu.shutdown()
    assert len(first) == len(second) == 1
    assert len([s for s in tracing.spans() if s.name == "once"]) == 1


SERVE_SPANS = ("serve.request", "serve.ingress.parse", "serve.router.assign",
               "serve.replica.request", "serve.replica.invoke",
               "serve.ingress.reply", "serve.ingress.get",
               "serve.ingress.write")


def test_one_request_id_from_ingress_to_reply():
    """One HTTP request through the worker-hosted async ingress to a
    replica on the driver's TPU lane: every span of the table under one
    id, from two processes, in order on the shared clock."""
    from ray_tpu import serve

    with time_limit(180):
        ray_tpu.init(num_cpus=4, num_tpus=8, max_process_workers=2)
        try:
            serve.start(http=True)

            @serve.deployment(ray_actor_options={"num_tpus": 1},
                              max_ongoing_requests=4)
            class Echo:
                def __call__(self, payload):
                    return {"got": payload, "pid": os.getpid()}

            serve.run(Echo.bind())
            host, port = serve.http_address()
            request = urllib.request.Request(
                f"http://{host}:{port}/Echo",
                data=json.dumps({"k": 1}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(request, timeout=60) as response:
                body = json.loads(response.read())
            with urllib.request.urlopen(
                    f"http://{host}:{port}/-/routes", timeout=30) as r:
                r.read()
        finally:
            serve.shutdown()
            ray_tpu.shutdown()
    assert body["got"] == {"k": 1} and body["pid"] == os.getpid()

    spans = tracing.spans()
    roots = [s for s in spans if s.name == "serve.request"]
    routed = [s for s in roots if s.request is not None]
    assert len(routed) == 1 and len(roots) == 2     # and the status route
    rid = routed[0].request
    mine = by_name(s for s in spans if s.request == rid)
    assert set(mine) == set(SERVE_SPANS) | {"serve.replica.admission"}
    assert all(len(v) == 1 for v in mine.values())
    one = {name: v[0] for name, v in mine.items()}

    # two processes, one clock
    proxy_pid = one["serve.request"].pid
    assert proxy_pid != os.getpid()
    for name in ("serve.ingress.parse", "serve.router.assign",
                 "serve.ingress.reply", "serve.ingress.get",
                 "serve.ingress.write"):
        assert one[name].pid == proxy_pid, name
    for name in ("serve.replica.request", "serve.replica.admission",
                 "serve.replica.invoke"):
        assert one[name].pid == os.getpid(), name
    order = ["serve.request", "serve.ingress.parse", "serve.router.assign",
             "serve.replica.request", "serve.replica.admission",
             "serve.replica.invoke", "serve.ingress.reply",
             "serve.ingress.get", "serve.ingress.write"]
    starts = [one[name].start_ns for name in order]
    assert starts == sorted(starts)
    root = one["serve.request"]
    assert all(root.start_ns <= s.start_ns and s.end_ns <= root.end_ns
               for s in one.values())
    # (the replica may begin before the submit's reply is back in the
    # proxy: the router's span need not have ended by then)
    assert one["serve.replica.request"].end_ns \
        <= one["serve.ingress.reply"].start_ns
    assert one["serve.ingress.write"].end_ns == root.end_ns \
        == one["serve.ingress.reply"].end_ns

    # the counts of the table
    assert root.counts["status"] == 200
    assert root.counts["bytes_in"] == len(json.dumps({"k": 1}))
    assert root.counts["bytes_out"] > 0
    assert one["serve.router.assign"].counts == {"inflight": 0, "parked": 0}
    assert one["serve.replica.request"].counts == {"ongoing": 0}
    assert one["serve.ingress.reply"].counts["polled"] >= 1
    assert one["serve.replica.invoke"].parent == "serve.replica.request"
    status_root = next(s for s in roots if s.request is None)
    assert status_root.counts["status"] == 200


def test_driver_side_batched_plane_spans():
    """The in-driver ingress parks a request as a promise: the router's
    span ends there, and one `serve.router.flush` a dispatch carries
    `items`."""
    from ray_tpu import serve

    with time_limit(180):
        ray_tpu.init(num_cpus=4, num_tpus=8, max_process_workers=2)
        try:
            serve.start(http=True, proxy_location="driver")

            @serve.deployment
            class Echo:
                def __call__(self, payload):
                    return {"got": payload}

            serve.run(Echo.bind())
            host, port = serve.http_address()
            request = urllib.request.Request(
                f"http://{host}:{port}/Echo",
                data=json.dumps({"k": 2}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(request, timeout=60) as response:
                assert json.loads(response.read()) == {"got": {"k": 2}}
        finally:
            serve.shutdown()
            ray_tpu.shutdown()
    got = by_name(tracing.spans())
    (root,) = got["serve.request"]
    promise = root.request
    assert promise is not None
    for name in ("serve.ingress.parse", "serve.router.assign",
                 "serve.ingress.reply", "serve.ingress.get",
                 "serve.ingress.write"):
        assert [s.request for s in got[name]] == [promise], name
    assert "polled" not in (got["serve.ingress.reply"][0].counts or {})
    (flush,) = got["serve.router.flush"]
    assert flush.counts == {"items": 1}
    (batch,) = got["serve.replica.request"]
    assert batch.counts == {"ongoing": 0, "items": 1}
    assert batch.request == flush.request != promise
    assert got["serve.replica.invoke"][0].counts == {"items": 1}


def test_train_report_spans_under_jax_trainer():
    from ray_tpu import train
    from ray_tpu.train import JaxTrainer, ScalingConfig

    def loop(config):
        for step in range(config["steps"]):
            train.report({"step": step})

    with time_limit(180):
        ray_tpu.init(num_cpus=4, num_tpus=8, max_process_workers=2)
        try:
            result = JaxTrainer(
                loop, train_loop_config={"steps": 3},
                scaling_config=ScalingConfig(num_workers=1,
                                             use_tpu=True)).fit()
        finally:
            ray_tpu.shutdown()
    assert result.error is None and len(result.metrics_history) == 3
    got = by_name(tracing.spans())
    reports, writes = got["train.report"], got["train.report.write"]
    assert [s.counts["seq"] for s in reports] == [1, 2, 3]
    assert len(writes) == 3
    for report, write in zip(reports, writes):
        assert write.parent == "train.report"
        assert report.start_ns <= write.start_ns
        assert write.end_ns <= report.end_ns
        assert write.counts["bytes"] > 0
        assert write.pid == report.pid and write.thread == report.thread
    assert "train.report.ack_wait" not in got
    drained = got["train.drain_reports"]
    assert sum(s.counts["files"] for s in drained) == 3


def test_ack_wait_span_only_under_sync_reports(tmp_path):
    from ray_tpu.train import _session

    ctx = _session.TrainContext(report_dir=str(tmp_path),
                                trial_dir=str(tmp_path), sync_reports=True)
    _session.init_session(ctx)
    try:
        name = "report_0000_00000001.pkl.ack"
        threading.Timer(0.05, lambda: open(
            os.path.join(str(tmp_path), name), "w").close()).start()
        _session.report({"x": 1})
    finally:
        _session.shutdown_session()
    got = by_name(tracing.spans())
    (wait,) = got["train.report.ack_wait"]
    assert wait.parent == "train.report"
    assert wait.end_ns - wait.start_ns >= 20_000_000
    assert got["train.report.write"][0].parent == "train.report"


# -- set-up: the process, the runtime, jax's builds ---------------------------

def test_process_boot_runs_from_the_os_start_to_the_recorders_import():
    tracing._note_process()             # what the import did
    (boot,) = tracing.spans()
    assert boot.name == "process.boot" and boot.pid == os.getpid()
    assert boot.request is None and boot.counts is None
    # it ends at the clock pair the process noted, and began before it
    assert boot.end_ns == tracing._anchors[os.getpid()][1]
    assert boot.start_ns < boot.end_ns <= time.perf_counter_ns()
    # no earlier than the host's boot, on a clock that may have slept
    slept = (time.clock_gettime_ns(time.CLOCK_BOOTTIME)
             - time.clock_gettime_ns(time.CLOCK_MONOTONIC))
    assert boot.start_ns + slept >= 0
    # the OS's own reading of this process's age agrees to a tick or two
    with open("/proc/uptime") as f:
        uptime_ns = int(float(f.read().split()[0]) * 1e9)
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    age_ns = uptime_ns - ticks * 1_000_000_000 // os.sysconf("SC_CLK_TCK")
    assert abs((time.perf_counter_ns() - boot.start_ns) - age_ns) < 50e6


def test_process_boot_waits_for_the_rings_first_reader(recorder_off):
    """Importing the recorder builds no `Config`, and a flag set after
    the import (`init(_system_config=...)`) still holds for the span
    of the process's start: it goes into the ring when the ring is
    first read."""
    import subprocess
    import sys

    probe = ("from ray_tpu.util import tracing; "
             "from ray_tpu._private import config; "
             "assert config._global_config is None; "
             "assert [s.name for s in tracing.spans()] == ['process.boot']")
    with time_limit(60):
        subprocess.run([sys.executable, "-c", probe], check=True)
    tracing._note_process()
    assert tracing.spans() == [] and tracing.drain()[3] == []
    get_config().apply_system_config({"event_log_enabled": True})
    assert [s.name for s in tracing.spans()] == ["process.boot"]
    assert len(tracing.spans()) == 1            # once


# the child of this fork takes no lock: it reads its ring and leaves
@pytest.mark.filterwarnings("ignore:.*fork.*")
def test_a_forked_child_has_its_own_process_boot():
    tracing._note_process()
    (mine,) = tracing.spans()
    tracing.record("before_fork", 1, 2)
    read_end, write_end = os.pipe()
    with time_limit(60):
        child = os.fork()
        if child == 0:                  # the child: report and leave
            try:
                os.write(write_end, json.dumps(tracing.spans()).encode())
            finally:
                os._exit(0)
        os.close(write_end)
        with os.fdopen(read_end) as f:
            rows = json.load(f)
        assert os.waitpid(child, 0)[1] == 0
    (boot,) = [tracing.Span(*row) for row in rows]  # the ring began anew
    assert boot.name == "process.boot" and boot.pid == child
    # a tick (10 ms) is as fine as the OS says when it forked
    assert boot.start_ns > mine.start_ns
    assert mine.end_ns - 20_000_000 <= boot.start_ns <= boot.end_ns


def test_runtime_init_and_serve_start_appear_once_each():
    from ray_tpu import serve

    with time_limit(120):
        ray_tpu.init(num_cpus=2, num_tpus=8, max_process_workers=1)
        try:
            assert ray_tpu.init() is not None   # the runtime is up: no span
            serve.start(http=True, proxy_location="driver")
        finally:
            serve.shutdown()
            ray_tpu.shutdown()
    got = by_name(tracing.spans())
    (init,), (start,) = got["runtime.init"], got["serve.start"]
    assert init.counts is None and start.counts is None
    assert init.pid == start.pid == os.getpid()
    assert init.parent is None and start.parent is None
    assert init.start_ns < init.end_ns <= start.start_ns < start.end_ns


def _built(name):
    return [s for s in tracing.spans()
            if s.name.startswith("jax.") and s.request == name]


def test_a_jitted_function_leaves_trace_lower_and_compile(monkeypatch,
                                                          tmp_path):
    """One span of each for one program, under the function's name, in
    that order; nothing for a warm call; one listener however often the
    cache is configured."""
    import jax
    import jax.numpy as jnp

    from ray_tpu._private.compile_cache import configure_compile_cache

    # placed from outside, so that nothing is set in code
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    configure_compile_cache()
    configure_compile_cache()

    @jax.jit
    def halved(x):
        return x / 2

    def scaled_and_shifted(x):
        return halved(x) * 3 + 1

    program = jax.jit(scaled_and_shifted)
    began = time.perf_counter_ns()
    with time_limit(60):
        jax.block_until_ready(program(jnp.arange(16.0)))
        ended = time.perf_counter_ns()
        first = _built("scaled_and_shifted")
        jax.block_until_ready(program(jnp.arange(16.0)))
    assert _built("scaled_and_shifted") == first        # warm: no event
    assert [s.name for s in first] == ["jax.trace", "jax.lower",
                                       "jax.compile"]
    trace, lower, compile_ = first
    assert began <= trace.start_ns <= trace.end_ns <= lower.end_ns \
        <= compile_.end_ns <= ended
    # jax's durations are wall-clock floats laid back from the event's
    # close: they abut to well under a millisecond
    assert lower.start_ns >= trace.end_ns - 1_000_000
    assert compile_.start_ns >= lower.end_ns - 1_000_000
    assert compile_.counts == {"cache_hit": 0}
    assert trace.counts is None and lower.counts is None
    assert {s.pid for s in first} == {os.getpid()}
    assert {s.thread for s in first} == {threading.get_ident()}
    # the inner jit's trace lies inside the outer one's, as jax gives it
    (inner,) = [s for s in _built("halved") if s.name == "jax.trace"]
    assert trace.start_ns <= inner.start_ns <= inner.end_ns <= trace.end_ns


def test_jax_builds_leave_nothing_with_the_recorder_off(recorder_off,
                                                        monkeypatch,
                                                        tmp_path):
    import jax
    import jax.numpy as jnp

    from ray_tpu._private.compile_cache import configure_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    configure_compile_cache()
    with time_limit(60):
        jax.block_until_ready(jax.jit(lambda x: x * 5 - 2)(jnp.arange(8.0)))
    tracing._note_process()
    assert tracing.spans() == []


_CACHE_PROBE = """
import json
import jax
import jax.numpy as jnp
from ray_tpu._private.compile_cache import configure_compile_cache
from ray_tpu.util import tracing

configure_compile_cache()


def cached_probe(x):
    return jnp.tanh(x) @ x.T

jax.block_until_ready(jax.jit(cached_probe)(jnp.ones((64, 64))))
print(json.dumps([[s.name, s.counts] for s in tracing.spans()
                  if s.request == "cached_probe"]))
"""


def test_a_second_process_reads_cache_hit_where_the_first_read_none(
        tmp_path):
    """The persistent cache in a directory of the test's own, every
    program kept whatever it took to compile."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    hits = []
    for _ in range(2):
        done = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                              cwd=repo, capture_output=True, text=True,
                              timeout=120)
        assert done.returncode == 0, done.stderr[-2000:]
        spans = json.loads(done.stdout.strip().splitlines()[-1])
        assert [name for name, _counts in spans] == [
            "jax.trace", "jax.lower", "jax.compile"]
        hits.append(spans[-1][1]["cache_hit"])
    assert hits == [0, 1]
    assert os.listdir(tmp_path)
