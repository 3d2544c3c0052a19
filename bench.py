"""Headline benchmark: tasks scheduled/sec on the north-star workload —
10k nodes x 1M pending tasks (BASELINE.json:2,5).

Compares the TPU scheduling kernel (vmapped class-fill, see
ray_tpu/_private/scheduler/tpu_policy.py) against the CPU
HybridSchedulingPolicy baseline, end to end: raw pending-queue demand
matrix -> scheduling-class grouping -> device kernel -> per-task node
assignments.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""

import json
import os
import sys
import time

import numpy as np

N_NODES = int(os.environ.get("BENCH_NODES", 10_000))
N_TASKS = int(os.environ.get("BENCH_TASKS", 1_000_000))
N_CLASSES = 8
N_RES = 4  # CPU, TPU, memory, custom
BASELINE_SAMPLE = int(os.environ.get("BENCH_BASELINE_TASKS", 8192))


def build_cluster_arrays(rng):
    total = np.zeros((N_NODES, N_RES), np.float32)
    total[:, 0] = rng.choice([256, 256, 384], N_NODES)           # CPU
    total[:, 1] = rng.choice([0, 4, 8, 8], N_NODES)              # TPU
    total[:, 2] = rng.choice([256, 512, 1024], N_NODES)          # memory GB
    total[:, 3] = rng.choice([0, 0, 0, 1], N_NODES)              # custom
    used_frac = rng.uniform(0.0, 0.15, (N_NODES, 1)).astype(np.float32)
    avail = np.maximum(total * (1.0 - used_frac), 0.0)
    alive = np.ones(N_NODES, bool)
    return avail, total, alive


def build_demand_classes(rng):
    demands = np.zeros((N_CLASSES, N_RES), np.float32)
    demands[:, 0] = rng.choice([1, 1, 1, 2], N_CLASSES)          # CPU
    demands[:4, 1] = rng.choice([0, 1], 4)                       # some want TPU
    demands[:, 2] = rng.choice([1, 2, 4], N_CLASSES)             # memory
    class_of_task = rng.randint(0, N_CLASSES, N_TASKS).astype(np.int32)
    counts = np.bincount(class_of_task, minlength=N_CLASSES).astype(np.int32)
    return demands, counts, class_of_task


def bench_tpu_kernel(avail, total, alive, demands, counts):
    from ray_tpu._private.scheduler.tpu_policy import TpuSchedulingPolicy

    pol = TpuSchedulingPolicy()
    prefs = np.full(N_CLASSES, -1, np.int32)
    placed_per_class = np.zeros(N_CLASSES, np.int64)
    fence = {}

    def run(avail_in):
        t0 = time.perf_counter()
        ds = pol.schedule_dense(
            avail_in.copy(), total, alive, demands, counts, prefs)
        # Expand to per-task node assignments (host, vectorized);
        # the residual pass's placements (order2/take2) count too.
        assignments = []
        for k in range(N_CLASSES):
            placed_per_class[k] = 0
            for order_k, take_k in ((ds.order[k], ds.take_sorted[k]),
                                    (ds.order2[k], ds.take2[k])):
                nz = take_k > 0
                placed_per_class[k] += int(take_k.sum())
                if nz.any():
                    assignments.append(np.repeat(order_k[nz],
                                                 take_k[nz]))
        out = np.concatenate(assignments) if assignments else np.empty(0)
        dt = time.perf_counter() - t0
        # Fence honesty split (docs/scheduler.md): "cluster cannot
        # fit" (per-class bound from node totals) vs "kernel failed
        # to place" (admitted-but-unplaced — should be 0).
        fence["fenced"] = int(ds.fenced[:N_CLASSES].sum())
        fence["admitted"] = int(ds.admitted[:N_CLASSES].sum())
        return out, dt

    run(avail)                      # warmup (compile)
    times = []
    for _ in range(5):
        out, dt = run(avail)
        times.append(dt)
    n_scheduled = len(out)
    best = min(times)
    return n_scheduled / best, n_scheduled, times, placed_per_class, fence


def bench_cpu_baseline(avail, total, alive, demands):
    """CPU HybridSchedulingPolicy baseline: the native C++ per-task
    policy (the shape of the reference's raylet hot loop — a feasibility
    scan + top-k score per pending task) on a sample, extrapolated to a
    rate. No native library, no baseline: the run fails rather than
    compare against the pure-Python policy."""
    import ctypes as ct
    from ray_tpu._private.native_loader import scheduler_lib
    lib = scheduler_lib()
    if lib is None:
        raise RuntimeError("native scheduler library failed to build")
    n = BASELINE_SAMPLE
    dem = np.ascontiguousarray(
        demands[np.arange(n) % N_CLASSES], np.float32)
    preferred = np.full(n, -1, np.int32)
    out_nodes = np.empty(n, np.int32)
    out_inf = np.empty(n, np.uint8)
    a = avail.copy()
    alive8 = alive.astype(np.uint8)
    f32p, u8p, i32p = (ct.POINTER(ct.c_float), ct.POINTER(ct.c_uint8),
                      ct.POINTER(ct.c_int32))
    t0 = time.perf_counter()
    lib.rtpu_hybrid_schedule(
        a.ctypes.data_as(f32p), total.ctypes.data_as(f32p),
        alive8.ctypes.data_as(u8p), N_NODES, N_RES,
        dem.ctypes.data_as(f32p), preferred.ctypes.data_as(i32p), n,
        ct.c_float(0.5), 1, ct.c_float(0.1), 42,
        out_nodes.ctypes.data_as(i32p), out_inf.ctypes.data_as(u8p))
    dt = time.perf_counter() - t0
    scheduled = int((out_nodes >= 0).sum())
    return max(scheduled, 1) / dt


def bench_p99_light_load(avail, total, alive, demands):
    """Light-load p99: submit→node-assignment latency for a SINGLE
    pending task through the production policy seam
    (AdaptiveSchedulingPolicy — routes shallow queues to the native CPU
    scan, so the TPU build has no device round-trip floor at low load),
    vs the bare native single-task scan (the reference raylet's
    per-task unit of work).
    """
    from ray_tpu._private.ids import NodeID
    from ray_tpu._private.scheduler.policy import SchedulingRequest
    from ray_tpu._private.scheduler.resources import (
        ClusterResourceManager, NodeResources)
    from ray_tpu._private.scheduler.tpu_policy import (
        AdaptiveSchedulingPolicy)

    names = ["CPU", "TPU", "memory", "custom"]
    cluster = ClusterResourceManager()
    for i in range(N_NODES):
        res = NodeResources(
            total={n: float(v) for n, v in zip(names, total[i]) if v > 0},
            available={n: float(avail[i][j]) for j, n in enumerate(names)
                       if total[i][j] > 0},
        )
        cluster.add_or_update_node(NodeID.from_random(), res)

    pol = AdaptiveSchedulingPolicy()
    reqs = [SchedulingRequest(demand={
        n: float(v) for n, v in zip(names, demands[k]) if v > 0})
        for k in range(N_CLASSES)]
    pol.schedule(cluster, reqs[0])   # warm the matrix cache

    # Baseline setup: the bare native scan for one task.
    import ctypes as ct
    from ray_tpu._private.native_loader import scheduler_lib
    lib = scheduler_lib()
    if lib is None:
        raise RuntimeError("native scheduler library failed to build")
    f32p = ct.POINTER(ct.c_float)
    u8p = ct.POINTER(ct.c_uint8)
    i32p = ct.POINTER(ct.c_int32)
    dem1 = np.ascontiguousarray(demands[:1], np.float32)
    pref1 = np.full(1, -1, np.int32)
    out1 = np.empty(1, np.int32)
    inf1 = np.empty(1, np.uint8)
    alive8 = alive.astype(np.uint8)
    a = avail.copy()

    def native(i):
        dem1[0] = demands[i % N_CLASSES]
        t0 = time.perf_counter()
        lib.rtpu_hybrid_schedule(
            a.ctypes.data_as(f32p), total.ctypes.data_as(f32p),
            alive8.ctypes.data_as(u8p), N_NODES, N_RES,
            dem1.ctypes.data_as(f32p), pref1.ctypes.data_as(i32p), 1,
            ct.c_float(0.5), 1, ct.c_float(0.1), 42,
            out1.ctypes.data_as(i32p), inf1.ctypes.data_as(u8p))
        return time.perf_counter() - t0

    # Interleaved best-of-3 sampling: on a small shared machine the
    # raw p99 is a lottery over multi-ms OS stalls landing on 4-of-400
    # samples of one series. Best-of-3 per sample point removes the
    # stalls while preserving each path's intrinsic per-class tail
    # (the deterministic scan's own worst case), and interleaving
    # makes residual noise hit both series equally.
    times, cpu_times = [], []
    for i in range(400):
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            pol.schedule(cluster, reqs[i % N_CLASSES])
            best = min(best, time.perf_counter() - t0)
        times.append(best)
        cpu_times.append(min(native(i) for _ in range(3)))
    adaptive_p99_us = float(np.percentile(np.array(times), 99) * 1e6)
    cpu_p99_us = float(np.percentile(np.array(cpu_times), 99) * 1e6)
    return adaptive_p99_us, cpu_p99_us


def bench_pg_pack(avail, total, alive, rng):
    """PG bin-pack as a jitted assignment solve vs the Python greedy
    (the north star's second mechanism, BASELINE.json:5)."""
    import jax.numpy as jnp
    from ray_tpu._private.scheduler.pg_kernel import _pack_kernel

    B = 512
    demands = np.zeros((B, N_RES), np.float32)
    demands[:, 0] = rng.choice([1, 2, 4], B)     # CPU
    demands[:, 2] = rng.choice([1, 2], B)        # memory

    av = jnp.asarray(avail, jnp.float32)
    tot = jnp.asarray(total, jnp.float32)
    al = jnp.asarray(alive)
    dm = jnp.asarray(demands)
    np.asarray(_pack_kernel(av, tot, al, dm, "spread"))   # compile
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        out = np.asarray(_pack_kernel(av, tot, al, dm, "spread"))
        times.append(time.perf_counter() - t0)
    assert out[-1] == 1, "pg kernel failed to place the bench bundles"
    kernel_rate = B / min(times)

    # Python greedy baseline on a sample of bundles, same semantics
    # (least-utilized feasible node, prefer-unused), extrapolated.
    from ray_tpu._private.ids import NodeID
    from ray_tpu._private.scheduler.resources import NodeResources

    names = ["CPU", "TPU", "memory", "custom"]
    nodes = {}
    for i in range(N_NODES):
        nodes[NodeID.from_random()] = NodeResources(
            total={n: float(v) for n, v in zip(names, total[i]) if v > 0},
            available={n: float(avail[i][j])
                       for j, n in enumerate(names) if total[i][j] > 0})
    sample = 16
    used = set()
    t0 = time.perf_counter()
    for b in range(sample):
        demand = {n: float(v) for n, v in zip(names, demands[b]) if v > 0}
        choices = sorted(
            ((n.critical_utilization() + (1e3 if nid in used else 0), nid)
             for nid, n in nodes.items() if n.is_available(demand)),
            key=lambda t: t[0])
        _, nid = choices[0]
        nodes[nid].allocate(demand)
        used.add(nid)
    python_rate = sample / (time.perf_counter() - t0)
    return kernel_rate, python_rate


def bench_pg_pack_batched(avail, total, alive, rng):
    """Batched gang packing (docs/scheduler.md): a restart-storm burst
    — G gangs × B bundles each, the shape a PR-4 gang-restart wave or
    PR-6 slice-set re-form produces — packed in ONE launch with one
    d2h via the top-k-prefiltered vmapped kernel. The single-group
    number above is kept for continuity; this is the path storms
    actually ride."""
    import jax.numpy as jnp
    from ray_tpu._private.scheduler.pg_kernel import _pack_batch_kernel

    G, B, K = 64, 8, 128
    demands = np.zeros((G, B, N_RES), np.float32)
    demands[:, :, 0] = rng.choice([1, 2, 4], (G, B))     # CPU
    demands[:, :, 2] = rng.choice([1, 2], (G, B))        # memory
    valid = np.ones((G, B), bool)

    av = jnp.asarray(avail, jnp.float32)
    tot = jnp.asarray(total, jnp.float32)
    al = jnp.asarray(alive)
    dm = jnp.asarray(demands)
    vd = jnp.asarray(valid)
    np.asarray(_pack_batch_kernel(av, tot, al, dm, vd, "spread", K))
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        out = np.asarray(_pack_batch_kernel(av, tot, al, dm, vd,
                                            "spread", K))
        times.append(time.perf_counter() - t0)
    ok_groups = int((out[:, -1] == 1).sum())
    assert ok_groups == G, f"batched pg pack placed {ok_groups}/{G}"
    return G * B / min(times), G


def _run_section_subprocess(flag: str) -> dict:
    """Run a RUNTIME-measuring section (e2e, serve) in a clean CPU
    subprocess: these sections measure the task/actor/ingress planes,
    not the chip, which the parent holds (one process per chip) —
    and in-process they would share the 1M-task section's heap. A
    section that dies or prints no record fails the run."""
    import subprocess
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), flag],
        env=env, stdout=subprocess.PIPE, timeout=900, check=True)
    for line in reversed(proc.stdout.decode().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            record = json.loads(line)
            if record:
                return record
    raise RuntimeError(f"bench section {flag} printed no record")


def bench_e2e_runtime():
    """End-to-end runtime numbers through the FULL hot path —
    submit → schedule → lease → worker process → result — on a live
    runtime, the analog of `ray microbenchmark`
    (reference ``python/ray/_private/ray_perf.py``): serial round-trip
    p50/p99 for config-1 pi tasks, pipelined task throughput, and
    actor calls/s. Returns a dict of fields (empty on failure — bench
    must never die on the runtime section)."""
    out = {}
    try:
        import ray_tpu
        # num_tpus: logical TPU resource slots for the (b2) TPU-lane
        # dispatch measurement — the lane's cost is dispatch, not chip
        # compute, so fake slots measure the honest thing on CPU rigs.
        ray_tpu.init(num_cpus=8, num_tpus=8, max_process_workers=4)

        @ray_tpu.remote
        def pi_task(n=100):
            import random
            inside = 0
            for _ in range(n):
                x, y = random.random(), random.random()
                inside += x * x + y * y <= 1.0
            return 4.0 * inside / n

        # Warm the worker pool (process spawn is seconds; steady-state
        # dispatch is what the reference benchmark measures too).
        ray_tpu.get([pi_task.remote() for _ in range(16)])
        # ... and wait for the pool to actually FINISH spawning: on a
        # 1-core box the background python process startups contend
        # with the measured tasks for ~2s, tripling the serial p50 of
        # whatever runs during that window.
        import ray_tpu._private.worker as _w
        _pool = (_w.global_worker().node_group
                 ._raylets[_w.global_worker().node_group.head_node_id]
                 .worker_pool)
        _deadline = time.monotonic() + 30
        while time.monotonic() < _deadline:
            with _pool._lock:
                spawning = [w for w in _pool._all.values()
                            if hasattr(w, "proc") and not w.ready]
            if not spawning:
                break
            time.sleep(0.1)
        ray_tpu.get([pi_task.remote() for _ in range(64)])

        # (a) serial submit→result round trip.
        lats = []
        for _ in range(200):
            t0 = time.perf_counter()
            ray_tpu.get(pi_task.remote())
            lats.append(time.perf_counter() - t0)
        lats = np.array(lats)
        out["e2e_roundtrip_p50_ms"] = round(
            float(np.percentile(lats, 50)) * 1e3, 3)
        out["e2e_roundtrip_p99_ms"] = round(
            float(np.percentile(lats, 99)) * 1e3, 3)

        # (b) pipelined throughput: submit wave + drain, best of 3
        # waves — the first wave after an allocation burst runs 20-40%
        # slow on this 1-core box (GC/ref churn; BASELINE.md variance
        # note), so steady state is the honest figure.
        n = 2000
        best_dt = float("inf")
        for _wave in range(3):
            t0 = time.perf_counter()
            refs = [pi_task.remote() for _ in range(n)]
            ray_tpu.get(refs)
            best_dt = min(best_dt, time.perf_counter() - t0)
        out["e2e_tasks_per_sec"] = round(n / best_dt, 1)

        # (b2) the TPU-task lane: tasks demanding TPU run on IN-PROCESS
        # thread workers (one process per host owns the chip —
        # ARCHITECTURE.md §1), so their dispatch skips the worker-pipe
        # serialization entirely. This is the lane real accelerator
        # tasks ride; reported separately from the process-worker path
        # above (the reference's worker-process architecture analog).
        @ray_tpu.remote(num_tpus=0.001)
        def tiny(i):
            return i

        ray_tpu.get([tiny.remote(i) for i in range(16)])
        best_dt = float("inf")
        for _wave in range(3):
            t0 = time.perf_counter()
            ray_tpu.get([tiny.remote(i) for i in range(n)])
            best_dt = min(best_dt, time.perf_counter() - t0)
        out["e2e_tpu_lane_tasks_per_sec"] = round(n / best_dt, 1)

        # (c) actor calls: serial latency + pipelined calls/s.
        @ray_tpu.remote
        class Counter:
            def __init__(self):
                self.n = 0

            def ping(self):
                self.n += 1
                return self.n

        a = Counter.remote()
        ray_tpu.get(a.ping.remote())          # actor process up
        t0 = time.perf_counter()
        m = 2000
        refs = [a.ping.remote() for _ in range(m)]
        assert ray_tpu.get(refs)[-1] == m + 1
        out["actor_calls_per_sec"] = round(m / (time.perf_counter() - t0),
                                           1)

        # (d) async actor calls: the event-loop runtime + batched wire
        # path (one frame per flush both directions) — the analog of
        # the reference's highest-throughput primitive.
        @ray_tpu.remote
        class AsyncCounter:
            def __init__(self):
                self.n = 0

            async def ping(self):
                self.n += 1
                return self.n

        b = AsyncCounter.remote()
        ray_tpu.get(b.ping.remote())
        for _ in range(2):                     # warm the batched path
            ray_tpu.get([b.ping.remote() for _ in range(1000)])
        m = 10000
        best = 0.0
        for _ in range(2):   # best-of-2: one OS stall mid-wave on a
            t0 = time.perf_counter()          # 1-core box halves a run
            refs = [b.ping.remote() for _ in range(m)]
            ray_tpu.get(refs)
            best = max(best, m / (time.perf_counter() - t0))
        out["async_actor_calls_per_sec"] = round(best, 1)
    except Exception as e:
        print(f"# e2e runtime bench failed: {e!r}", file=sys.stderr)
        raise
    finally:
        try:
            import ray_tpu
            ray_tpu.shutdown()
        except Exception:
            pass
    return out


def bench_wire():
    """Open-loop data-plane numbers (docs/data_plane.md): burst-submit
    through the REAL owner<->raylet wire path — one remote raylet, so
    submits leave as coalesced submit_many frames, completions return
    as task_done_many pushes, and small frames ride the negotiated
    binary protocol. Reports the pipelined throughput the 10x claim
    is tracked by ALONGSIDE the realized coalescing factor and wire
    cost per task, so a regression in batching shows up as a frame
    metric, not just a throughput mystery."""
    out = {}
    try:
        import ray_tpu
        from ray_tpu._private import wire_stats
        from ray_tpu.cluster_utils import Cluster

        cluster = Cluster(head_num_cpus=1)
        try:
            cluster.add_node(num_cpus=8, resources={"W": 8},
                             remote=True, max_process_workers=4)

            # zero-CPU + fractional custom resource: the whole burst
            # is schedulable at once, so the measurement is the wire
            # pipeline, not owner-side resource throttling
            @ray_tpu.remote(num_cpus=0, resources={"W": 0.001})
            def tiny(i):
                return i

            # Two warm waves: the remote raylet's worker spawns run in
            # the background for ~2s on a 1-core box and pollute
            # whatever is measured during that window.
            for _ in range(2):
                ray_tpu.get([tiny.remote(i) for i in range(300)])
            n = 2000
            best, snap = 0.0, {}
            for _wave in range(3):
                wire_stats.reset()
                t0 = time.perf_counter()
                refs = [tiny.remote(i) for i in range(n)]
                ray_tpu.get(refs)
                rate = n / (time.perf_counter() - t0)
                if rate > best:
                    best, snap = rate, wire_stats.snapshot()
            out["e2e_pipelined_tasks_per_sec"] = round(best, 1)
            lease = snap.get("lease_rpc", {})
            out["rpc_frame_avg_batch"] = round(
                lease.get("avg_batch", 0.0), 2)
            # full-duplex owner<->raylet wire cost of one task: bytes
            # sent (lease frames) + received (completion pushes),
            # driver side of the channel
            sent = snap.get("rpc:raylet_channel", {}).get("bytes", 0)
            rcvd = snap.get("rpcin:raylet_channel", {}).get("bytes", 0)
            out["rpc_bytes_per_task"] = round((sent + rcvd) / n, 1)
            out["rpc_fastframe_hits"] = (
                snap.get("rpc:raylet_channel", {}).get(
                    "fastframe_hits", 0)
                + snap.get("rpcin:raylet_channel", {}).get(
                    "fastframe_hits", 0))
        finally:
            cluster.shutdown()
    except Exception as e:
        print(f"# wire bench failed: {e!r}", file=sys.stderr)
        raise
    return out


def bench_serve():
    """Serve-plane numbers (docs/serve.md):

    (a) OPEN-LOOP sustained load through the batched handle path —
    requests paced at a fixed arrival rate regardless of completions
    (the production shape: users don't wait for each other), echo
    deployment with ``@serve.batch``, 2 replicas. Reports completed
    RPS, per-request p99 (submit -> result landing), realized batch
    size, shed fraction, and whether the queue gauge returned to
    baseline after the run.

    (b) HTTP ingress, same box same session, three numbers: the
    legacy CLOSED-LOOP stdlib thread-per-request backend measured on
    the WORKER-hosted proxy actor exactly as pre-async serve.start
    shipped it (one connection per request, 4 clients — the pre-PR
    shape, continuous with BENCH r05's recorded numbers) as
    serve_http_legacy_*; OPEN-LOOP keep-alive pipelined load against
    the async event-loop ingress on the driver (where it rides the
    router's batched promise plane — paced arrivals on raw sockets,
    latency measured from the SCHEDULED arrival so queueing under
    overload is charged to the system, not hidden by a blocked
    client) as serve_http_*; and streamed first-token latency
    (client-observed + the ray_tpu_serve_first_token_ms window) as
    serve_first_token_ms.
    """
    out = {}
    try:
        import threading

        import ray_tpu
        from ray_tpu import serve
        from ray_tpu._private import serve_stats

        ray_tpu.init(num_cpus=8, max_process_workers=4,
                     _system_config={"serve_max_queued_requests": 60000})

        @serve.deployment(num_replicas=2)
        class Echo:
            @serve.batch(max_batch_size=256, batch_wait_timeout_ms=2)
            async def __call__(self, items):
                return items

        handle = serve.run(Echo.bind())
        ray_tpu.get([handle.remote(i) for i in range(512)],
                    timeout=120)            # warm replicas + batch path
        serve_stats.reset()

        # open loop: pace N requests at TARGET_RPS in TICK_S ticks.
        # Latency is SAMPLED 1-in-8 via completion callbacks (a stamp
        # per request costs a ready-callback registration each — at
        # 25k/s that overhead alone shaved ~15% off throughput);
        # completion COUNTING rides the same sampled callbacks plus a
        # final full drain on the unsampled refs.
        TARGET_RPS = 28500
        N = 57000
        SAMPLE = 8
        TICK_S = 0.01
        chunk = int(TARGET_RPS * TICK_S)
        w = ray_tpu._private.worker.global_worker()
        lat_lock = threading.Lock()
        lats, shed = [], 0
        refs = []
        t_start = time.perf_counter()
        next_tick = t_start
        submitted = 0
        while submitted < N:
            n_now = min(chunk, N - submitted)
            for _ in range(n_now):
                sampled = (submitted % SAMPLE) == 0
                t0 = time.perf_counter() if sampled else 0.0
                try:
                    ref = handle.remote(submitted)
                except Exception:       # BackpressureError: shed
                    shed += 1
                    continue
                refs.append(ref)
                if sampled:
                    def _done(_oid, _t0=t0):
                        dt_ms = (time.perf_counter() - _t0) * 1e3
                        with lat_lock:
                            lats.append(dt_ms)

                    w.on_object_ready(ref.id(), _done)
                submitted += 1
            next_tick += TICK_S
            delay = next_tick - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
        # drain: every accepted request resolves exactly once
        ray_tpu.get(refs, timeout=120)
        dt = time.perf_counter() - t_start
        with lat_lock:
            arr = np.array(lats)
        out["serve_rps"] = round(submitted / dt, 1)
        out["serve_p99_ms"] = round(float(np.percentile(arr, 99)), 2)
        out["serve_p50_ms"] = round(float(np.percentile(arr, 50)), 2)
        out["serve_batch_avg"] = round(serve_stats.batch_avg(), 1)
        out["serve_shed_fraction"] = round(shed / (submitted + shed), 4)
        # gauges return to baseline once load stops
        settle_deadline = time.perf_counter() + 10
        settled = False
        while time.perf_counter() < settle_deadline:
            st = serve.status()["Echo"]
            if (st["queued_requests"] == 0
                    and st["ongoing_requests"] == 0):
                settled = True
                break
            time.sleep(0.05)
        out["serve_queue_settled"] = settled
        serve.delete("Echo")

        # ---- (b) HTTP ingress: legacy vs async, same session ----
        import json as _json
        import socket as _socket
        import urllib.request
        from collections import deque as _deque

        from ray_tpu.serve._private.http_proxy import HttpProxy

        @serve.deployment(num_replicas=2)
        class HttpEcho:
            @serve.batch(max_batch_size=256, batch_wait_timeout_ms=2)
            async def __call__(self, items):
                return items

        serve.run(HttpEcho.bind())
        controller = serve._controller
        body = _json.dumps({"v": 1}).encode()

        # legacy closed-loop: the stdlib thread-per-request backend in
        # a WORKER-hosted ProxyActor — the exact topology pre-async
        # serve.start(http=True) brought up — with a fresh connection
        # per request (what every pre-PR client did)
        from ray_tpu._private.worker import global_worker
        from ray_tpu.serve._private.http_proxy import ProxyActor
        from ray_tpu.util.scheduling_strategies import (
            NodeAffinitySchedulingStrategy)
        head = global_worker().node_group.head_node_id.hex()
        legacy = ray_tpu.remote(ProxyActor).options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(
                node_id=head)).remote(backend="threaded")
        ray_tpu.get(legacy.ping.remote(), timeout=60)
        controller.register_proxy(legacy)
        lhost, lport = ray_tpu.get(legacy.address.remote(), timeout=30)
        url = f"http://{lhost}:{lport}/HttpEcho"

        def one():
            req = urllib.request.Request(
                url, data=body,
                headers={"Content-Type": "application/json"},
                method="POST")
            with urllib.request.urlopen(req, timeout=60) as resp:
                assert resp.status == 200
                resp.read()

        for _ in range(20):
            one()
        n_threads, per = 4, 100
        hlats = []
        hlat_lock = threading.Lock()

        def client():
            mine = []
            for _ in range(per):
                t0 = time.perf_counter()
                one()
                mine.append(time.perf_counter() - t0)
            with hlat_lock:
                hlats.extend(mine)

        threads = [threading.Thread(target=client)
                   for _ in range(n_threads)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        out["serve_http_legacy_rps"] = round(n_threads * per / dt, 1)
        out["serve_http_legacy_p99_ms"] = round(
            float(np.percentile(np.array(hlats), 99)) * 1e3, 2)
        controller.detach_proxies()
        ray_tpu.get(legacy.prepare_shutdown.remote(5.0), timeout=30)
        ray_tpu.kill(legacy)

        # open-loop keep-alive pipelined load on the async ingress:
        # paced arrivals fanned over NCONN persistent connections;
        # each request's latency runs from its SCHEDULED arrival to
        # its response, so a backed-up server pays in p99 instead of
        # silently slowing the client (open-loop honesty).
        proxy = HttpProxy(controller, backend="async")
        ahost, aport = proxy.address
        REQ = (b"POST /HttpEcho HTTP/1.1\r\nHost: b\r\n"
               b"Content-Type: application/json\r\n"
               b"Content-Length: "
               + str(len(body)).encode() + b"\r\n\r\n" + body)
        HTTP_RPS, NCONN = 12500, 4
        NH = 32000
        H_TICK = 0.005
        H_SAMPLE = 8        # stamp 1-in-8: the client shares this
        #                     core with the server under test, so
        #                     per-response bookkeeping shaves capacity
        conns = []
        for _ in range(NCONN):
            s = _socket.create_connection((ahost, aport), timeout=60)
            s.setsockopt(_socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1)
            conns.append(s)
        # warm: one round-trip per connection; the echo response is
        # byte-identical every time, so readers consume fixed-size
        # blocks instead of parsing headers per response (client CPU
        # shares this one core with the server under test)
        for s in conns:
            s.sendall(REQ)
        resp_len = 0
        files = [s.makefile("rb") for s in conns]
        for f in files:
            line = f.readline()
            assert b"200" in line
            total = len(line)
            clen = 0
            while True:
                ln = f.readline()
                total += len(ln)
                if not ln.strip():
                    break
                if ln.lower().startswith(b"content-length"):
                    clen = int(ln.split(b":")[1])
            f.read(clen)
            resp_len = total + clen
        scheds = [_deque() for _ in range(NCONN)]
        alats, alock = [], threading.Lock()
        per_conn = NH // NCONN
        t_end_box = [0.0]

        def reader(i):
            f, q, mine = files[i], scheds[i], []
            for k in range(per_conn):
                blob = f.read(resp_len)
                assert len(blob) == resp_len
                # sampled stamps carry their per-conn sequence number;
                # the producer appends before sendall, so a stamp is
                # always present before its response can arrive
                if q and q[0][0] == k:
                    mine.append(time.perf_counter() - q.popleft()[1])
            with alock:
                alats.extend(mine)
                t_end_box[0] = max(t_end_box[0], time.perf_counter())

        readers = [threading.Thread(target=reader, args=(i,))
                   for i in range(NCONN)]
        h_chunk = int(HTTP_RPS * H_TICK)
        t_start = time.perf_counter()
        for t in readers:
            t.start()
        next_tick = t_start
        g = 0
        seqs = [0] * NCONN
        while g < NH:
            k = min(h_chunk, NH - g)
            counts = [0] * NCONN
            for _ in range(k):
                i = g % NCONN
                if g % H_SAMPLE == 0:           # scheduled arrival
                    scheds[i].append((seqs[i], next_tick))
                seqs[i] += 1
                counts[i] += 1
                g += 1
            for i in range(NCONN):
                if counts[i]:
                    conns[i].sendall(REQ * counts[i])
            next_tick += H_TICK
            delay = next_tick - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
        for t in readers:
            t.join(timeout=120)
        arr = np.array(alats) * 1e3
        out["serve_http_rps"] = round(
            NH / (t_end_box[0] - t_start), 1)
        out["serve_http_p99_ms"] = round(float(np.percentile(arr, 99)), 2)
        out["serve_http_p50_ms"] = round(float(np.percentile(arr, 50)), 2)

        # streamed first-token latency through the async ingress
        @serve.deployment
        class Tok:
            def __call__(self, n):
                for i in range(int(n)):
                    yield {"t": i}

        serve.run(Tok.bind(), name="Tok")
        sreq = (b"POST /Tok?stream=1 HTTP/1.1\r\nHost: b\r\n"
                b"Content-Type: application/json\r\n"
                b"Content-Length: 1\r\n\r\n8")
        ft = []
        s = conns[0]
        f = s.makefile("rb")
        for _ in range(20):
            t0 = time.perf_counter()
            s.sendall(sreq)
            f.readline()                        # status line
            while f.readline().strip():
                pass                            # headers
            first_seen = False
            while True:                         # chunks to terminator
                size = int(f.readline().strip(), 16)
                if size == 0:
                    f.readline()
                    break
                if not first_seen:
                    ft.append((time.perf_counter() - t0) * 1e3)
                    first_seen = True
                f.read(size)
                f.readline()
        out["serve_first_token_ms"] = round(
            float(np.percentile(np.array(ft), 50)), 2)
        out["serve_first_token_gauge_ms"] = round(
            serve_stats.first_token_ms(), 2)
        for s in conns:
            s.close()
        proxy.shutdown()
    except Exception as e:
        print(f"# serve bench failed: {e!r}", file=sys.stderr)
        raise
    finally:
        try:
            from ray_tpu import serve as _s
            _s.shutdown()
        except Exception:
            pass
        try:
            import ray_tpu
            ray_tpu.shutdown()
        except Exception:
            pass
    return out


_PEAK_BF16_TFLOPS = {
    # marketing peak bf16 TFLOP/s per chip, keyed on device_kind prefix
    "TPU v6": 918.0,
    "TPU v5 lite": 197.0,
    "TPU v5p": 459.0,
    "TPU v5": 459.0,
    "TPU v4 lite": 138.0,
    "TPU v4": 275.0,
    "TPU v3": 123.0,
    "TPU v2": 46.0,
}


def bench_multislice():
    """Cross-slice runtime plane (docs/multislice.md): per-step time
    of a 2-slice hierarchical-DCN trainer vs the identical single-mesh
    (flat, no DCN tier) run, under a REALISTIC simulated DCN cost
    model, plus the byte accounting that proves only ~1/num_slices of
    gradient bytes cross the DCN tier. Runs on the actor/collective
    plane — subprocess'd like the other runtime sections."""
    out = {}
    GRAD = 256 * 1024          # float64 elements => 2 MiB per payload
    STEPS = 6

    def init_fn():
        return np.zeros(GRAD)

    def grad_fn(state, global_rank, world, step):
        return np.full(GRAD, float(global_rank + step))

    def apply_fn(state, synced):
        state = state + synced
        return state, float(state[0])

    def one_run(num_slices, ranks_per_slice):
        import ray_tpu
        from ray_tpu.train.multislice import (MultiSliceConfig,
                                              MultiSliceTrainer)
        # realistic DCN point: ~1 ms latency, 25 Gb/s per link
        ray_tpu.init(num_cpus=8, max_process_workers=4,
                     _system_config={"dcn_latency_ms": 1.0,
                                     "dcn_gbps": 25.0})
        try:
            tr = MultiSliceTrainer(
                init_fn, grad_fn, apply_fn,
                MultiSliceConfig(num_slices=num_slices,
                                 ranks_per_slice=ranks_per_slice,
                                 resources_per_worker={"CPU": 1.0}))
            tr.start()
            tr.run(2)                      # warm the worker paths
            t0 = time.perf_counter()
            tr.run(STEPS)
            dt = (time.perf_counter() - t0) / STEPS
            stats = tr.dcn_stats()
            tr.shutdown()
            return dt, stats
        finally:
            ray_tpu.shutdown()

    try:
        flat_dt, _ = one_run(1, 4)
        hier_dt, stats = one_run(2, 2)
        grad_bytes = GRAD * 8
        total_steps = 2 + STEPS
        flat_dcn_bytes = 4 * grad_bytes * total_steps  # all ranks x DCN
        out["multislice_step_ms"] = round(hier_dt * 1e3, 2)
        out["singlemesh_step_ms"] = round(flat_dt * 1e3, 2)
        out["dcn_step_overhead_pct"] = round(
            100.0 * (hier_dt - flat_dt) / max(flat_dt, 1e-9), 1)
        out["dcn_bytes_per_step"] = int(stats["bytes_tx"] / total_steps)
        # hierarchical-vs-flat DCN traffic: 2 leader payloads per step
        # against every rank's payload — the ~1/num_slices claim
        out["dcn_bytes_fraction_vs_flat"] = round(
            stats["bytes_tx"] / flat_dcn_bytes, 4)
        out["dcn_collective_ms_per_step"] = round(
            stats["ms"] / total_steps, 2)
    except Exception as e:
        print(f"# multislice bench failed: {e!r}", file=sys.stderr)
        raise
    return out


def bench_data():
    """Streaming data plane (docs/data_pipeline.md): block throughput
    through a read -> map -> map pipeline consumed incrementally, and
    the trainer-ingestion starvation fraction with a 2-slice trainer
    fed by ``run_with_data``. Runtime-plane numbers — subprocess'd
    like e2e/serve, and honest the same way: deltas are same-box
    same-session only."""
    out = {}
    ROWS_PER_BLOCK = 4096
    NUM_BLOCKS = 48

    try:
        import ray_tpu
        from ray_tpu import data as rdata
        ray_tpu.init(num_cpus=8, num_tpus=8, max_process_workers=4)
        try:
            def pipeline():
                ds = rdata.range(NUM_BLOCKS * ROWS_PER_BLOCK,
                                 parallelism=NUM_BLOCKS)
                ds = ds.map_batches(lambda b: {"id": b["id"] * 2})
                return ds.map_batches(
                    lambda b: {"id": b["id"] + 1})

            # warm the worker pool (spawn cost is seconds; steady
            # state is what the pipeline runs at)
            for _ in pipeline().iter_batches(batch_size=ROWS_PER_BLOCK):
                pass

            from ray_tpu._private import data_stats
            before = data_stats.snapshot()
            t0 = time.perf_counter()
            nrows = 0
            for batch in pipeline().iter_batches(
                    batch_size=ROWS_PER_BLOCK, prefetch_batches=2):
                nrows += len(batch["id"])
            dt = time.perf_counter() - t0
            after = data_stats.snapshot()
            blocks = (after["blocks_produced"]
                      - before["blocks_produced"])
            nbytes = (after["bytes_produced"]
                      - before["bytes_produced"])
            out["data_blocks_per_sec"] = round(blocks / dt, 1)
            out["data_bytes_per_sec"] = int(nbytes / dt)
            out["data_rows_per_sec"] = int(nrows / dt)

            # trainer ingestion: starvation fraction of a 2-slice
            # trainer fed off the pipeline with prefetch
            from ray_tpu.train.multislice import (MultiSliceConfig,
                                                  MultiSliceTrainer)

            def init_fn():
                return np.zeros(8)

            def grad_fn(state, rank, world, step, batch):
                return np.full(8, float(np.asarray(
                    batch["id"], dtype=np.float64).mean()))

            def apply_fn(state, synced):
                state = state + synced
                return state, float(state[0])

            tr = MultiSliceTrainer(
                init_fn, grad_fn, apply_fn,
                MultiSliceConfig(num_slices=2, ranks_per_slice=1,
                                 resources_per_worker={"CPU": 1.0}))
            tr.start()
            tr.run_with_data(
                pipeline().iter_batches(batch_size=ROWS_PER_BLOCK,
                                        batch_format="numpy"),
                prefetch_batches=2)
            out["data_trainer_starvation_fraction"] = round(
                tr.last_ingest["starvation_fraction"], 4)
            out["data_trainer_steps_per_sec"] = round(
                tr.last_ingest["steps"]
                / max(tr.last_ingest["wall_s"], 1e-9), 1)
            tr.shutdown()
        finally:
            ray_tpu.shutdown()
    except Exception as e:
        print(f"# data bench failed: {e!r}", file=sys.stderr)
        raise
    return out


def bench_objects():
    """Object plane (docs/object_plane.md): tree-broadcast time
    1 -> N consumers vs N sequential single-peer pulls, restart-storm
    re-distribution time (half the holders die, fresh consumers
    re-pull through failover), and stage-to-stage bytes/s through the
    PullManager vs the flat single-source wire client.

    In-process node harness (store + pull engine + object server per
    simulated node) over loopback TCP. Loopback has no per-link
    bandwidth, which is the whole variable broadcast fan-out exists to
    manage — so the broadcast/sequential comparison runs under a fixed
    per-chunk service time on every serving node (LINK_S below, the
    modeled cost of a constrained peer link). The sequential baseline
    pays that cost serially, chunk after chunk after consumer after
    consumer; the tree overlaps it across links. The stage-to-stage
    section runs with NO link model — it measures the real path
    overhead of the two clients doing identical work (wire pull into
    a sealed local store object). Same-box modeled numbers: deltas
    are same-session only, like the other runtime sections."""
    import shutil
    import tempfile
    import threading

    out = {}
    tmp = tempfile.mkdtemp(prefix="rtpu-bench-objects-")
    nodes = []
    try:
        from ray_tpu._private import wire_stats
        from ray_tpu._private.config import get_config
        from ray_tpu._private.ids import JobID, ObjectID, TaskID
        from ray_tpu._private.object_store import ShmStore
        from ray_tpu._private.object_transfer import (PeerClients,
                                                      PullManager,
                                                      pull_object,
                                                      serve_store)
        from ray_tpu._private.rpc import RpcClient, RpcServer

        SIZE = 16 << 20
        N = 8
        LINK_S = 0.006          # modeled per-chunk link service time
        get_config().apply_system_config(
            {"object_chunk_size_bytes": 1 << 20})

        class Node:
            def __init__(self, name, link_s=0.0):
                self.store = ShmStore(
                    f"ob{os.getpid()}-{name}",
                    capacity_bytes=256 << 20,
                    spill_dir=os.path.join(tmp, name),
                    spill_threshold=0.95)
                self.peers = PeerClients()
                self.pm = PullManager(self.store, self.peers,
                                      label=name)
                self.served = wire_stats.ChannelStats()
                self.server = RpcServer(component=f"ob_{name}")

                def view(oid_bytes):
                    if link_s:
                        time.sleep(link_s)
                    return self.store.get_local(ObjectID(oid_bytes))

                serve_store(self.server, view,
                            progress=self.pm.progress,
                            stats=self.served)
                self.addr = tuple(self.server.address)
                nodes.append(self)

            def close(self):
                self.peers.close()
                self.server.shutdown()
                self.store.shutdown()

        task = TaskID.for_normal_task(JobID.from_int(9))  # random bits

        def oid(i):
            return ObjectID.from_index(task, i)

        payload = os.urandom(SIZE)
        root = Node("root", link_s=LINK_S)
        root.store.put_blob(oid(1), payload)

        # -- N sequential single-peer pulls (the pre-broadcast shape:
        # every consumer drains the one holder's link, one at a time)
        seq = [Node(f"s{i}", link_s=LINK_S) for i in range(N)]
        t0 = time.perf_counter()
        for node in seq:
            node.pm.pull(oid(1).binary(), SIZE, (root.addr,))
        dt_seq = time.perf_counter() - t0

        # -- tree broadcast: N fresh consumers, binary tree over
        # (parent, root-fallback) source lists, all pulls concurrent;
        # parents re-serve chunks while their own pull is in flight
        tree = [Node(f"t{i}", link_s=LINK_S) for i in range(N)]

        def wait_pulling(node, oid_b, deadline=30.0):
            end = time.perf_counter() + deadline
            while time.perf_counter() < end:
                if node.store.contains(ObjectID(oid_b)) \
                        or node.pm.progress(oid_b, 0, 0) is not None:
                    return
                time.sleep(0.001)

        root_bytes0 = root.served.bytes
        threads = []
        t0 = time.perf_counter()
        for k, node in enumerate(tree):
            parent = root if k == 0 else tree[(k - 1) // 2]
            if parent is not root:
                wait_pulling(parent, oid(1).binary())
            th = threading.Thread(
                target=node.pm.pull,
                args=(oid(1).binary(), SIZE, (parent.addr, root.addr)))
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=120)
        dt_tree = time.perf_counter() - t0
        out["object_broadcast_gbps"] = round(
            N * SIZE * 8 / dt_tree / 1e9, 2)
        out["object_broadcast_seq_gbps"] = round(
            N * SIZE * 8 / dt_seq / 1e9, 2)
        out["object_broadcast_vs_sequential"] = round(
            dt_seq / dt_tree, 2)
        out["object_link_model_ms_per_chunk"] = LINK_S * 1e3
        # of the 8 delivered copies, the fraction the ROOT's link
        # carried during the broadcast (1/N = perfect fan-out)
        out["object_broadcast_root_bytes_fraction"] = round(
            (root.served.bytes - root_bytes0) / (N * SIZE), 3)

        # -- restart storm: half the sealed holders die; fresh
        # consumers listing a corpse FIRST must fail over and re-seal
        dead, live = tree[:N // 2], tree[N // 2:]
        for node in dead:
            node.server.shutdown()
        storm = [Node(f"r{i}") for i in range(N // 2)]
        threads = []
        t0 = time.perf_counter()
        for i, node in enumerate(storm):
            srcs = (dead[i].addr, live[i].addr, root.addr)
            th = threading.Thread(target=node.pm.pull,
                                  args=(oid(1).binary(), SIZE, srcs))
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=120)
        out["object_restart_storm_redistribute_s"] = round(
            time.perf_counter() - t0, 3)

        # -- stage-to-stage blocks, NO link model: the flat
        # single-source client (the pre-PullManager localization path:
        # wire pull into bytes, then a second copy into the store) vs
        # the pull engine writing chunks straight into the unsealed
        # shm segment. Both end with the block sealed locally.
        BLOCK, NBLOCKS = 4 << 20, 16
        stage_src = Node("stagesrc")
        for i in range(NBLOCKS):
            stage_src.store.put_blob(oid(10 + i), os.urandom(BLOCK))
        flat_sink = Node("flatsink")
        flat_client = RpcClient(stage_src.addr)
        t0 = time.perf_counter()
        for i in range(NBLOCKS):
            data = pull_object(flat_client, oid(10 + i).binary(),
                               BLOCK)
            flat_sink.store.put_blob(oid(10 + i), data)
        dt_flat = time.perf_counter() - t0
        flat_client.close()
        pm_sink = Node("pmsink")
        t0 = time.perf_counter()
        for i in range(NBLOCKS):
            pm_sink.pm.pull(oid(10 + i).binary(), BLOCK,
                            (stage_src.addr,))
        dt_pm = time.perf_counter() - t0
        out["object_stage_bytes_per_sec"] = int(
            NBLOCKS * BLOCK / dt_pm)
        out["object_stage_bytes_per_sec_flat"] = int(
            NBLOCKS * BLOCK / dt_flat)
        out["object_stage_vs_flat"] = round(dt_flat / dt_pm, 2)
    except Exception as e:
        print(f"# objects bench failed: {e!r}", file=sys.stderr)
        raise
    finally:
        for node in nodes:
            try:
                node.close()
            except Exception:
                pass    # teardown best effort
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def bench_model_mfu():
    """Flagship-transformer training-step time and MFU% on the real
    chip. K steps run inside ONE jitted lax.scan (with the state
    donated) and the step time is the slope between two scan lengths,
    so the fixed per-invocation cost (dispatch + transfer) cancels and
    the measurement is device time. Fails on a device_kind whose peak
    is not in the table.

    FLOP accounting is HONEST about causality: the attention term is
    6·L·d·T·S (HALF the full square) because the flash kernels iterate
    KV blocks only to the diagonal under causal masking — crediting the
    full 12·L·d·T·S would flatter MFU by the skipped half. Config and
    convention recorded in BASELINE.md.
    """
    out = {}
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    peak_tflops = next((v for k, v in _PEAK_BF16_TFLOPS.items()
                        if dev.device_kind.startswith(k)), None)
    if peak_tflops is None:
        raise RuntimeError(f"no bf16 peak known for device_kind "
                           f"{dev.device_kind!r}")
    peak = peak_tflops * 1e12
    from ray_tpu.models import (
        TransformerConfig, init_state, make_optimizer, make_train_step)
    from ray_tpu.ops.flash_attention import flash_attention

    # Flagship sizing chosen by on-chip sweep (BASELINE.md): d2048
    # matmuls fill the MXU, Pallas flash attention at 512x512
    # blocks, no remat (remat re-executes forward FLOPs and
    # deflates MFU ~25%), state donated through the scan.
    cfg = TransformerConfig(
        vocab_size=32_768, d_model=2048, n_layers=8, n_heads=16,
        n_kv_heads=16, d_ff=8192, max_seq_len=2048, remat=False,
        use_flash=True)
    batch, seq = 4, 2048
    block_q = block_k = 512
    k_lo, k_hi = 2, 8
    tx = make_optimizer(total_steps=1000)
    state = init_state(jax.random.PRNGKey(0), cfg, tx)
    attn = lambda q, k, v, causal=True: flash_attention(  # noqa: E731
        q, k, v, causal=causal, block_q=block_q, block_k=block_k)
    step = make_train_step(cfg, tx, attn_fn=attn, donate=False)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size,
                                         (batch, seq), np.int32))

    def make_k(k_steps):
        def k_step(state, tokens):
            def body(s, _):
                s, metrics = step(s, {"tokens": tokens})
                return s, metrics["loss"]
            return jax.lax.scan(body, state, None, length=k_steps)
        # donate the 8 GB train state: without donation the scan
        # holds input AND output state live and the d2048 config
        # cannot run un-rematerialized
        return jax.jit(k_step, donate_argnums=(0,))

    def timed(k_jit, st):
        # np.asarray forces the d2h materialization, so the
        # timed region ends when the losses are on the host.
        t0 = time.perf_counter()
        st2, losses = k_jit(st, tokens)
        losses = np.asarray(losses)
        assert np.isfinite(losses[-1])
        return time.perf_counter() - t0, st2

    lo_jit, hi_jit = make_k(k_lo), make_k(k_hi)
    _, state = timed(lo_jit, state)              # compile + warm
    _, state = timed(hi_jit, state)
    # Slope timing: (t_hi - t_lo) / (k_hi - k_lo) cancels the fixed
    # per-invocation cost (dispatch + transfer).
    t_los, t_his = [], []
    for _ in range(3):
        dt, state = timed(lo_jit, state)
        t_los.append(dt)
        dt, state = timed(hi_jit, state)
        t_his.append(dt)
    step_s = max(min(t_his) - min(t_los), 1e-9) / (k_hi - k_lo)

    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(state.params))
    tokens_per_step = batch * seq
    # 6·N·T for the parameter matmuls (fwd + bwd) plus the CAUSAL
    # attention term 6·L·d·T·S — half the dense square, matching
    # what the kernels actually compute (see docstring).
    flops_per_step = (6.0 * n_params * tokens_per_step
                      + 6.0 * cfg.n_layers * cfg.d_model
                      * tokens_per_step * seq)

    print(f"# mfu: flops/step={flops_per_step:.3e} "
          f"step={step_s * 1e3:.2f}ms peak={peak:.2e} "
          f"params={n_params/1e6:.0f}M",
          file=sys.stderr)
    out["model_step_ms"] = round(step_s * 1e3, 2)
    out["model_tokens_per_sec"] = round(batch * seq / step_s, 1)
    out["model_mfu_pct"] = round(
        100.0 * flops_per_step / (step_s * peak), 2)
    out["model_device"] = dev.device_kind
    return out


def main():
    import jax
    from ray_tpu._private.compile_cache import configure_compile_cache
    configure_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench.py measures the chip and found none: jax "
                 f"reports platform {dev.platform!r}")
    rng = np.random.RandomState(42)
    avail, total, alive = build_cluster_arrays(rng)
    demands, counts, _ = build_demand_classes(rng)

    tpu_rate, n_scheduled, tpu_times, placed_per_class, fence = \
        bench_tpu_kernel(avail, total, alive, demands, counts)
    cpu_rate = bench_cpu_baseline(avail, total, alive, demands)

    # Capacity-sufficient companion (round-3 weak #7): the same kernel
    # on a queue scaled PER CLASS to what the cluster proved it can
    # place (infeasibility is per-resource-class, not global), so the
    # headline rate can't be read as partly an infeasibility discount.
    counts_fit = np.maximum(
        (placed_per_class * 0.9).astype(np.int32), 1)
    fit_rate, fit_scheduled, _t, _p, _f = bench_tpu_kernel(
        avail, total, alive, demands, counts_fit)
    fit_fraction = fit_scheduled / max(1, counts_fit.sum())
    light_p99_us, light_base_us = bench_p99_light_load(
        avail, total, alive, demands)
    pg_kernel_rate, pg_python_rate = bench_pg_pack(avail, total, alive,
                                                   rng)
    pg_batched_rate, pg_batched_groups = bench_pg_pack_batched(
        avail, total, alive, rng)

    # Heavy-load p99 (the north-star workload itself, 1M pending): a
    # task's dispatch latency is its wait until assignment. The TPU
    # kernel drains every placeable task in ONE invocation, so p99 =
    # invocation wall time (measured); the CPU baseline p99 is MODELED,
    # not measured: sequential dispatch at the measured cpu_rate means
    # the p99 task waits for 99% of the queue ahead of it (draining the
    # full 1M through the scalar loop would take minutes per run).
    heavy_p99_tpu_s = max(tpu_times)
    heavy_p99_cpu_s = 0.99 * n_scheduled / cpu_rate

    record = {
        "metric": "scheduler_tasks_per_sec_10k_nodes_1M_tasks",
        "value": round(tpu_rate, 1),
        "unit": "tasks/s",
        "vs_baseline": round(tpu_rate / cpu_rate, 2),
        # Second north-star number, both regimes. >= 1 means the TPU
        # build's p99 is at or below the CPU baseline's.
        "p99_heavy_load_s": round(heavy_p99_tpu_s, 3),
        # baseline side of this ratio is modeled from the measured CPU
        # rate (see comment above), not a measured drain
        "p99_heavy_vs_baseline_modeled": round(
            heavy_p99_cpu_s / heavy_p99_tpu_s, 1),
        "p99_light_load_us": round(light_p99_us, 1),
        # fraction of the 1M pending tasks the 10k-node cluster had
        # capacity to place this round (the rest stay queued).
        "placeable_fraction": round(n_scheduled / N_TASKS, 4),
        # honesty split (docs/scheduler.md): per-class capacity bound
        # from NODE TOTALS — the fraction any scheduler could place
        # even on an idle cluster; everything beyond it is fenced as
        # "cluster cannot fit", not a kernel miss
        "capacity_upper_fraction": round(
            (N_TASKS - fence["fenced"]) / N_TASKS, 4),
        # of the work the live cluster admitted at each class's commit
        # turn, the fraction the kernel actually placed — the "kernel
        # failed to place" number, ~1.0 by the fill's completeness
        # contract (scarcity-ordered commit + residual pass)
        "placeable_fraction_of_feasible": round(
            n_scheduled / max(fence["admitted"], 1), 4),
        # companion run on a queue scaled to FIT the cluster: the rate
        # with (near-)full placeability, no infeasibility discount
        "capacity_fit_tasks_per_sec": round(fit_rate, 1),
        "capacity_fit_placeable_fraction": round(fit_fraction, 4),
        # PG bin-pack as a jitted assignment solve (512 bundles onto
        # the 10k-node cluster) vs the Python greedy.
        "pg_pack_bundles_per_sec": round(pg_kernel_rate, 1),
        "pg_pack_vs_baseline": round(pg_kernel_rate / pg_python_rate, 1),
        # restart-storm shape: many gangs in ONE launch through the
        # top-k-prefiltered vmapped kernel (docs/scheduler.md)
        "pg_pack_batched_bundles_per_sec": round(pg_batched_rate, 1),
        "pg_pack_batched_groups": pg_batched_groups,
        "pg_pack_batched_vs_single": round(
            pg_batched_rate / pg_kernel_rate, 1),
    }
    record["p99_light_baseline_us"] = round(light_base_us, 1)
    record["p99_light_vs_baseline"] = round(light_base_us / light_p99_us, 2)
    record.update(_run_section_subprocess("--e2e"))
    record.update(_run_section_subprocess("--wire"))
    record.update(_run_section_subprocess("--serve"))
    record.update(_run_section_subprocess("--multislice"))
    record.update(_run_section_subprocess("--data"))
    record.update(_run_section_subprocess("--objects"))
    record.update(bench_model_mfu())
    print(json.dumps(record))
    print(f"# scheduled {n_scheduled} of {N_TASKS} pending; "
          f"cpu baseline {cpu_rate:.1f} tasks/s (sample {BASELINE_SAMPLE}); "
          f"heavy p99 {heavy_p99_tpu_s:.3f}s vs cpu {heavy_p99_cpu_s:.1f}s; "
          f"light p99 {light_p99_us:.0f}us vs native scan {light_base_us}us",
          file=sys.stderr)


if __name__ == "__main__":
    if "--e2e" in sys.argv:
        print(json.dumps(bench_e2e_runtime()))
    elif "--wire" in sys.argv:
        print(json.dumps(bench_wire()))
    elif "--serve" in sys.argv:
        print(json.dumps(bench_serve()))
    elif "--multislice" in sys.argv:
        print(json.dumps(bench_multislice()))
    elif "--data" in sys.argv:
        print(json.dumps(bench_data()))
    elif "--objects" in sys.argv:
        print(json.dumps(bench_objects()))
    else:
        main()
