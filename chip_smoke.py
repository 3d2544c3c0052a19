"""Quickest proof that ray_tpu's main path still starts on the chip.

    python chip_smoke.py             # one TPU: five phases, ~5 min cold
    python chip_smoke.py --chips 4   # four TPUs: the sharded step only

One process owns the chip(s): a driver calls ``ray_tpu.init()``, the
scheduler places work (device kernel for deep queues), CPU tasks run in
process workers that never open the device, ``TPU``-demand tasks and
actors run on the driver's in-process lane, ``ray_tpu.train`` and
``ray_tpu.serve`` run the benchmark's model (Mistral-7B-v0.1 at its
published widths, two layers) on that lane. Every
phase checks what came out against something that shares no code with
it; any failure is an exception, so the script cannot reach its last
line with a phase broken. Without a TPU it stops in the first phase.

Each phase prints one JSON line of facts (compile seconds, round-trip
probe, kernel/scan batch split, step seconds, peak bytes). The LAST
stdout line is ``{"ok": true, "device": {...}}`` with the device as jax
reports it. Times printed here are observations, not a benchmark.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
import urllib.request

import numpy as np

_PUBLISHED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "benchmark", "configs", "mistral-7b-v0.1-l3.json")


def _flagship() -> dict:
    """The benchmark's model, Mistral-7B-v0.1 at its published widths,
    as ``TransformerConfig`` fields: read from the benchmark's own file
    and cut in depth alone, to two layers with the embedding and the
    head (698M parameters, 8.4 GB of float32 train state: what one chip
    holds beside a 4 x 2,048-token step with every activation kept).
    No remat, Pallas flash attention."""
    from ray_tpu.models import config_from_hf

    with open(_PUBLISHED) as f:
        published = json.load(f)
    cfg = dataclasses.replace(
        config_from_hf({**published, "num_hidden_layers": 2},
                       max_seq_len=2048),
        remat=False, use_flash=True)
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


FLAGSHIP = _flagship()


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the chip run uses. The CPU rehearsal in the tests shrinks
    these; the program has no option for it."""

    cpu_tasks: int = 300
    # BASELINE.json's north-star scheduling problem
    sched_nodes: int = 10_000
    sched_tasks: int = 1_000_000
    native_sample: int = 8192
    # BASELINE.json configs[0], as examples/eval_01_pi_tasks.py runs it
    pi_tasks: int = 10_000
    pi_samples: int = 10_000
    model: dict = dataclasses.field(default_factory=lambda: dict(FLAGSHIP))
    batch: int = 4
    seq: int = 2048
    train_steps: int = 5
    attn_shape: tuple = (2, 2048, 4, 128)      # [B, S, N, H]
    serve_seq: int = 128
    serve_requests: int = 8


_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class _CompileMeter:
    """Backend-compile seconds and persistent-cache hits since the last
    ``take()``, from jax's own monitoring events (every thread's)."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event, secs, **_kw):
        if event == _BACKEND_COMPILE:
            self.seconds += secs

    def _on_event(self, event, **_kw):
        if event == _CACHE_HIT:
            self.hits += 1

    def take(self) -> dict:
        out = {"compile_s": round(self.seconds, 2), "cache_hits": self.hits}
        self.seconds, self.hits = 0.0, 0
        return out


def _emit(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, "ok": True, **facts}), flush=True)


def _shm_segments() -> list:
    return sorted(f for f in os.listdir("/dev/shm") if f.startswith("rtpu_"))


def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# --------------------------------------------------------------------------
# device
# --------------------------------------------------------------------------

def phase_device(chips: int):
    """The accelerator as jax reports it, or no run at all."""
    import jax

    from ray_tpu._private.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    devices = jax.devices()
    platforms = sorted({d.platform for d in devices})
    if platforms != ["tpu"]:
        raise SystemExit(f"chip_smoke: jax found no TPU (platforms "
                         f"{platforms}); nothing was run")
    if len(devices) != chips:
        raise SystemExit(f"chip_smoke: asked for {chips} chip(s), jax "
                         f"reports {len(devices)}")
    _emit("device", kind=devices[0].device_kind, count=len(devices),
          compile_cache=cache_dir,
          cache_placed_by_env=bool(
              os.environ.get("JAX_COMPILATION_CACHE_DIR")))
    return devices


# --------------------------------------------------------------------------
# runtime
# --------------------------------------------------------------------------

def phase_runtime(sz: Sizes, devices) -> dict:
    """init() detects the chips; CPU work runs in child processes that
    stay off them; TPU-demand work and device objects stay in this
    process; shutdown leaves nothing behind."""
    import jax
    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu._private.worker import global_worker

    platform = devices[0].platform
    ray_tpu.init()
    resources = ray_tpu.cluster_resources()
    assert resources.get("TPU") == float(len(devices)), resources

    @ray_tpu.remote
    def square(x):
        return x * x

    @ray_tpu.remote
    def child_view():
        import jax
        return os.getpid(), jax.devices()[0].platform

    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.total = 0

        def add(self, k):
            self.total += k
            return self.total

        def pid(self):
            return os.getpid()

    n = sz.cpu_tasks
    assert ray_tpu.get([square.remote(i) for i in range(n)]) == \
        [i * i for i in range(n)]
    child_pid, child_platform = ray_tpu.get(child_view.remote())
    assert child_pid != os.getpid() and child_platform == "cpu", \
        (child_pid, child_platform)
    counter = Counter.remote()
    sums = ray_tpu.get([counter.add.remote(k) for k in range(100)])
    assert sums == list(np.cumsum(np.arange(100))), sums[-3:]
    assert ray_tpu.get(counter.pid.remote()) != os.getpid()

    a = np.random.RandomState(0).randn(512, 512).astype(np.float32)

    @ray_tpu.remote(num_tpus=1)
    def matmul(a):
        x = jnp.asarray(a, jnp.bfloat16)
        return os.getpid(), jax.jit(lambda x: x @ x.T)(x)

    lane_pid, prod = ray_tpu.get(matmul.remote(a))
    assert lane_pid == os.getpid()          # the driver's in-process lane
    assert isinstance(prod, jax.Array) and prod.dtype == jnp.bfloat16
    assert {d.platform for d in prod.devices()} == {platform}
    a_bf16 = np.asarray(jnp.asarray(a, jnp.bfloat16), np.float32)
    # bf16 inputs, f32 accumulation, bf16 result: one rounding, 2^-8
    assert _rel_err(prod, a_bf16 @ a_bf16.T) < 2.0 ** -7

    x = jax.device_put(jnp.arange(1 << 20, dtype=jnp.float32), devices[0])
    assert ray_tpu.get(ray_tpu.put(x)) is x     # the HBM buffer itself
    store = global_worker().device_store.stats()
    assert store["num_spilled_to_host"] == 0, store

    ray_tpu.shutdown()
    assert _shm_segments() == [], _shm_segments()
    return {"resources": {k: resources[k] for k in ("CPU", "TPU")},
            "cpu_tasks": n, "worker_platform": child_platform,
            "tpu_task_devices": sorted(str(d) for d in prod.devices()),
            "device_store": store}


# --------------------------------------------------------------------------
# scheduler
# --------------------------------------------------------------------------

_N_CLASSES = 8
_N_RES = 4      # CPU, TPU, memory, custom


def _cluster_arrays(rng, n_nodes):
    """A synthetic cluster (same draws for the same seed)."""
    total = np.zeros((n_nodes, _N_RES), np.float32)
    total[:, 0] = rng.choice([256, 256, 384], n_nodes)
    total[:, 1] = rng.choice([0, 4, 8, 8], n_nodes)
    total[:, 2] = rng.choice([256, 512, 1024], n_nodes)
    total[:, 3] = rng.choice([0, 0, 0, 1], n_nodes)
    used_frac = rng.uniform(0.0, 0.15, (n_nodes, 1)).astype(np.float32)
    avail = np.maximum(total * (1.0 - used_frac), 0.0)
    return avail, total, np.ones(n_nodes, bool)


def _demand_classes(rng, n_tasks):
    demands = np.zeros((_N_CLASSES, _N_RES), np.float32)
    demands[:, 0] = rng.choice([1, 1, 1, 2], _N_CLASSES)
    demands[:4, 1] = rng.choice([0, 1], 4)
    demands[:, 2] = rng.choice([1, 2, 4], _N_CLASSES)
    counts = np.bincount(rng.randint(0, _N_CLASSES, n_tasks),
                         minlength=_N_CLASSES).astype(np.int32)
    return demands, counts


def _placed_per_node(ds, n_nodes):
    """[K, N] tasks the kernel's returned assignments put on each node."""
    placed = np.zeros((_N_CLASSES, n_nodes), np.int64)
    for k in range(_N_CLASSES):
        np.add.at(placed[k], ds.order[k], ds.take_sorted[k])
        np.add.at(placed[k], ds.order2[k], ds.take2[k])
    return placed


def _check_dense_schedule(ds, avail, total, demands, counts) -> dict:
    """Plain numpy recomputation of the kernel's contract from its
    returned assignments alone (docs/scheduler.md)."""
    n_nodes = avail.shape[0]
    placed_kn = _placed_per_node(ds, n_nodes)
    assert (ds.local_take[:_N_CLASSES] == 0).all()      # no preferred node
    placed = placed_kn.sum(axis=1)
    usage = np.einsum("kn,kr->nr", placed_kn.astype(np.float64),
                      demands.astype(np.float64))
    over = float(np.max(usage - avail))
    assert over <= 1e-3, f"a node is oversubscribed by {over}"

    fenced = ds.fenced[:_N_CLASSES].astype(np.int64)
    admitted = ds.admitted[:_N_CLASSES].astype(np.int64)
    assert (placed == admitted).all(), (placed, admitted)
    with np.errstate(divide="ignore", invalid="ignore"):
        per_node = np.floor(np.min(
            np.where(demands[:, None, :] > 0,
                     total[None, :, :] / demands[:, None, :], np.inf),
            axis=2))                                     # [K, N]
    bound = per_node.sum(axis=1).astype(np.int64)
    assert (fenced == np.clip(counts - bound, 0, None)).all(), \
        (fenced, counts, bound)
    queued = counts - fenced - placed
    assert (queued >= 0).all(), queued
    # a class is left queued only once the nodes are full for it: what
    # still fits afterwards is float-rounding slack, never capacity
    left = avail - usage
    with np.errstate(divide="ignore", invalid="ignore"):
        still_fits = np.floor(np.min(
            np.where(demands[:, None, :] > 0,
                     (left[None, :, :] + 1e-6) / demands[:, None, :],
                     np.inf), axis=2)).clip(0).sum(axis=1)
    stranded = int(still_fits[queued > 0].sum())
    assert stranded <= n_nodes // 1000 + 1, stranded
    return {"placed": int(placed.sum()), "fenced": int(fenced.sum()),
            "left_queued": int(queued.sum()),
            "max_oversubscription": over, "stranded_slots": stranded}


def _native_placed(avail, total, alive, demands, sample) -> np.ndarray:
    """Per-class placed counts of the native C++ policy on ``sample``
    tasks cycling the classes."""
    import ctypes as ct

    from ray_tpu._private.native_loader import scheduler_lib

    lib = scheduler_lib()
    assert lib is not None, "native scheduler library failed to build"
    n_nodes = avail.shape[0]
    cls = np.arange(sample) % _N_CLASSES
    dem = np.ascontiguousarray(demands[cls], np.float32)
    preferred = np.full(sample, -1, np.int32)
    out_nodes = np.empty(sample, np.int32)
    out_inf = np.empty(sample, np.uint8)
    a = avail.copy()
    alive8 = alive.astype(np.uint8)
    f32p, u8p, i32p = (ct.POINTER(ct.c_float), ct.POINTER(ct.c_uint8),
                       ct.POINTER(ct.c_int32))
    lib.rtpu_hybrid_schedule(
        a.ctypes.data_as(f32p), total.ctypes.data_as(f32p),
        alive8.ctypes.data_as(u8p), n_nodes, _N_RES,
        dem.ctypes.data_as(f32p), preferred.ctypes.data_as(i32p), sample,
        ct.c_float(0.5), 1, ct.c_float(0.1), 42,
        out_nodes.ctypes.data_as(i32p), out_inf.ctypes.data_as(u8p))
    return np.bincount(cls[out_nodes >= 0], minlength=_N_CLASSES)


def _pi_sample(n: int, seed: int) -> int:
    rng = np.random.RandomState(seed)
    xy = rng.uniform(-1, 1, (n, 2))
    return int((np.einsum("ij,ij->i", xy, xy) <= 1.0).sum())


def phase_scheduler(sz: Sizes) -> dict:
    """The kernel at the north-star size against numpy and the native
    policy; then the live path with the policy init() installed. Leaves
    the runtime up for the train and serve phases."""
    import ray_tpu
    from ray_tpu._private.scheduler import tpu_policy
    from ray_tpu._private.worker import global_worker

    rng = np.random.RandomState(42)
    avail, total, alive = _cluster_arrays(rng, sz.sched_nodes)
    demands, counts = _demand_classes(rng, sz.sched_tasks)
    prefs = np.full(_N_CLASSES, -1, np.int32)
    pol = tpu_policy.TpuSchedulingPolicy()
    t0 = time.perf_counter()
    ds = pol.schedule_dense(avail.copy(), total, alive, demands, counts,
                            prefs)
    first_s = time.perf_counter() - t0       # compiles
    t0 = time.perf_counter()
    ds = pol.schedule_dense(avail.copy(), total, alive, demands, counts,
                            prefs)
    warm_s = time.perf_counter() - t0
    dense = _check_dense_schedule(ds, avail, total, demands, counts)

    sample_counts = np.bincount(np.arange(sz.native_sample) % _N_CLASSES,
                                minlength=_N_CLASSES).astype(np.int32)
    ds_s = pol.schedule_dense(avail.copy(), total, alive, demands,
                              sample_counts, prefs)
    kernel_placed = _placed_per_node(ds_s, sz.sched_nodes).sum(axis=1)
    native_placed = _native_placed(avail, total, alive, demands,
                                   sz.native_sample)
    assert (kernel_placed == native_placed).all(), \
        (kernel_placed, native_placed)

    ray_tpu.init()
    live = global_worker().node_group._policy._inner
    assert isinstance(live, tpu_policy.AdaptiveSchedulingPolicy), live
    assert live._cpu.name == "hybrid_native", \
        f"CPU policy is {live._cpu.name!r}: the native library did not load"
    tpu_policy._device_rt_thread.join(timeout=120)
    rt_s = tpu_policy._device_rt_s
    assert rt_s is not None and np.isfinite(rt_s), rt_s

    pi = ray_tpu.remote(_pi_sample)
    t0 = time.perf_counter()
    hits = ray_tpu.get([pi.remote(sz.pi_samples, i)
                        for i in range(sz.pi_tasks)])
    live_s = time.perf_counter() - t0
    assert hits == [_pi_sample(sz.pi_samples, i)
                    for i in range(sz.pi_tasks)]
    return {
        "dense": {"nodes": sz.sched_nodes, "tasks": sz.sched_tasks,
                  "first_call_s": round(first_s, 3),
                  "warm_call_s": round(warm_s, 4), **dense},
        "native_sample": {"tasks": sz.native_sample,
                          "placed": int(native_placed.sum())},
        "live": {"policy": live.name, "cpu_policy": live._cpu.name,
                 "device_round_trip_us": round(rt_s * 1e6, 1),
                 "tasks": sz.pi_tasks, "wall_s": round(live_s, 2),
                 "pi": 4.0 * sum(hits) / (sz.pi_tasks * sz.pi_samples),
                 "kernel_batches": live.num_kernel_batches,
                 "scan_batches": live.num_scan_batches},
    }


# --------------------------------------------------------------------------
# train
# --------------------------------------------------------------------------

# Flash output and gradients leave the kernel as bf16 (one rounding,
# 2^-8 relative) and its matmuls feed the MXU operands that are no
# longer bf16-exact (q·scale, the probabilities), which at the MXU's
# default precision is a second 2^-8. Gradients chain two such
# products. Against an f32 precision=HIGHEST reference that gives
# 2^-7 forward and 2^-6 backward, relative to the largest element —
# 60x and 120x the 2e-4 the f32 interpret-mode test holds.
_FWD_TOL = 2.0 ** -7
_BWD_TOL = 2.0 ** -6


def _check_flash_against_reference(shape, seed: int = 0) -> dict:
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import (
        flash_attention, mha_reference, repeat_kv)

    # K and V at a quarter of the query heads, the model's own ratio:
    # the kernel serves a KV group a step, the reference is given the
    # repeated K and V (its dk and dv are then the sums over a group)
    b, s, n, h = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v, w = (jax.random.normal(kk, (b, s, heads, h), jnp.float32)
                  for kk, heads in zip(keys, (n, max(1, n // 4),
                                              max(1, n // 4), n)))
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))

    def flash_loss(q, k, v):
        out = flash_attention(q, k, v)
        return jnp.sum(out.astype(jnp.float32) * w), out

    def ref_loss(q, k, v):
        with jax.default_matmul_precision("highest"):
            q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
            out = mha_reference(q, *repeat_kv(k, v, n))
        return jnp.sum(out * w), out

    grad = lambda f: jax.jit(jax.grad(f, argnums=(0, 1, 2), has_aux=True))
    f_grads, f_out = grad(flash_loss)(q, k, v)
    r_grads, r_out = grad(ref_loss)(q, k, v)
    errs = {"out": _rel_err(f_out, r_out)}
    for name, fg, rg in zip(("dq", "dk", "dv"), f_grads, r_grads):
        errs[name] = _rel_err(fg, rg)
    assert errs["out"] < _FWD_TOL, errs
    assert max(errs["dq"], errs["dk"], errs["dv"]) < _BWD_TOL, errs
    return {"shape": list(shape), "fwd_tol": _FWD_TOL, "bwd_tol": _BWD_TOL,
            **{f"err_{k}": float(f"{v:.3g}") for k, v in errs.items()}}


def _train_loop(config):
    """Runs inside the trainer's worker actor (TPU demand: a thread of
    this process). Reports one record per step, then one of facts."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import train
    from ray_tpu.models import (
        TransformerConfig, init_state, make_optimizer, make_train_step)

    cfg = TransformerConfig(**config["model"])
    tx = make_optimizer(warmup_steps=0, total_steps=100)
    state = init_state(jax.random.PRNGKey(0), cfg, tx)
    jax.block_until_ready(state)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(state.params))
    tokens = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (config["batch"], config["seq"]), np.int32))
    batch = {"tokens": tokens}
    step = make_train_step(cfg, tx, donate=True)
    lowered = step.lower(state, batch)
    has_kernel = "tpu_custom_call" in lowered.as_text()
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    for i in range(config["steps"]):
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch)
        loss = float(metrics["loss"])       # d2h: the step has retired
        train.report({"step": i + 1, "loss": loss,
                      "step_s": time.perf_counter() - t0})
    device = next(iter(jax.tree.leaves(state.params)[0].devices()))
    train.report({"facts": {
        "params": n_params, "has_tpu_custom_call": has_kernel,
        "step_compile_s": round(compile_s, 2),
        "state_platform": device.platform,
        "peak_bytes_in_use": (device.memory_stats() or {}).get(
            "peak_bytes_in_use")}})


def phase_train(sz: Sizes, devices) -> dict:
    from ray_tpu.train import JaxTrainer, ScalingConfig

    platform = devices[0].platform
    attention = _check_flash_against_reference(sz.attn_shape)
    result = JaxTrainer(
        _train_loop,
        train_loop_config={"model": sz.model, "batch": sz.batch,
                           "seq": sz.seq, "steps": sz.train_steps},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True)).fit()
    if result.error is not None:
        raise result.error
    *steps, last = result.metrics_history
    facts = last["facts"]
    assert [m["step"] for m in steps] == \
        list(range(1, sz.train_steps + 1)), steps
    losses = [m["loss"] for m in steps]
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    assert facts["state_platform"] == platform, facts
    # the kernel, not the interpreter, on a TPU — and only there
    assert facts["has_tpu_custom_call"] == (platform == "tpu"), facts
    if platform == "tpu":
        assert facts["peak_bytes_in_use"], facts
    step_s = sorted(m["step_s"] for m in steps[1:])
    return {"attention_vs_f32_reference": attention,
            "model": {k: sz.model[k] for k in
                      ("d_model", "n_layers", "n_heads", "d_ff",
                       "vocab_size")},
            "batch": sz.batch, "seq": sz.seq,
            "losses": [round(x, 4) for x in losses],
            "step_s_after_warmup_median": round(
                step_s[len(step_s) // 2], 4),
            **facts}


# --------------------------------------------------------------------------
# serve
# --------------------------------------------------------------------------

class _FlagshipForward:
    """The deployment: jits the model's forward at construction and
    answers a token list with the last position's logits."""

    def __init__(self, model: dict, seed: int):
        import jax

        from ray_tpu.models import TransformerConfig, forward, init_params

        cfg = TransformerConfig(**model)
        self.params = init_params(jax.random.PRNGKey(seed), cfg)
        self.forward = jax.jit(lambda p, t: forward(p, t, cfg)[:, -1])

    def __call__(self, tokens):
        import jax.numpy as jnp
        logits = self.forward(self.params,
                              jnp.asarray([tokens], jnp.int32))
        return np.asarray(logits[0]).tolist()

    def where(self):
        import jax
        return sorted({(os.getpid(), d.platform)
                       for leaf in jax.tree.leaves(self.params)
                       for d in leaf.devices()})


def phase_serve(sz: Sizes, devices) -> dict:
    import ray_tpu
    from ray_tpu import serve

    platform = devices[0].platform
    serve.start(http=True)          # worker-hosted ingress, the default
    app = serve.deployment(
        _FlagshipForward, name="flagship",
        ray_actor_options={"num_tpus": 1}).bind(sz.model, 0)
    handle = serve.run(app, timeout=600.0)
    assert ray_tpu.get(handle.where.remote(), timeout=600) == \
        [(os.getpid(), platform)]

    direct = _FlagshipForward(sz.model, 0)
    rng = np.random.RandomState(1)
    requests = [rng.randint(0, sz.model["vocab_size"], sz.serve_seq).tolist()
                for _ in range(2 * sz.serve_requests)]
    want = [np.asarray(direct(t), np.float32) for t in requests]
    assert all(np.isfinite(w).all() for w in want)

    by_handle = ray_tpu.get(
        [handle.remote(t) for t in requests[:sz.serve_requests]],
        timeout=600)
    host, port = serve.http_address()
    by_http = []
    for tokens in requests[sz.serve_requests:]:
        req = urllib.request.Request(
            f"http://{host}:{port}/flagship",
            data=json.dumps(tokens).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=600) as resp:
            assert resp.status == 200
            by_http.append(json.loads(resp.read()))
    got = [np.asarray(g, np.float32) for g in by_handle + by_http]
    assert all(g.shape == (sz.model["vocab_size"],) for g in got)
    # same program, same weights, same device: not a digit apart
    assert all(np.array_equal(g, w) for g, w in zip(got, want))
    serve.shutdown()
    return {"handle_requests": len(by_handle),
            "http_requests": len(by_http),
            "logits_shape": list(got[0].shape), "replica_on": platform,
            "ingress": f"{host}:{port}"}


# --------------------------------------------------------------------------
# --chips 4
# --------------------------------------------------------------------------

def _two_steps(cfg, mesh, tokens, devices):
    """Losses of two train steps (the second sees one update), and per
    device the bytes of train state placed there: counted from the
    arrays' shards, and as the device's allocator reports them."""
    import contextlib

    import jax

    from ray_tpu.models import init_state, make_optimizer, make_train_step

    tx = make_optimizer(warmup_steps=0, total_steps=100)
    with mesh if mesh is not None else contextlib.nullcontext():
        state = init_state(jax.random.PRNGKey(0), cfg, tx, mesh)
        jax.block_until_ready(state)
        placed = dict.fromkeys(devices, 0)
        for leaf in jax.tree.leaves(state):
            for shard in leaf.addressable_shards:
                placed[shard.device] += shard.data.nbytes
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in devices]
        step = make_train_step(cfg, tx, mesh)
        losses = []
        for _ in range(2):
            state, metrics = step(state, {"tokens": tokens})
            losses.append(float(metrics["loss"]))
    return losses, list(placed.values()), in_use


def phase_mesh(sz: Sizes, devices) -> dict:
    """One-device step, then the same step sharded over the real
    4-device mesh in two layouts."""
    import jax

    from ray_tpu.models import TransformerConfig
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh

    cfg = TransformerConfig(**sz.model)
    tokens = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (sz.batch, sz.seq), np.int32)
    t0 = time.perf_counter()
    want, placed_one, in_use_one = _two_steps(
        cfg, None, jax.device_put(tokens, devices[0]), devices)
    whole = placed_one[0]
    assert placed_one[1:] == [0] * (len(devices) - 1), placed_one
    out = {"layers": cfg.n_layers,
           "one_device": {"losses": want, "state_bytes": whole,
                          "memory_stats_bytes_in_use": in_use_one,
                          "seconds": round(time.perf_counter() - t0, 1)}}
    # fsdp x tp shards every matrix four ways; dp x tp only two
    for name, spec, share in (("fsdp2_tp2", MeshSpec(fsdp=2, tp=2), 1 / 4),
                              ("dp2_tp2", MeshSpec(dp=2, tp=2), 1 / 2)):
        t0 = time.perf_counter()
        got, placed, in_use = _two_steps(cfg, make_mesh(spec, devices),
                                         tokens, devices)
        # bf16 activations summed in another order across shards: a few
        # 2^-8 roundings on a loss of ~10
        assert np.allclose(got, want, rtol=2.0 ** -6), (name, got, want)
        # spread, not parked on device 0 (norm scales stay replicated)
        assert all(share * whole <= p < 1.01 * share * whole
                   for p in placed), (name, placed, whole)
        out[name] = {"losses": got, "state_bytes_per_device": placed,
                     "memory_stats_bytes_in_use": in_use,
                     "seconds": round(time.perf_counter() - t0, 1)}
    return out


def phase_replica_placement(devices) -> dict:
    """Where four ``num_tpus=1`` actors' arrays land. A finding, not a
    check: nothing maps a TPU lease to a device index yet."""
    import ray_tpu

    @ray_tpu.remote(num_tpus=1)
    class Replica:
        def __init__(self):
            import jax.numpy as jnp
            self.x = jnp.ones((1024, 1024))

        def device_ids(self):
            return sorted(d.id for d in self.x.devices())

    ray_tpu.init()
    replicas = [Replica.remote() for _ in devices]
    landed = ray_tpu.get([r.device_ids.remote() for r in replicas])
    ray_tpu.shutdown()
    return {"actor_device_ids": landed,
            "all_on_one_device": len({tuple(x) for x in landed}) == 1}


# --------------------------------------------------------------------------

def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    chips = parser.parse_args().chips
    if not __debug__:
        raise SystemExit("chip_smoke: its checks are asserts; run "
                         "without -O")

    devices = phase_device(chips)
    meter = _CompileMeter()
    sz = Sizes()

    def run(name, fn, *args):
        t0 = time.perf_counter()
        facts = fn(*args)
        _emit(name, seconds=round(time.perf_counter() - t0, 1),
              **facts, **meter.take())

    import ray_tpu
    from ray_tpu import serve
    try:
        if chips == 4:
            run("mesh", phase_mesh, sz, devices)
            run("replica_placement", phase_replica_placement, devices)
        else:
            run("runtime", phase_runtime, sz, devices)
            run("scheduler", phase_scheduler, sz)
            run("train", phase_train, sz, devices)
            run("serve", phase_serve, sz, devices)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    assert _shm_segments() == [], _shm_segments()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
