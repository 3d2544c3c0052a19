"""Round-5 feature tour: detached actor services, elastic training,
async-actor call cancellation, a multi-slice mesh, and a rolling serve
redeploy — every plane of that round, driven end to end.

    python examples/round5_feature_tour.py

Runs against an in-process cluster; ~1 minute. The detached-actor
section additionally works across real drivers — see
``tests/test_detached.py`` for the two-process version.
"""

import json
import os
import tempfile
import threading
import time

import ray_tpu


def detached_actor_service() -> None:
    """A named, detached key-value service: survives its creating
    scope; any later code (or driver) reaches it by name."""
    @ray_tpu.remote
    class KV:
        def __init__(self):
            self.d = {}

        def put(self, k, v):
            self.d[k] = v
            return len(self.d)

        def get(self, k):
            return self.d.get(k)

    KV.options(name="kv", lifetime="detached").remote()
    h = ray_tpu.get_actor("kv")                 # reach it BY NAME
    ray_tpu.get(h.put.remote("model_version", 7))
    assert ray_tpu.get(h.get.remote("model_version")) == 7
    print("detached actor: named service up, state", 7)
    ray_tpu.kill(h)


def async_cancel() -> None:
    """ray_tpu.cancel on an async-actor call: the coroutine cancels at
    its next await; the actor stays healthy."""
    @ray_tpu.remote
    class Worker:
        async def slow(self):
            import asyncio
            await asyncio.sleep(60)
            return "never"

        async def quick(self):
            return "ok"

    a = Worker.remote()
    ref = a.slow.remote()
    time.sleep(0.3)
    ray_tpu.cancel(ref)
    from ray_tpu.exceptions import TaskCancelledError
    try:
        ray_tpu.get(ref, timeout=30)
    except TaskCancelledError:
        pass
    assert ray_tpu.get(a.quick.remote(), timeout=30) == "ok"
    print("async cancel: 60s coroutine cancelled, actor healthy")
    ray_tpu.kill(a)


def elastic_training() -> None:
    """ScalingConfig(min_workers=...): the gang continues from the
    last checkpoint at whatever size fits (plain run here — the
    node-loss shrink/regrow version is tests/test_elastic.py)."""
    from ray_tpu.train import (DataParallelTrainer, RunConfig,
                               ScalingConfig)

    def loop(config):
        from ray_tpu import train
        ctx = train.get_context()
        start = 0
        ck = train.get_checkpoint()
        if ck is not None:
            with open(os.path.join(ck.path, "state.json")) as f:
                start = json.load(f)["epoch"] + 1
        for epoch in range(start, 3):
            d = tempfile.mkdtemp(prefix="tour_ck_")
            with open(os.path.join(d, "state.json"), "w") as f:
                json.dump({"epoch": epoch}, f)
            train.report({"epoch": epoch,
                          "world": ctx.get_world_size()},
                         checkpoint=train.Checkpoint.from_directory(d))

    result = DataParallelTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2, min_workers=1),
        run_config=RunConfig()).fit()
    assert result.error is None
    print("elastic train:", result.metrics)


def multi_slice_mesh() -> None:
    """'fsdp within slice, dp across slices' as one constructor call;
    the cross axis's collectives ride DCN on real multi-slice pods."""
    import jax

    from ray_tpu.parallel import MeshSpec, SliceTopology, make_slice_mesh

    n = len(jax.devices())
    if n < 2:
        print("multi-slice: skipped (1 device)")
        return
    topo = SliceTopology(num_slices=2, inner=MeshSpec(fsdp=n // 2),
                         cross="dp")
    sm = make_slice_mesh(topo, allow_split_slices=True)
    print("multi-slice:", sm.describe())


def rolling_redeploy() -> None:
    """serve.run over an existing deployment rolls replicas one
    health-gated step at a time; in-flight requests drain."""
    from ray_tpu import serve

    @serve.deployment(num_replicas=2)
    class V:
        def __init__(self, tag):
            self.tag = tag

        def __call__(self, i):
            return (self.tag, i)

    h = serve.run(V.bind("v1"), name="svc")
    errors = []
    stop = threading.Event()

    def spam():
        i = 0
        while not stop.is_set():
            try:
                ray_tpu.get(h.remote(i), timeout=60)
            except Exception as e:     # noqa: BLE001
                errors.append(e)
            i += 1

    t = threading.Thread(target=spam)
    t.start()
    serve.run(V.options(num_replicas=2).bind("v2"), name="svc")
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        st = serve.status()["svc"]
        if not st["updating"] and st["draining_replicas"] == 0:
            break
        time.sleep(0.1)
    stop.set()
    t.join(timeout=30)
    assert not errors, errors[:2]
    tag = ray_tpu.get(h.remote(0))[0]
    print(f"rolling redeploy: zero dropped requests, now serving {tag}")
    serve.shutdown()


if __name__ == "__main__":
    ray_tpu.init(num_cpus=8, max_process_workers=3)
    detached_actor_service()
    async_cancel()
    elastic_training()
    multi_slice_mesh()
    rolling_redeploy()
    ray_tpu.shutdown()
    print("round-5 tour complete")
