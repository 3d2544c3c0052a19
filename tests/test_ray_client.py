"""Proxied remote driver (``rtpu://`` — the Ray Client analog).

Reference: ``python/ray/util/client/`` + ``server/proxier.py``
[UNVERIFIED — mount empty, SURVEY.md §0]. A client-server process
joins the cluster as a driver; thin clients drive the full API over
one token-gated connection.
"""

import os
import subprocess
import sys
import time

import pytest

import ray_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def client_cluster(tmp_path):
    """GCS + client-server processes; yields (rtpu_addr, token,
    add_raylet) — the helper spawns extra cluster raylets (all reaped
    at teardown)."""
    from ray_tpu._private import rpc as _rpc
    from ray_tpu._private.config import get_config
    from ray_tpu._private.gcs_server import spawn_gcs_process

    session = os.urandom(4).hex()
    token = _rpc.ensure_session_token(session)
    gcs_proc, gcs_addr = spawn_gcs_process(session,
                                           get_config().serialize(),
                                           persist=True)
    port_file = str(tmp_path / "cs.addr")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + env.get("PYTHONPATH", "").split(os.pathsep))
    env["RTPU_SESSION_TOKEN"] = token
    env["JAX_PLATFORMS"] = "cpu"
    cs_proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.client_server",
         "--address", f"{gcs_addr[0]}:{gcs_addr[1]}",
         "--port-file", port_file,
         "--config", get_config().serialize()],
        env=env, start_new_session=True)
    deadline = time.monotonic() + 60
    addr = None
    while time.monotonic() < deadline:
        if os.path.exists(port_file):
            addr = open(port_file).read().strip()
            break
        assert cs_proc.poll() is None, "client server died"
        time.sleep(0.05)
    assert addr, "client server never reported its address"
    raylet_procs = []

    def add_raylet(resources):
        from ray_tpu._private.ids import NodeID
        from ray_tpu._private.raylet_server import spawn_raylet_process
        proc, _ = spawn_raylet_process(
            f"{session}r{len(raylet_procs) + 1}", NodeID.from_random(),
            resources, gcs_addr=gcs_addr, max_process_workers=2)
        raylet_procs.append(proc)
        return proc

    yield f"rtpu://{addr}", token, add_raylet
    ray_tpu.shutdown()
    for proc in [*raylet_procs, cs_proc, gcs_proc]:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


def test_client_tasks_objects_wait(client_cluster):
    addr, _token, _add_raylet = client_cluster
    w = ray_tpu.init(address=addr)
    assert type(w).__name__ == "ClientWorker"

    @ray_tpu.remote
    def add(a, b):
        return a + b

    # tasks + chained refs through the proxy
    r1 = add.remote(1, 2)
    r2 = add.remote(r1, 10)
    assert ray_tpu.get(r2, timeout=60) == 13

    # put/get round trip (driver-owned object)
    ref = ray_tpu.put({"k": [1, 2, 3]})
    assert ray_tpu.get(ref, timeout=30) == {"k": [1, 2, 3]}

    # wait
    ready, not_ready = ray_tpu.wait([add.remote(5, 5)], num_returns=1,
                                    timeout=30)
    assert len(ready) == 1 and not not_ready
    assert ray_tpu.get(ready[0]) == 10

    # error propagation
    @ray_tpu.remote
    def boom():
        raise ValueError("client boom")

    with pytest.raises(Exception, match="client boom"):
        ray_tpu.get(boom.remote(), timeout=30)


def test_client_actors(client_cluster):
    addr, _token, _add_raylet = client_cluster
    ray_tpu.init(address=addr)

    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def incr(self, k=1):
            self.n += k
            return self.n

    c = Counter.remote()
    assert ray_tpu.get(c.incr.remote(), timeout=60) == 1
    assert ray_tpu.get(c.incr.remote(5), timeout=30) == 6
    ray_tpu.kill(c)


def test_client_detached_actor_across_connections(client_cluster):
    """Detached actors through the rtpu:// thin driver: connection A
    creates a named detached actor hosted on a cluster raylet and
    disconnects; connection B finds it by name with state intact
    (reference: Ray Client + detached actor composition)."""
    addr, _token, add_raylet = client_cluster
    ray_tpu.init(address=addr)
    # Baseline BEFORE the raylet exists: the proxied driver's own head
    # node already contributes CPUs, so "total >= 2" alone would pass
    # before the new node attaches (flake). Poll for the DELTA.
    baseline = ray_tpu.cluster_resources().get("CPU", 0)
    add_raylet({"CPU": 2.0})
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        if ray_tpu.cluster_resources().get("CPU", 0) >= baseline + 2:
            break
        time.sleep(0.1)
    else:
        raise AssertionError("added raylet never became visible")

    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def incr(self):
            self.n += 1
            return self.n

    Counter.options(name="cli_det", lifetime="detached",
                    num_cpus=1).remote()
    h = ray_tpu.get_actor("cli_det")
    assert ray_tpu.get(h.incr.remote(), timeout=120) == 1
    assert ray_tpu.get(h.incr.remote(), timeout=60) == 2
    ray_tpu.shutdown()       # connection A gone

    ray_tpu.init(address=addr)   # connection B
    h2 = ray_tpu.get_actor("cli_det")
    assert ray_tpu.get(h2.incr.remote(), timeout=120) == 3
    ray_tpu.kill(h2)
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        try:
            ray_tpu.get_actor("cli_det")
        except ValueError:
            break
        time.sleep(0.2)
    else:
        raise AssertionError("name not freed after kill")
    ray_tpu.shutdown()
