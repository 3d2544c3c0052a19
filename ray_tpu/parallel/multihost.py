"""Multi-host (multi-process) device meshes — the DCN plane.

Reference: the reference scales across hosts with NCCL/Gloo process
groups and gRPC control (SURVEY.md §5 [UNVERIFIED — mount empty]).
TPU-native, cross-host device collectives are not a separate backend:
``jax.distributed`` connects the per-host runtimes, every process sees
the GLOBAL device set, and the same jitted SPMD programs run on meshes
spanning hosts — XLA routes collectives over ICI within a slice and
the cross-host plane (DCN; Gloo/TCP on CPU test rigs) between them.
NCCL never appears.

Usage (same code on every host)::

    from ray_tpu.parallel import multihost
    multihost.initialize(coordinator_address="10.0.0.1:7777",
                         num_processes=4, process_id=rank)
    mesh = multihost.global_mesh(MeshSpec.auto())   # spans all hosts
    # pjit/shard_map programs over `mesh` now collect across hosts

Tests simulate hosts with processes on one machine, each holding a
virtual CPU device slab (``spawn_local_group``) — the same topology a
TPU pod presents, minus the bandwidth.
"""

from __future__ import annotations

import os
import subprocess
import sys
from typing import List, Optional, Sequence, Tuple

from ray_tpu.parallel.mesh import MeshSpec, make_mesh

_initialized = False


def initialize(coordinator_address: str, num_processes: int,
               process_id: int) -> None:
    """Connect this process into the multi-host runtime. Call before
    any jax device access; idempotent per process."""
    global _initialized
    if _initialized:
        return
    import jax
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id)
    _initialized = True


def is_initialized() -> bool:
    return _initialized


def global_device_count() -> int:
    import jax
    return len(jax.devices())


def local_device_count() -> int:
    import jax
    return len(jax.local_devices())


def process_index() -> int:
    import jax
    return jax.process_index()


def global_mesh(spec: Optional[MeshSpec] = None):
    """A mesh over the GLOBAL device set (all hosts). With no spec,
    data-parallel over everything."""
    import jax
    devs = jax.devices()
    if spec is None:
        spec = MeshSpec(fsdp=len(devs))
    return make_mesh(spec, devs)


def host_local_batch(global_batch, mesh, spec):
    """Place this host's shard of a globally-sharded array: each
    process provides its local rows and jax assembles the global
    array (the standard multi-host input pipeline contract)."""
    import jax
    from jax.sharding import NamedSharding
    sharding = NamedSharding(mesh, spec)
    return jax.make_array_from_process_local_data(sharding, global_batch)


def spawn_local_group(script: str, num_processes: int,
                      devices_per_process: int, port: int = 0,
                      timeout: float = 300.0,
                      extra_args: Optional[Sequence[str]] = None
                      ) -> List[subprocess.CompletedProcess]:
    """Test harness: run ``script`` in N processes, each a simulated
    host with its own virtual CPU device slab, connected through a
    coordinator — the fake-pod analog of the reference's multi-node
    test clusters."""
    import socket
    if port == 0:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
    coord = f"127.0.0.1:{port}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(
        f"--xla_force_host_platform_device_count={devices_per_process}")
    env["XLA_FLAGS"] = " ".join(flags)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    procs = []
    for pid in range(num_processes):
        procs.append(subprocess.Popen(
            [sys.executable, script, coord, str(num_processes), str(pid),
             *(extra_args or ())],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    done = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
            raise RuntimeError(
                f"multihost member timed out; output:\n{out}")
        done.append(subprocess.CompletedProcess(p.args, p.returncode,
                                                out, None))
    return done
