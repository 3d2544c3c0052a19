"""``chip_smoke.py`` off the chip: it refuses to run there, and its
phases rehearse on the CPU at a tiny size (guide on-chip-measurement
§2.1) so that a chip call is not spent on a wrong path or argument."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "found no TPU" in proc.stderr


@pytest.mark.slow
def test_phases_rehearse_on_cpu_at_tiny_size(monkeypatch):
    """Every phase after the device check, through the same functions
    the chip run calls, on the 8 virtual CPU devices standing in for 8
    fake TPUs. Run it before a chip call:
    ``pytest tests/test_chip_smoke.py -m slow``."""
    import jax

    import chip_smoke
    import ray_tpu
    from ray_tpu import serve

    # a CPU host schedules with the native scan unless told otherwise;
    # the chip run gets the adaptive policy from detection
    monkeypatch.setenv("RAY_TPU_use_tpu_scheduler", "1")
    tiny = chip_smoke.Sizes(
        cpu_tasks=50, sched_nodes=64, sched_tasks=4000, native_sample=256,
        pi_tasks=200, pi_samples=100,
        model=dict(vocab_size=256, d_model=128, n_layers=2, n_heads=2,
                   n_kv_heads=2, d_ff=256, max_seq_len=128, remat=False,
                   use_flash=True),
        batch=2, seq=128, attn_shape=(1, 256, 2, 128), serve_seq=16,
        serve_requests=2)
    devices = jax.devices()
    ray_tpu.shutdown()
    try:
        runtime = chip_smoke.phase_runtime(tiny, devices)
        assert runtime["worker_platform"] == "cpu"
        sched = chip_smoke.phase_scheduler(tiny)
        assert sched["live"]["cpu_policy"] == "hybrid_native"
        train = chip_smoke.phase_train(tiny, devices)
        assert len(train["losses"]) == tiny.train_steps
        served = chip_smoke.phase_serve(tiny, devices)
        assert served["http_requests"] == tiny.serve_requests
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    mesh = chip_smoke.phase_mesh(tiny, devices[:4])
    assert set(mesh) >= {"one_device", "fsdp2_tp2", "dp2_tp2"}
    placement = chip_smoke.phase_replica_placement(devices[:4])
    assert len(placement["actor_device_ids"]) == 4
