"""Cluster lifecycle via the CLI: start --head, start --address, a
driver joining with init(address=...), status, stop.

Reference analog: ``ray start/stop/status`` (``python/ray/scripts/
scripts.py``) [UNVERIFIED — mount empty, SURVEY.md §0].
"""

import os
import re
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _cli(*args, timeout=60):
    return subprocess.run(
        [sys.executable, "-m", "ray_tpu", *args],
        capture_output=True, text=True, env=_env(), timeout=timeout)


def test_cli_bootstrap_join_and_stop(tmp_path):
    session = f"boot{os.getpid()}"
    head = _cli("start", "--head", "--session", session)
    assert head.returncode == 0, head.stderr
    m = re.search(r"at (\d+\.\d+\.\d+\.\d+:\d+)", head.stdout)
    assert m, head.stdout
    addr = m.group(1)
    try:
        node = _cli("start", "--address", addr, "--session", session,
                    "--num-cpus", "2", "--resources", '{"BOOT": 1}')
        assert node.returncode == 0, node.stderr
        assert "raylet started" in node.stdout

        status = _cli("status", "--address", addr)
        assert status.returncode == 0, status.stderr
        assert "BOOT" in status.stdout
        assert "True" in status.stdout

        # a driver process joins the cluster and runs a task on the
        # CLI-started raylet
        driver = tmp_path / "driver.py"
        driver.write_text(f"""
import ray_tpu
w = ray_tpu.init(address="{addr}", num_cpus=1, max_process_workers=1)

@ray_tpu.remote(num_cpus=1, resources={{"BOOT": 1}})
def whereami():
    import os
    return os.getpid()

pid = ray_tpu.get(whereami.remote(), timeout=120)
import os
assert pid != os.getpid()
print("JOIN-OK", pid)
ray_tpu.shutdown()
""")
        run = subprocess.run([sys.executable, str(driver)],
                             capture_output=True, text=True, env=_env(),
                             timeout=180)
        assert run.returncode == 0, run.stderr[-2000:]
        assert "JOIN-OK" in run.stdout
    finally:
        stop = _cli("stop", "--session", session)
        assert "terminated" in stop.stdout
