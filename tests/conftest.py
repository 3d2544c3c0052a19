"""Test fixtures.

Mirrors the reference's test strategy (SURVEY.md §4): tests run against
a fake device mesh — jax on CPU with
``--xla_force_host_platform_device_count=8`` — the analog of the
reference's fake-resource test clusters, so multi-chip sharding logic
is exercised without TPU hardware.
"""

import os

# Tests run on the CPU: 8 virtual devices, 8 fake TPU resources.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("RAY_TPU_FAKE_TPUS", "8")
# Pin the memory watchdog to explicit-total mode with an effectively
# infinite denominator: REAL readings then never cross the threshold,
# so exact-count assertions (retries, oom_kills) can't flake on a
# loaded CI host. Watchdog tests inject readings via the chaos
# `pressure` action, which bypasses the measurement entirely — they
# are unaffected. Env var, so spawned raylet/GCS children inherit it.
os.environ.setdefault("RAY_TPU_memory_watchdog_total_bytes",
                      str(1 << 60))

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running smoke (sanitized chaos run, the "
        "docs/soak.md long soak); excluded by the tier-1 "
        "`-m 'not slow'` selection")


# ---------------------------------------------------------------------------
# graftsan: with RTPU_SANITIZE=1 every test answers for the violations
# it produced. Two channels are drained per test: the in-process ring
# (this process's own acquires) and the RTPU_SANITIZE_LOG artifact
# (children inherit the env, so raylet/GCS/worker processes report
# into the same file; a byte watermark scopes each test to its own
# window). A violation fails the test at teardown — hard, like the
# static pass, not a warning.
# ---------------------------------------------------------------------------

if os.environ.get("RTPU_SANITIZE") == "1":
    os.environ.setdefault("RTPU_SANITIZE_LOG",
                          os.path.join(os.environ.get("TMPDIR", "/tmp"),
                                       f"graftsan-{os.getpid()}.jsonl"))

    @pytest.fixture(autouse=True)
    def _graftsan_check():
        from ray_tpu.devtools.sanitizer import read_log, reporter

        rep = reporter()
        log = os.environ["RTPU_SANITIZE_LOG"]
        try:
            start = os.path.getsize(log)
        except OSError:
            start = 0
        before = len(rep.snapshot())
        yield
        fresh = rep.snapshot()[before:]
        logged, _ = read_log(log, start)
        seen = {(v.kind, v.key) for v in fresh}
        for rec in logged:
            if (rec.get("kind"), rec.get("key")) not in seen:
                seen.add((rec.get("kind"), rec.get("key")))
                fresh.append(rec)
        if fresh:
            def _render(v):
                if hasattr(v, "render"):
                    return v.render()
                out = [f"[{v.get('kind')}] (pid {v.get('pid')}) "
                       f"{v.get('message')}"]
                for label, stack in (v.get("stacks") or {}).items():
                    out.append(f"  --- {label} ---")
                    out.extend("  " + ln for ln in
                               str(stack).rstrip().splitlines())
                return "\n".join(out)

            pytest.fail(
                f"graftsan: {len(fresh)} concurrency-contract "
                "violation(s) during this test:\n\n"
                + "\n\n".join(_render(v) for v in fresh),
                pytrace=False)


@pytest.fixture
def ray_start_regular():
    """A small single-host runtime (2 process workers, 8 fake TPUs)."""
    import ray_tpu
    w = ray_tpu.init(num_cpus=4, num_tpus=8, max_process_workers=2)
    yield w
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """Multi-(logical-)node runtime: head + helper for adding nodes."""
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(head_num_cpus=4)
    yield cluster
    cluster.shutdown()
