"""Each driver end to end at a tiny size on the CPU, past the look for a
chip; the runner's refusal to print a CPU number; a fourth cell added as
data; and the timed path broken underneath, which has to read as not
correct under the limits the cells are held to."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmark import run
from benchmark.drivers import serve_openloop

ROOT = run.ROOT
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# float32 compute: at these widths bfloat16's rounding is a larger share
# of every number than at the cells' own, and the limits are the cells'
TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "vocab_size": 512,
        "torch_dtype": "float32"}
TRAIN, SERVE = "train_mistral7b_l3_s4096", "serve_mistral7b_l12_short"
MESH = "mesh"   # the train cell under the sharded traffic file (PERF.md 7)


def tiny_job(root, workload):
    job = run.load_job(root, TRAIN if workload == MESH else workload)
    if workload == MESH:
        with open(os.path.join(root, "benchmark", "traffic",
                               "pretrain_s4096_b2.json")) as f:
            job["traffic"] = json.load(f)
    job["config"].update(TINY)
    if job["traffic"]["driver"] == "train_loop":
        job["traffic"].update(batch=2, seq=128)
        if "mesh" in job["traffic"]:    # the tests' platform has 8 devices
            job["traffic"].update(batch=4, mesh={"dp": 2, "fsdp": 2, "tp": 2})
    else:
        job["traffic"].update(rate_per_s=20.0, check_requests=16,
                              check_batch=4)
    return job


def run_tiny(job, seed=5, seconds=2.0):
    return run.run_cell(job, CPU, seed, seconds, False)


@pytest.mark.parametrize("workload,metric", [
    (TRAIN, "train_tokens_per_s"), (MESH, "train_tokens_per_s"),
    (SERVE, "serve_ttft_p50_ms")])
def test_cell_end_to_end_at_a_tiny_size(workload, metric):
    result = run_tiny(tiny_job(ROOT, workload))
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) >= {metric, "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "checks"
    assert all(set(c) == {"value", "limit"}
               for c in result["checks"].values())
    json.dumps(result)


def test_no_chip_no_number(tmp_path):
    """On a CPU the command exits non-zero and prints no result; so it
    does in a directory with only BENCHMARK.json and the paths."""
    args = [sys.executable, "-m", "benchmark.run", "--workload", TRAIN,
            "--seed", "1", "--seconds", "1", "--trace", "0"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    here = subprocess.run(args, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=300)
    assert here.returncode != 0 and "found no TPU" in here.stderr
    assert here.stdout.strip() == ""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark")
    alone = subprocess.run(args, cwd=tmp_path, env=env, capture_output=True,
                           text=True, timeout=300)
    assert alone.returncode != 0 and alone.stdout.strip() == ""


def test_a_fourth_cell_is_data(tmp_path):
    """A new traffic file, its limits and a BENCHMARK.json entry in a
    copy: the runner takes the cell with no edit under benchmark/."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(tmp_path / "benchmark/traffic/pretrain_s4096.json") as f:
        traffic = json.load(f)
    traffic.update(batch=2, seq=64)
    (tmp_path / "benchmark/traffic/pretrain_s64.json").write_text(
        json.dumps(traffic))
    shutil.copy(tmp_path / f"benchmark/limits/{TRAIN}.json",
                tmp_path / "benchmark/limits/train_tiny_s64.json")
    manifest["workloads"].append({
        "name": "train_tiny_s64", "config": "mistral-7b-v0.1-l3",
        "traffic": "pretrain_s64", "chips": 1, "why": "a test's cell"})
    manifest["end_to_end"][0]["workloads"].append("train_tiny_s64")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    job = run.load_job(str(tmp_path), "train_tiny_s64")
    job["config"].update(TINY)
    result = run_tiny(job, seconds=1.0)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_schedule_is_a_function_of_the_seed_alone():
    traffic = dict(run.load_job(ROOT, SERVE)["traffic"], vocab=32000)
    a = serve_openloop.schedule(traffic, 2**31 + 5, 20.0)
    b = serve_openloop.schedule(traffic, 2**31 + 5, 20.0)
    c = serve_openloop.schedule(traffic, 6, 20.0)
    assert np.array_equal(a["due"], b["due"]) and a["tokens"] == b["tokens"]
    # every seed: the same arrivals and sizes in the same order, other ids
    assert np.array_equal(a["due"], c["due"])
    assert np.array_equal(a["lengths"], c["lengths"])
    assert a["tokens"] != c["tokens"]
    other = serve_openloop.schedule(dict(traffic, schedule_seed=1), 6, 20.0)
    assert not np.array_equal(other["due"], c["due"])
    assert sorted(other["lengths"]) == sorted(c["lengths"])
    assert len(a["due"]) == round(traffic["rate_per_s"] * 20.0)
    assert 0 < a["due"][0] and a["due"][-1] < 20.0
    lengths = a["lengths"]
    assert lengths.min() >= 16 and lengths.max() <= 512
    assert 110 <= np.median(lengths) <= 146
    assert all(len(t) == n for t, n in zip(a["tokens"], lengths))


# ---- the timed path broken underneath -------------------------------------

def _break_train_step(monkeypatch, wrap):
    from ray_tpu.models import training
    real = training.make_train_step

    def broken(cfg, tx, mesh=None, **kw):
        return wrap(real, cfg, tx, mesh, kw)

    monkeypatch.setattr(training, "make_train_step", broken)


def _state_unchanged(real, cfg, tx, mesh, kw):
    step = real(cfg, tx, mesh, **dict(kw, donate=False))
    return lambda state, batch: (state, step(state, batch)[1])


def _half_batch(real, cfg, tx, mesh, kw):
    import jax.numpy as jnp
    step = real(cfg, tx, mesh, batch_keys=("tokens", "loss_mask"), **kw)

    def run_step(state, batch):
        tokens = batch["tokens"]
        mask = jnp.zeros(tokens.shape, jnp.float32).at[
            :tokens.shape[0] // 2].set(1.0)
        return step(state, {"tokens": tokens, "loss_mask": mask})
    return run_step


def _exchange_left_out(real, cfg, tx, mesh, kw):
    """One tensor-parallel shard's partial sum, its partner's never
    added: half of the heads reach the output projection."""
    from ray_tpu.ops import make_attention_fn
    whole = make_attention_fn(mesh, impl="flash")

    def partial_sum(q, k, v, causal=True):
        out = whole(q, k, v)
        return out.at[:, :, out.shape[2] // 2:].set(0.0)
    return real(cfg, tx, mesh, attn_fn=partial_sum, **kw)


@pytest.mark.parametrize("workload,fault", [
    (TRAIN, _state_unchanged), (TRAIN, _half_batch),
    (MESH, _exchange_left_out)])
def test_a_broken_train_step_is_not_correct(monkeypatch, workload, fault):
    _break_train_step(monkeypatch, fault)
    result = run_tiny(tiny_job(ROOT, workload), seconds=0.5)
    assert not result["correct"], result["checks"]
    assert result["failed"] == 0


def test_an_altered_token_is_not_correct(monkeypatch):
    """The answer altered where it is produced: the replica's second
    token served as its first."""
    real = serve_openloop.Prefill.__call__

    def altered(self, request):
        answer = real(self, request)
        answer["ids"][0] = answer["ids"][1]
        return answer

    monkeypatch.setattr(serve_openloop.Prefill, "__call__", altered)
    result = run_tiny(tiny_job(ROOT, SERVE))
    assert not result["correct"], result["checks"]
