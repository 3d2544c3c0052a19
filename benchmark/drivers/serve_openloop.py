"""Driver `serve_openloop`: prompts over HTTP on a fixed schedule.

`serve.start(http=True)`, one deployment with `num_tpus=1` that jits the
program's `forward` and answers a prompt with the largest last-position
logits and their ids; requests go through the worker-hosted ingress from
this process's sender threads, which never touch jax. A request is timed
from when it was due. After the window the replica is shut down and the
plain reference reads a sample of the prompts.

Traffic file: `rate_per_s`, `prompt_tokens` (`median`, `sigma`, `min`,
`max` of a log-normal), `pad_to` (the program shapes), `top_k`,
`client_threads`, `check_requests`, `check_batch`, `trace_seconds`,
`schedule_seed`.
"""

from __future__ import annotations

import contextlib
import http.client
import json
import math
import statistics
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import check, trace, xplane
from benchmark.drivers.train_loop import program_config, reference_module

DRAIN_S = 60.0
DEPLOYMENT = "prefill"


def schedule(traffic: dict, seed: int, seconds: float) -> dict:
    """Due times, prompt lengths and token ids. The gaps are the
    quantiles of the exponential of that rate and the lengths those of
    the clipped log-normal, both shuffled by the traffic file's
    `schedule_seed`: every run offers the same arrivals and sizes in the
    same order (a queue's waits follow the order, and a median over one
    window moved by a tenth from order to order). The run's seed draws
    the token ids."""
    n = max(1, round(traffic["rate_per_s"] * seconds))
    quantile = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-quantile) / traffic["rate_per_s"]
    p = traffic["prompt_tokens"]
    normal = statistics.NormalDist()
    lengths = np.array([
        min(p["max"], max(p["min"], round(p["median"] * math.exp(
            p["sigma"] * normal.inv_cdf(q))))) for q in quantile])
    order = np.random.default_rng(traffic["schedule_seed"])
    gaps, lengths = order.permutation(gaps), order.permutation(lengths)
    due = np.cumsum(gaps)
    due = due * (seconds * n / (n + 1)) / due[-1]   # the last lies inside
    rng = np.random.default_rng([seed, 7])
    return {"due": due, "lengths": lengths,
            "tokens": [rng.integers(0, traffic["vocab"], int(k)).tolist()
                       for k in lengths]}


class Prefill:
    """The deployment. One prompt a call, padded to the next of `pad_to`
    (the flash kernel's blocks are 128): three programs, and no batching
    policy of its own."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        import jax

        from ray_tpu.models import forward
        ref = reference_module(config)
        sz = ref.Sizes.from_config(config)
        self.pad_to = sorted(traffic["pad_to"])
        cfg = program_config(config, self.pad_to[-1])
        top_k = traffic["top_k"]
        self.setup = {"replica_entered": time.perf_counter()}
        self.params = jax.block_until_ready(jax.jit(
            lambda k: ref.make_weights(k, sz))(ref.seed_key(seed)))
        self.setup["weights_on_device"] = time.perf_counter()
        self.forward = jax.jit(lambda p, t, last: jax.lax.top_k(
            forward(p, t, cfg)[0, last], top_k))
        self.spans = []
        for length in self.pad_to:          # compile each shape
            self({"id": -1, "tokens": [0] * length})
            self.setup[f"shape_{length}_ready"] = time.perf_counter()
        self.spans.clear()

    def __call__(self, request: dict) -> dict:
        entered = time.perf_counter()
        with xplane.span("replica_call"):
            tokens = request["tokens"]
            padded = next(n for n in self.pad_to if n >= len(tokens))
            row = np.zeros((1, padded), np.int32)
            row[0, :len(tokens)] = tokens
            with xplane.span("forward"):
                logits, ids = self.forward(self.params, row, len(tokens) - 1)
                logits, ids = np.asarray(logits), np.asarray(ids)
            answer = {"id": request["id"], "ids": ids.tolist(),
                      "logits": logits.tolist()}
        self.spans.append((request["id"], entered, time.perf_counter(),
                           padded))
        return answer

    def setup_marks(self) -> dict:
        return self.setup

    def take_spans(self) -> list:
        taken, self.spans = self.spans, []
        return taken

    def free(self) -> int:
        import jax
        for leaf in jax.tree.leaves(self.params):
            leaf.delete()
        self.params = None
        return 0


class Sender:
    """Posts requests from a few threads, one kept-alive connection
    each, and records when each was sent and answered."""

    def __init__(self, address, threads: int):
        self.address = address
        self.local = threading.local()
        self.pool = ThreadPoolExecutor(max_workers=threads,
                                       thread_name_prefix="bench-sender")
        self.records = {}

    def post(self, index: int, tokens: list) -> None:
        body = json.dumps({"id": index, "tokens": tokens}).encode()
        sent = time.perf_counter()
        record = {"sent": sent}
        try:
            conn = getattr(self.local, "conn", None)
            if conn is None:
                conn = self.local.conn = http.client.HTTPConnection(
                    *self.address, timeout=DRAIN_S)
            conn.request("POST", f"/{DEPLOYMENT}", body=body,
                         headers={"Content-Type": "application/json"})
            response = conn.getresponse()
            payload = response.read()
            record["done"] = time.perf_counter()
            record["status"] = response.status
            if response.status == 200:
                record["answer"] = json.loads(payload)
        except (OSError, http.client.HTTPException, ValueError) as e:
            record["error"] = repr(e)
            self.local.conn = None
        self.records[index] = record

    def close(self) -> None:
        self.pool.shutdown(wait=True)


def offer(sender: Sender, plan: dict, start: float, on_index=None) -> None:
    """Open loop: submit each request when it is due, whatever became
    of the earlier ones."""
    for i, due in enumerate(plan["due"]):
        if on_index is not None:
            on_index(i, due)
        wait = start + due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        sender.pool.submit(sender.post, i, plan["tokens"][i])


def reference_logits(ref, sz, weights, prompts: list, pad_to: list,
                     batch: int, mode: str = "f32") -> np.ndarray:
    """The reference's last-position logits for each prompt, `batch`
    prompts of one padded length a call (padding after a prompt cannot
    reach it under causal attention)."""
    import jax
    fn = jax.jit(lambda w, t, last: ref.logits_at(w, t, last, sz, mode))
    out = [None] * len(prompts)
    for padded in sorted(pad_to):
        lower = max([n for n in pad_to if n < padded], default=0)
        group = [i for i, p in enumerate(prompts) if lower < len(p) <= padded]
        for at in range(0, len(group), batch):
            part = group[at:at + batch]
            rows = np.zeros((batch, padded), np.int32)
            last = np.zeros((batch,), np.int32)
            for r, i in enumerate(part):
                rows[r, :len(prompts[i])] = prompts[i]
                last[r] = len(prompts[i]) - 1
            logits = np.asarray(fn(weights, rows, last), np.float64)
            for r, i in enumerate(part):
                out[i] = logits[r]
    return np.stack(out)


def control_answers(logits: np.ndarray, top_k: int) -> list:
    """What a server computing these logits would have answered."""
    ids = np.argsort(-logits, axis=-1)[:, :top_k]
    return [{"ids": row.tolist(), "logits": lg[row].tolist()}
            for row, lg in zip(ids, logits)]


def sample_to_check(plan: dict, finished: list, traffic: dict,
                    seed: int) -> list:
    """A sample of the finished requests drawn from the seed, the
    longest among them."""
    rng = np.random.default_rng([seed, 11])
    count = min(traffic["check_requests"], len(finished))
    picked = set(rng.choice(finished, size=count, replace=False).tolist())
    if finished:
        picked.add(max(finished, key=lambda i: plan["lengths"][i]))
    return sorted(picked)


def run(job: dict) -> dict:
    import jax

    import ray_tpu
    from ray_tpu import serve

    config, seed = job["config"], job["seed"]
    traffic = dict(job["traffic"], vocab=config["vocab_size"])
    seconds = job["seconds"]
    ref = reference_module(config)
    sz = ref.Sizes.from_config(config)
    plan = schedule(traffic, seed, seconds)
    warm = [[1] * n for n in traffic["pad_to"]]

    since = lambda: time.perf_counter() - job["process_start"]  # noqa: E731
    marks = {"driver_entered": since()}
    ray_tpu.init()
    marks["runtime_up"] = since()
    try:
        serve.start(http=True)
        marks["ingress_up"] = since()
        app = serve.deployment(
            Prefill, name=DEPLOYMENT,
            ray_actor_options={"num_tpus": 1}).bind(config, traffic, seed)
        handle = serve.run(app, timeout=1100.0)
        marks["replica_ready"] = since()
        marks.update({k: t - job["process_start"] for k, t in ray_tpu.get(
            handle.setup_marks.remote(), timeout=60).items()})
        sender = Sender(serve.http_address(), traffic["client_threads"])
        # every shape once through the whole path, then the window
        for i, tokens in enumerate(warm):
            sender.post(-1 - i, tokens)
            if sender.records[-1 - i].get("status") != 200:
                raise RuntimeError(f"warm-up request failed: "
                                   f"{sender.records[-1 - i]}")
        ray_tpu.get(handle.take_spans.remote(), timeout=60)
        setup_s = time.perf_counter() - job["process_start"]

        traced = None
        trace_from = seconds - traffic["trace_seconds"]
        with contextlib.ExitStack() as profile:
            def maybe_trace(_i, due):
                nonlocal traced
                if job["trace"] and traced is None and due >= trace_from:
                    traced = profile.enter_context(
                        xplane.profiled("trace_window"))

            start = time.perf_counter()
            offer(sender, plan, start, maybe_trace)
            sender.close()              # waits for every answer
        spans = ray_tpu.get(handle.take_spans.remote(), timeout=60)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.devices())
        ray_tpu.get(handle.free.remote(), timeout=60)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()

    records = [sender.records.get(i, {}) for i in range(len(plan["due"]))]
    finished = [i for i, r in enumerate(records) if r.get("status") == 200
                and r["answer"].get("id") == i]
    ttft = np.array([records[i]["done"] - start - plan["due"][i]
                     for i in finished]) * 1e3
    late = np.array([r["sent"] - start - plan["due"][i]
                     for i, r in enumerate(records) if "sent" in r]) * 1e3
    by_id = {s[0]: s for s in spans}
    inbound = np.array([by_id[i][1] - start - plan["due"][i]
                        for i in finished if i in by_id]) * 1e3
    outbound = np.array([records[i]["done"] - by_id[i][2]
                         for i in finished if i in by_id]) * 1e3

    # the check: the reference over a sample of the prompts as served
    sample = sample_to_check(plan, finished, traffic, seed)
    prompts = [plan["tokens"][i] for i in sample]
    weights = jax.jit(lambda k: ref.make_weights(k, sz))(ref.seed_key(seed))
    logits = reference_logits(ref, sz, weights, prompts,
                              traffic["pad_to"], traffic["check_batch"])
    numbers = check.serve_numbers(
        [records[i]["answer"] for i in sample], logits)
    extras = {}
    if "int8" in job.get("extras", ()):     # calibration only: the control
        extras["int8"] = check.serve_numbers(control_answers(
            reference_logits(ref, sz, weights, prompts, traffic["pad_to"],
                             traffic["check_batch"], "int8"),
            traffic["top_k"]), logits)
    for leaf in jax.tree.leaves(weights):
        leaf.delete()

    attempted = len(records)
    out = {
        "attempted": attempted, "failed": attempted - len(finished),
        "numbers": numbers, "extras": extras, "memory_peak_bytes": peak,
        "setup_marks": marks,
        "end_to_end": {
            "serve_ttft_p50_ms": float(np.percentile(ttft, 50)),
            "setup_s": setup_s},
        "facts": {
            "window_s": seconds,
            "prompt_tokens_answered": int(sum(
                plan["lengths"][i] for i in finished)),
            "ttft_ms": ttft, "late_ms": late, "inbound_ms": inbound,
            "outbound_ms": outbound},
    }
    if traced is not None:
        out["trace"] = trace.summary(traced["device_ops"], traced["spans"],
                                     "trace_window")
    return out
