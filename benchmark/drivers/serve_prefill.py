"""Driver `serve_prefill`: `serve_openloop`'s run for any architecture
the program's `config_from_hf` reads.

The same open loop over HTTP (`serve_openloop`'s schedule, sender and
sample are imported, not copied), one deployment with `num_tpus=1`. What
differs is the deployment: it builds the program's configuration with
`ray_tpu.models.config_from_hf`, holds the weights in the
configuration's `torch_dtype`, asks the forward for the last position's
logits alone, and reads the rows each held expert was given back with
them (the program's `model.moe.route` record). `facts` holds what
`serve_openloop` gives and, for readers that count work from facts,
`forwards`: the padded and real length and the routed rows of every
forward of the window, in the order the replica ran them.

Traffic file: as `serve_openloop`'s.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from benchmark import check, trace, xplane
from benchmark.drivers.serve_openloop import (
    DEPLOYMENT, Sender, control_answers, offer, reference_logits,
    sample_to_check, schedule)
from benchmark.drivers.train_loop import reference_module


COMPILE_S = 900.0


class Prefill:
    """The deployment. One prompt a call, padded to the next of
    `pad_to`: one program a length. The constructor makes the weights;
    the driver then asks for each program in a call of its own
    (`compile`), because the controller gives a replica 120 s to be
    constructed and four programs of this size take longer cold."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        import jax

        from ray_tpu.models import config_from_hf, forward_with_stats
        ref = reference_module(config)
        sz = ref.Sizes.from_config(config)
        self.pad_to = sorted(traffic["pad_to"])
        cfg = dataclasses.replace(
            config_from_hf(config, self.pad_to[-1]), use_flash=True,
            remat=False)
        self.top_k_experts, self.vocab = cfg.expert_top_k, cfg.vocab_size
        top_k = traffic["top_k"]
        self.setup = {"replica_entered": time.perf_counter()}
        self.params = jax.block_until_ready(jax.jit(
            lambda k: ref.make_weights(k, sz))(ref.seed_key(seed)))
        self.setup["weights_on_device"] = time.perf_counter()

        def answer(p, t, last):
            logits, stats = forward_with_stats(p, t, cfg,
                                               logit_positions=last)
            return jax.lax.top_k(logits[0], top_k), stats["moe_rows"]

        self.forward = jax.jit(answer)
        self.spans = []

    def compile(self, length: int) -> float:
        """One forward at that padded length; -> when it was ready."""
        self({"id": -1, "tokens": [0] * length})
        self.spans.clear()
        return time.perf_counter()

    def __call__(self, request: dict) -> dict:
        import jax

        from ray_tpu.ops.moe import record_route
        from ray_tpu.util import tracing
        entered = time.perf_counter()
        with xplane.span("replica_call"):
            tokens = request["tokens"]
            padded = next(n for n in self.pad_to if n >= len(tokens))
            # padding is routed like any other row: distinct ids, so
            # that it spreads over the experts as real tokens do and
            # does not pile onto the four that one id would choose
            row = np.arange(padded, dtype=np.int32)[None] % self.vocab
            row[0, :len(tokens)] = tokens
            last = np.array([len(tokens) - 1], np.int32)
            began = time.perf_counter_ns()
            with xplane.span("forward"):
                (logits, ids), rows = jax.device_get(
                    self.forward(self.params, row, last))
            record_route(rows, padded, self.top_k_experts, began,
                         time.perf_counter_ns(), tracing.current_request())
            answer = {"id": request["id"], "ids": ids.tolist(),
                      "logits": logits.tolist()}
        self.spans.append((request["id"], entered, time.perf_counter(),
                           padded, len(tokens), int(rows.sum()),
                           int((rows > 0).sum())))
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


def forward_facts(spans: list) -> list:
    """The replica's record of each forward, as readers take it."""
    return [{"id": s[0], "padded": s[3], "real": s[4], "rows_held": s[5],
             "experts_hit": s[6]} for s in spans]


def run(job: dict) -> dict:
    import jax

    # before any process is started: a program without the layer
    # pattern cannot run this cell, and says so at once
    from ray_tpu.models import config_from_hf, forward_with_stats  # noqa: F401

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
        for length in sorted(traffic["pad_to"]):    # compile each shape
            marks[f"shape_{length}_ready"] = ray_tpu.get(
                handle.compile.remote(length),
                timeout=COMPILE_S) - job["process_start"]
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
            "outbound_ms": outbound,
            "forwards": forward_facts(spans)},
    }
    if traced is not None:
        out["trace"] = trace.summary(traced["device_ops"], traced["spans"],
                                     "trace_window")
    return out
