"""Driver `train_loop`: a user's training loop under `JaxTrainer`.

One `_TrainWorker` actor on the driver's TPU lane builds the state and
the jitted step, takes the first three steps (the ones the reference
follows), hands the same state and step to the window, steps for
`--seconds`, then frees the state and runs the plain reference. All of
it is `measure`, which the loop calls inside the worker.

Traffic file: `batch`, `seq`, `mesh` (axis sizes for `MeshSpec`, absent
on one chip), `optimizer` (what `make_optimizer` is given, and the
constants it fixes, for the reference), `trace_skip_steps`,
`trace_steps`.
"""

from __future__ import annotations

import contextlib
import functools
import importlib
import time

import numpy as np

from benchmark import check, trace, xplane

REFERENCE_STEPS = 3


def run(job: dict) -> dict:
    import ray_tpu
    from ray_tpu.train import JaxTrainer, ScalingConfig

    job = dict(job, marks={"driver_entered": _since(job)})
    ray_tpu.init()
    job["marks"]["runtime_up"] = _since(job)
    try:
        result = JaxTrainer(
            _loop, train_loop_config=job,
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True)).fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        raise result.error
    return result.metrics_history[-1]["outcome"]


def _since(job: dict) -> float:
    """Seconds since the process started: the set-up's marks."""
    return time.perf_counter() - job["process_start"]


def _loop(job: dict) -> None:
    from ray_tpu import train
    train.report({"outcome": measure(job, train.report)})


def reference_module(config: dict):
    return importlib.import_module(
        f"benchmark.reference.{config['architecture']}")


def program_config(config: dict, seq: int):
    """The configuration file's HF keys as the program names them."""
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig
    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], max_seq_len=seq,
        rope_theta=float(config["rope_theta"]),
        dtype=jnp.dtype(config["torch_dtype"]), remat=True, use_flash=True)


def _adam_state(opt_state):
    import jax
    import optax
    found = [n for n in jax.tree.leaves(
        opt_state, is_leaf=lambda n: isinstance(n, optax.ScaleByAdamState))
        if isinstance(n, optax.ScaleByAdamState)]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0]


class Program:
    """The compiled step with its state: built once, driven from the
    seed through the first steps, and handed on to the window."""

    def __init__(self, job: dict):
        import jax

        from ray_tpu.models import make_optimizer, make_train_step
        from ray_tpu.models.training import TrainState, state_specs
        from ray_tpu.parallel.mesh import MeshSpec, make_mesh, tree_shardings

        config, traffic = job["config"], job["traffic"]
        self.ref = reference_module(config)
        self.sz = self.ref.Sizes.from_config(config)
        self.seed, self.traffic = job["seed"], traffic
        self.opt = traffic["optimizer"]
        model = program_config(config, traffic["seq"])
        tx = make_optimizer(
            lr=self.opt["lr"], weight_decay=self.opt["weight_decay"],
            warmup_steps=self.opt["warmup_steps"],
            total_steps=self.opt["total_steps"])
        self.mesh = None
        if traffic.get("mesh"):
            self.mesh = make_mesh(MeshSpec(**traffic["mesh"]), jax.devices())
        self.key = self.ref.seed_key(self.seed)

        def make_state(key):
            weights = self.ref.make_weights(key, self.sz)
            return TrainState(step=jax.numpy.zeros((), jax.numpy.int32),
                              params=weights, opt_state=tx.init(weights))

        shardings = None
        if self.mesh is not None:
            shape = jax.eval_shape(
                lambda k: self.ref.make_weights(k, self.sz), self.key)
            shardings = tree_shardings(
                self.mesh, state_specs(model, tx, shape))
        with self.placed():
            self.state = jax.jit(make_state, out_shardings=shardings)(
                self.key)
            self.step = make_train_step(model, tx, self.mesh, donate=True)
        self.steps_taken = 0

    def placed(self):
        return self.mesh if self.mesh is not None else \
            contextlib.nullcontext()

    def take_step(self) -> float:
        """The window's own call and feed: one step on the next rows."""
        tokens = self.ref.make_tokens(
            self.seed, self.steps_taken, self.traffic["batch"],
            self.traffic["seq"], self.sz.vocab)
        with self.placed():
            self.state, metrics = self.step(self.state, {"tokens": tokens})
        self.steps_taken += 1
        return float(metrics["loss"])       # d2h: the step has retired

    def first_steps(self, mark=lambda name: None) -> dict:
        """The readings the reference is held against: each step's loss,
        the first gradient as Adam got it (its first moment after one
        step, over 1 - b1), and each leaf's move over the three steps."""
        import jax
        losses = [self.take_step()]
        mark("first_step_done")
        moment = jax.jit(functools.partial(self.ref.leaf_norms, self.sz))(
            _adam_state(self.state.opt_state).mu)
        grad_norms = np.asarray(moment, np.float64) / (1 - self.opt["b1"])
        while self.steps_taken < REFERENCE_STEPS:
            losses.append(self.take_step())
        return {"losses": losses, "grad_norms": grad_norms,
                "change_norms": self.ref.change_norms(
                    self.sz, self.state.params, self.key)}

    def free(self) -> None:
        import jax
        for leaf in jax.tree.leaves(self.state):
            leaf.delete()
        self.state = None


def reference_readings(ref, sz, opt: dict, seed: int, batch: int, seq: int,
                       mode: str = "f32", keep_rows=None,
                       steps: int = REFERENCE_STEPS) -> dict:
    """The same three steps and readings by the plain reference, on all
    the chips of this process. `mode` and `keep_rows` make the control
    and the planted faults; with fewer `steps` there is no reading of
    the parameters' change."""
    import jax
    import jax.numpy as jnp
    shardings = ref.weight_shardings(sz, jax.devices())
    key = ref.seed_key(seed)
    weights = jax.jit(lambda k: ref.make_weights(k, sz),
                      out_shardings=shardings)(key)
    zeros = jax.jit(lambda w: jax.tree.map(jnp.zeros_like, w),
                    out_shardings=shardings)
    gradient = jax.jit(
        lambda w, t, rows: jax.value_and_grad(ref.loss)(w, t, sz, mode, rows),
        out_shardings=(None, shardings))
    update = jax.jit(functools.partial(ref.apply_update, sz=sz, opt=opt),
                     donate_argnums=(0, 1, 2),
                     out_shardings=(shardings, shardings, shardings, None))
    # always an array, so that the fault shares the reference's program
    row_weight = np.ones((batch, seq), np.float32)
    if keep_rows is not None:
        row_weight.reshape(-1)[int(keep_rows * batch * seq):] = 0.0
    losses, grad_norms, on_host = [], None, None
    for i in range(steps):
        tokens = ref.make_tokens(seed, i, batch, seq, sz.vocab)
        loss, grads = gradient(weights, tokens, row_weight)
        losses.append(float(loss))
        mu, nu = (zeros(weights), zeros(weights)) if on_host is None else \
            jax.device_put(on_host, (shardings, shardings))
        weights, mu, nu, norms = update(weights, mu, nu, grads, i)
        if i == 0:
            grad_norms = np.asarray(norms, np.float64)
        if i + 1 < steps:
            # weights, both moments, the gradient and its activations do
            # not fit the chips together: the moments wait on the host
            # while the next gradient is made
            on_host = jax.device_get((mu, nu))
            for leaf in jax.tree.leaves((mu, nu)):
                leaf.delete()
    out = {"losses": losses, "grad_norms": grad_norms}
    if steps == REFERENCE_STEPS:
        out["change_norms"] = ref.change_norms(sz, weights, key)
    for leaf in jax.tree.leaves((weights, mu, nu)):
        leaf.delete()
    return out


def measure(job: dict, report) -> dict:
    """Set-up, window and check. `report` is `train.report`."""
    import jax

    traffic, config, seconds = job["traffic"], job["config"], job["seconds"]
    marks = dict(job.get("marks", {}), loop_entered=_since(job))
    program = Program(job)
    marks["state_on_device"] = _since(job)
    readings = program.first_steps(
        lambda name: marks.__setitem__(name, _since(job)))
    devices = jax.devices()
    tokens_per_step = traffic["batch"] * traffic["seq"]
    setup_s = time.perf_counter() - job["process_start"]

    # a traced run profiles `trace_steps` whole steps inside the window
    trace_from = traffic["trace_skip_steps"] if job["trace"] else None
    trace_to = trace_from + traffic["trace_steps"] if job["trace"] else -1
    traced = None
    steps, start = 0, time.perf_counter()
    with contextlib.ExitStack() as profile:
        while steps < trace_to or time.perf_counter() - start < seconds:
            if steps == trace_from:
                traced = profile.enter_context(
                    xplane.profiled("trace_window"))
            with xplane.span("step"):
                loss = program.take_step()
            with xplane.span("report"):
                report({"step": program.steps_taken, "loss": loss})
            steps += 1
            if steps == trace_to:
                profile.close()
    window_s = time.perf_counter() - start
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    program.free()

    reference = reference_readings(
        program.ref, program.sz, program.opt, job["seed"],
        traffic["batch"], traffic["seq"])
    numbers = check.train_numbers(readings, reference)
    # calibration only: the control and the planted faults, each the
    # reference put in the program's place
    variants = {"int8": {"mode": "int8"}, "tp_partial": {"mode": "tp_partial"},
                "half_batch": {"keep_rows": 0.5}}
    extras = {name: check.train_numbers(reference_readings(
        program.ref, program.sz, program.opt, job["seed"], traffic["batch"],
        traffic["seq"], steps=job.get("extra_steps", REFERENCE_STEPS),
        **variants[name]), reference)
        for name in job.get("extras", ())}
    out = {
        "attempted": steps, "failed": 0, "numbers": numbers,
        "extras": extras,
        "memory_peak_bytes": peak,
        "end_to_end": {"train_tokens_per_s": steps * tokens_per_step
                       / window_s, "setup_s": setup_s},
        "setup_marks": marks,
        "facts": {"tokens_per_step": tokens_per_step},
    }
    if traced is not None:
        out["trace"] = trace.summary(traced["device_ops"], traced["spans"],
                                     "trace_window")
    return out
