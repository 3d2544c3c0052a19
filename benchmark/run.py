"""Runs one cell of BENCHMARK.json and prints the contract's result line.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: it holds the cell's chips, sets up, warms up, measures for
`--seconds`, checks what the timed path produced against the plain
reference, and prints one JSON object as the last line of its standard
output. Without the chips the cell asks for it exits non-zero and prints
no result: there is no CPU number.

Everything that belongs to one cell is data found by the names in
BENCHMARK.json: `configs/<config>.json`, `traffic/<traffic>.json` (which
names its driver under `drivers/`), `limits/<cell>.json`, and one reader
`layer_metrics/<metric>.py` for each per-layer metric.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse                                             # noqa: E402
import importlib                                            # noqa: E402
import importlib.util                                       # noqa: E402
import json                                                 # noqa: E402
import os                                                   # noqa: E402
import sys                                                  # noqa: E402

from benchmark import check, costs                          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_job(root: str, workload: str) -> dict:
    """The cell, its configuration, traffic and limits, from the
    manifest at `root`; the data files lie where the manifest says."""
    manifest = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         f"BENCHMARK.json (has {sorted(cells)})")
    cell = cells[workload]
    config = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    bench_dir = os.path.join(root, os.path.dirname(os.path.dirname(
        config["file"])))
    return {
        "manifest": manifest, "cell": cell,
        "config": _json(os.path.join(root, config["file"])),
        "traffic": _json(os.path.join(
            bench_dir, "traffic", cell["traffic"] + ".json")),
        "limits": _json(os.path.join(
            bench_dir, "limits", workload + ".json"))["limits"],
        "layer_metrics_dir": os.path.join(bench_dir, "layer_metrics"),
    }


def metrics_of(manifest: dict, kind: str, workload: str) -> list:
    """The `end_to_end` or `per_layer` metrics this cell reports."""
    return [m for m in manifest[kind]
            if "workloads" not in m or workload in m["workloads"]]


def require_chips(chips: int) -> dict:
    """The accelerator as jax reports it, or no run at all."""
    import jax

    from ray_tpu._private.compile_cache import configure_compile_cache
    configure_compile_cache()
    devices = jax.devices()
    if {d.platform for d in devices} != {"tpu"}:
        raise SystemExit("benchmark: jax found no TPU (platforms "
                         f"{sorted({d.platform for d in devices})}); "
                         "nothing was run")
    if len(devices) != chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chip(s), "
                         f"jax reports {len(devices)}")
    costs.chip_peaks(devices[0].device_kind)
    return {"platform": "tpu", "kind": devices[0].device_kind,
            "count": len(devices)}


def read_layer_metric(directory: str, name: str, ctx: dict):
    """Loads the metric's reader, a file of its own, and asks it."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric", os.path.join(directory, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(ctx)


def run_cell(job: dict, device: dict, seed: int, seconds: float,
             traced: bool) -> dict:
    """Drives the cell and builds the result. `device` is what
    `require_chips` returned (the tests hand in their own)."""
    cell, manifest = job["cell"], job["manifest"]
    driver = importlib.import_module(
        f"benchmark.drivers.{job['traffic']['driver']}")
    outcome = driver.run({
        "cell": cell, "config": job["config"], "traffic": job["traffic"],
        "seed": seed, "seconds": seconds, "trace": traced,
        "process_start": _PROCESS_START})
    verdict = check.judge(outcome["numbers"], job["limits"],
                          outcome["attempted"], outcome["failed"])
    device = dict(device, memory_peak_bytes=outcome["memory_peak_bytes"])
    result = {"correct": verdict["correct"],
              "attempted": outcome["attempted"],
              "failed": outcome["failed"], "metrics": {}, "device": device}
    if traced:
        summary = outcome["trace"]
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result["breakdown"] = summary["breakdown"]
        ctx = {"job": job, "facts": outcome["facts"], "trace": summary,
               "chips": device["count"],
               "peaks": costs.chip_peaks(device["kind"])}
        for metric in metrics_of(manifest, "per_layer", cell["name"]):
            value = read_layer_metric(job["layer_metrics_dir"],
                                      metric["name"], ctx)
            if value is not None:
                result["metrics"][metric["name"]] = {
                    "value": value, "unit": metric["unit"]}
    else:
        for metric in metrics_of(manifest, "end_to_end", cell["name"]):
            result["metrics"][metric["name"]] = {
                "value": outcome["end_to_end"][metric["name"]],
                "unit": metric["unit"]}
    result["setup_marks"] = outcome.get("setup_marks", {})
    result["checks"] = verdict["checks"]
    return result


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    job = load_job(ROOT, args.workload)
    device = require_chips(job["cell"]["chips"])
    result = run_cell(job, device, args.seed, args.seconds, bool(args.trace))
    for name, c in result["checks"].items():
        print(f"check {name}: value {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
