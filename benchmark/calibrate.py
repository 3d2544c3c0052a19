"""Readings that the limits under `limits/` are set from.

    python3 -m benchmark.calibrate --workload <name> --seeds 12 \\
        --extra-seeds 3 --extras int8 half_batch --seconds 0

In one process, seed after seed: the cell's numbers as the timed path
gives them (the lower readings), and on the first `--extra-seeds` seeds
the control (`int8`) and the planted faults, each the reference put in
the program's place (the upper readings). A training cell needs no
window (`--seconds 0`); a serving cell a short one at its own load.
Prints one JSON line a seed and a summary; needs the cell's chips.
"""

from __future__ import annotations

import argparse
import importlib
import json
import time

from benchmark import run


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--first-seed", type=int, default=2_400_000_001)
    parser.add_argument("--extra-seeds", type=int, default=3)
    parser.add_argument("--extras", nargs="*", default=["int8"])
    parser.add_argument("--seconds", type=float, default=0.0)
    parser.add_argument("--extra-steps", type=int, default=3,
                        help="steps a training cell's extras follow: with "
                             "1, only the first loss and gradient are read")
    args = parser.parse_args(argv)
    job = run.load_job(run.ROOT, args.workload)
    run.require_chips(job["cell"]["chips"])
    driver = importlib.import_module(
        f"benchmark.drivers.{job['traffic']['driver']}")
    lower: dict = {}
    upper: dict = {}
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        began = time.perf_counter()
        outcome = driver.run({
            "cell": job["cell"], "config": job["config"],
            "traffic": job["traffic"], "seed": seed,
            "seconds": args.seconds, "trace": False,
            "process_start": began,
            "extra_steps": args.extra_steps,
            "extras": args.extras if i < args.extra_seeds else []})
        print(json.dumps({
            "seed": seed, "numbers": outcome["numbers"],
            "extras": outcome["extras"], "failed": outcome["failed"],
            "attempted": outcome["attempted"],
            "seconds": time.perf_counter() - began}), flush=True)
        for name, value in outcome["numbers"].items():
            lower[name] = max(lower.get(name, 0.0), value)
        for extra, numbers in outcome["extras"].items():
            for name, value in numbers.items():
                key = f"{extra}.{name}"
                upper[key] = min(upper.get(key, float("inf")), value)
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "largest_of_the_program": lower,
                      "smallest_of_each_extra": upper}), flush=True)


if __name__ == "__main__":
    main()
