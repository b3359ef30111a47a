"""The control: the plain reference computed in bfloat16, the precision
below the configuration's float32, put in the program's place and judged
by the run's own comparison (``run.judge``) against the float64
reference.

    python benchmark/tests/control.py [--seeds 3] [--seconds 20]
        [--workload <cell> ...] [--small] [--spec BENCHMARK.json]

For each cell (by default every cell of ``BENCHMARK.json``), at the
cell's own size: its traffic drawn from the seed as a run draws it, the
same sample of traces a run compares, every request answered. Prints one
JSON line per cell and seed with ``correct``, which must be false, and
each compared number beside its limit. It runs on the host alone.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import build, run  # noqa: E402
from benchmark.reference import Reference  # noqa: E402


def verdict(workload: str, seed: int, seconds: float,
            small: bool = False, spec: "str | None" = None) -> tuple:
    """(correct, numbers) of the control in ``workload``'s place, as
    ``spec`` (default: the checkout's ``BENCHMARK.json``) declares it;
    with ``small``, on the configuration's own city but the rehearsal's
    share of the traffic (a test's size: bfloat16's error grows with the
    city's extent, so the rehearsal's small city would hide it)."""
    import ml_dtypes

    cell = run.Cell(workload, spec or os.path.join(ROOT, "BENCHMARK.json"),
                    False)
    if small:
        cell.traffic.update(cell.traffic.get("rehearsal", {}))
        cell.config["correct"] = cell.config["rehearsal"]["correct"]
    g = build.graph(cell.config, build.city(cell.config))
    driver = run.load_module("drivers", cell.traffic["driver"])
    low = Reference(g, cell.config["matcher"], dtype=ml_dtypes.bfloat16)
    answers = [(xy, t, low.match(xy, t))
               for xy, t in driver.sample(cell, g, seed, seconds)]
    counts = {"failed_requests": 0, "publish_mismatch": 0} if (
        cell.traffic["driver"] == "report") else {}
    return run.judge(cell, g, answers, counts)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--workload", action="append")
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--spec", default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args()
    with open(args.spec) as f:
        spec = json.load(f)
    seconds = args.seconds or spec["run_seconds"]
    for name in args.workload or [w["name"] for w in spec["workloads"]]:
        for k in range(args.seeds):
            seed = 2**31 + 1000 + k
            ok, numbers = verdict(name, seed, seconds, args.small,
                                  args.spec)
            print(json.dumps({"workload": name, "seed": seed,
                              "correct": ok, "checks": numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
