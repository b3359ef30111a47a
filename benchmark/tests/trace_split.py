"""Where a cell's time goes, by the program's own spans and device scopes:
one run of the cell as ``run.py --trace 1`` makes it (the profiler over
the window's first ``run.Tracer.SECONDS``), reduced by ``trace_spans``.

    python3 benchmark/tests/trace_split.py --workload <cell> --seed <n>
        [--seconds 20]

Prints one JSON line: ``probes_per_s`` over the whole window (the profiler
on for its first seconds: the cost of tracing, against a ``--trace 0``
run), the traced calls, per call in ms each device scope's time,
the walk, the readback with the device idle, each program span's time,
the device's idle time split by coarse label and innermost span, and the
longest idle gaps with their labels. It checks no answer (``run.py``
does); on a program without the spans or scopes the new numbers are
null. It needs the cell's chips, as ``run.py`` does.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import types

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import run, trace_spans  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args()
    cell = run.Cell(args.workload, os.path.join(ROOT, "BENCHMARK.json"),
                    False)
    run._set_env(types.SimpleNamespace(rehearse=False), cell.chips)
    import jax

    devs = run._devices(cell.chips, False)
    if devs is None:
        print(f"trace_split.py: {cell.name} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from benchmark.build import Timer

    driver = run.load_module("drivers", cell.traffic["driver"])
    r = driver.Run(cell, args.seed, devs, Timer(), args.seconds)
    trace_dir = os.path.join(run.CACHE, "trace", f"split-{cell.name}")
    tracer = run.Tracer(trace_dir)
    window = r.window(args.seconds, tracer)
    tracer.stop()
    r.close()
    red = trace_spans.reduce(trace_spans.load(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    calls = red["calls"]

    def per_call(seconds):
        return None if seconds is None or not calls else \
            1000.0 * seconds / calls

    print(json.dumps({
        "workload": cell.name, "seed": args.seed,
        "device": {"kind": devs[0].device_kind, "count": len(devs)},
        "probes_per_s": window["end_to_end"]["probes_per_s"],
        "traced_calls": calls, "window_s": red["window_s"],
        "ms_per_call": {
            "busy": per_call(red["busy_s"]),
            "sweep": per_call(red["sweep_s"]),
            "viterbi": per_call(red["viterbi_s"]),
            "scopes": {k: per_call(v) for k, v in red["scope_s"].items()},
            "walk": per_call(red["walk_s"]),
            "readback": per_call(red["readback_s"]),
            "spans": {k: per_call(v) for k, v in red["span_s"].items()},
            "idle": {k: per_call(v) for k, v in red["idle_split"]}},
        "device_ops": red["device_ops"],
        "idle_gaps": red["idle_gaps"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
