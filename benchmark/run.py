#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: its entry in
``BENCHMARK.json`` names a configuration (``configs/<config>.json``) and a
traffic mix (``traffic/<traffic>.json``); the mix names the driver that
makes the window's calls (``drivers/<driver>.py``); each per-layer metric
is read by ``layer_metrics/<metric>.py``. The run loads, warms up only the
cell's shapes, measures for ``--seconds``, checks what the window produced
against the plain reference (``reference.py``) and prints one JSON line.

There is no CPU branch: without a TPU, or with fewer chips than the cell
asks for, the run exits 2 and prints no result. ``--rehearse`` is the one
exception: a tiny size on the CPU, whose output never names a metric.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(BENCH, ".cache")


def load_module(kind: str, name: str):
    """``<kind>/<name>.py``; a metric split by cells (``<base>.<part>``)
    may share one reader, ``<kind>/<base>.py``."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(BENCH, kind, f"{name.split('.')[0]}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Cell:
    """What the files say about one workload."""

    def __init__(self, name: str, spec_path: str, rehearse: bool):
        spec = load_json(spec_path)
        entry = next((w for w in spec["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise SystemExit(f"unknown workload {name!r}")
        self.name = name
        self.chips = int(entry["chips"])
        conf = next(c for c in spec["configs"]
                    if c["name"] == entry["config"])
        self.config = load_json(ROOT, conf["file"])
        self.traffic = load_json(BENCH, "traffic", f"{entry['traffic']}.json")
        if rehearse:
            self.config.update(self.config.get("rehearsal", {}))
            self.traffic.update(self.traffic.get("rehearsal", {}))

        def here(m):
            return name in m.get("workloads", [name])

        self.end_to_end = [m for m in spec["end_to_end"] if here(m)]
        e2e = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in spec["per_layer"]
                          if m["moves"] in e2e and here(m)]


class CompileClock:
    """JAX's own compile events: backend compile (a persistent-cache hit
    shows as a short retrieval) and jaxpr trace + MLIR lowering (paid on
    a cache hit too). Copied from the program's chip_smoke."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    FRONT = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration")

    def __init__(self):
        self.backend_s = 0.0
        self.trace_lower_s = 0.0
        self.programs = 0

    def __call__(self, event, duration, **_):
        if event == self.BACKEND:
            self.backend_s += duration
            self.programs += 1
        elif event in self.FRONT:
            self.trace_lower_s += duration


def log(**kv) -> None:
    print(json.dumps(kv, default=str), file=sys.stderr, flush=True)


def _set_env(args, chips: int) -> None:
    env = os.environ
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        if chips > 1:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                f" --xla_force_host_platform_device_count"
                                f"={chips}").strip()
    # every cache at a fixed path inside the checkout
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax")
    env["RTPU_AUTOTUNE_CACHE"] = os.path.join(CACHE, "autotune")
    # the shadow auditor runs the exact CPU oracle on sampled batches in a
    # background thread: a quality monitor, not the served path
    env["RTPU_QUALITY_AUDIT_RATE"] = "0"
    env.pop("REPORTER_TPU_TRACE_DIR", None)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)


class Tracer:
    """The profiler over the first ``SECONDS`` of the window (a no-op
    without a directory). Reading a whole window's trace of four chips
    takes minutes; a few seconds of steady calls give the same shares.
    The drivers call ``start`` as the window opens and ``stop`` at the
    first call boundary past ``SECONDS``; the ``window`` annotation marks
    the traced span for ``trace_reduce``."""

    SECONDS = 4.0

    def __init__(self, trace_dir: "str | None"):
        self.dir = trace_dir
        self._ann = None

    def start(self) -> None:
        if not self.dir:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("window")
        self._ann.__enter__()

    def stop(self) -> None:
        if self._ann is None:
            return
        import jax

        self._ann.__exit__(None, None, None)
        self._ann = None
        jax.profiler.stop_trace()


def _devices(chips: int, rehearse: bool):
    import jax

    devs = jax.devices()
    want = "cpu" if rehearse else "tpu"
    if devs[0].platform != want or len(devs) < chips:
        return None
    return devs[:chips]


def _memory_peak(devs) -> "int | None":
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def judge(cell: Cell, g, answers: list, counts: dict) -> tuple:
    """(correct, numbers): the float64 reference over each answer's trace,
    the mean segment disagreement, the number compared, and each count
    that must be 0, each number beside its limit. The control
    (``tests/control.py``) is judged here too."""
    from benchmark.reference import Reference, disagreement

    limits = cell.config["correct"]
    ref = Reference(g, cell.config["matcher"])
    dis = [disagreement(got, ref.match(xy, times))
           for xy, times, got in answers]
    mean_pct = float(100.0 * sum(dis) / max(len(dis), 1))
    numbers = {
        "segment_disagreement_pct": {
            "value": mean_pct,
            "limit": limits["segment_disagreement_pct"]},
        "compared_traces": {"value": len(dis),
                            "limit": limits["min_compared_traces"]},
        **{k: {"value": v, "limit": 0} for k, v in counts.items()},
    }
    ok = bool(mean_pct <= limits["segment_disagreement_pct"]
              and len(dis) >= limits["min_compared_traces"]
              and all(v == 0 for v in counts.values()))
    return ok, numbers


def check(run, cell: Cell, seed: int) -> tuple:
    from benchmark import build

    answers, counts = run.answers(seed)
    return judge(cell, build.graph(cell.config, run.city), answers, counts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on the CPU; prints no metric")
    args = ap.parse_args(argv)

    cell = Cell(args.workload, os.path.join(ROOT, "BENCHMARK.json"),
                args.rehearse)
    _set_env(args, cell.chips)
    import jax

    devs = _devices(cell.chips, args.rehearse)
    if devs is None:
        print(f"run.py: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"has {jax.devices()}", file=sys.stderr)
        return 2
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    # cache every program, however fast it compiled, so that only a
    # checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from benchmark.build import Timer

    timer = Timer()
    driver = load_module("drivers", cell.traffic["driver"])
    run = driver.Run(cell, args.seed, devs, timer, args.seconds)
    setup_s = time.perf_counter() - T_START
    log(setup_split=timer.split, backend_compile_s=clock.backend_s,
        trace_lower_s=clock.trace_lower_s, programs=clock.programs,
        setup_s=setup_s)

    trace_dir = os.path.join(CACHE, "trace", cell.name)
    if args.rehearse:
        args.trace = 0                # the CPU has no device plane
    tracer = Tracer(trace_dir if args.trace else None)
    programs0 = clock.programs
    window = run.window(args.seconds, tracer)
    tracer.stop()
    if "diag" in window:
        log(window=window["diag"])
    if clock.programs != programs0:
        log(warning="programs compiled inside the window",
            count=clock.programs - programs0)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": _memory_peak(devs)}
    run.close()

    correct, numbers = check(run, cell, args.seed)
    result: dict = {"correct": correct, "attempted": window["attempted"],
                    "failed": window["failed"]}
    if args.trace:
        from benchmark import trace_reduce

        red = trace_reduce.reduce(trace_reduce.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        inputs = {"trace": red, "window": window}
        metrics = {}
        for m in cell.per_layer:
            v = load_module("layer_metrics", m["name"]).read(inputs)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    else:
        values = {**window["end_to_end"], "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    result["metrics"] = metrics
    result["device"] = device
    if args.rehearse:
        result = {"rehearsal": True, "correct": correct,
                  "attempted": window["attempted"],
                  "failed": window["failed"], "device": device}
    result["checks"] = numbers
    for k, v in numbers.items():
        print(f"check {k} = {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
