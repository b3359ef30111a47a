#!/usr/bin/env python3
"""Bring-up smoke of the match path on one TPU chip (or four, by option).

ONE process owns the chip for the whole run and starts no child that
touches JAX. Phases, each reported on its own stdout line with its wall
and compile seconds:

  device     jax.devices() must be a TPU — there is no CPU branch
  batch      bayarea-xl tile, 4000 x 120-point traces through
             SegmentMatcher.match_many once per sweep-kernel arm (block,
             subcull, mxu; each a pinned kernel plan): the three record
             streams must be identical. Fidelity against the exact CPU
             oracle (bench._oracle_audit) on 200 sf traces — the bench's
             own oracle tile; exact Dijkstra costs ~13 s per trace at
             bayarea-xl — plus a few bayarea-xl traces, each within the
             BASELINE.json 5% segment-ID disagreement bound
  autotune   a matcher with sweep_autotune=True on the same tile: no
             candidate error, a `measured` plan
  service    make_app + wsgiref on port 0 in a thread: /report, /health,
             /metrics answer 200, every /report matches segments
  streaming  a few waves of the columnar pipeline into an in-process
             sink: lag 0, sink rows == rows published

The last stdout line is {"ok": true, "device": {...}}; any failed phase
exits non-zero before it. ``--chips 4`` runs ONLY the data-parallel mesh
path (mesh SegmentMatcher + mesh BackfillEngine over the bayarea tile)
against the single-device run. ``--rehearse`` runs the same phases at the
tiny size on CPU with the Pallas interpreter — a control-flow check,
never a chip result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

# BASELINE.json north star: "< 5% segment-ID disagreement vs. Meili"
ORACLE_TOLERANCE = 0.05

FULL = dict(city="bayarea-xl", traces=4000, points=120, oracle_city="sf",
            oracle_traces=200, xl_oracle_traces=4, probes=24,
            stream_vehicles=64, mesh_city="bayarea", mesh_traces=512,
            bf_vehicles=96, bf_points=240)
TINY = dict(city="tiny", traces=12, points=40, oracle_city="tiny",
            oracle_traces=6, xl_oracle_traces=2, probes=4,
            stream_vehicles=8, mesh_city="tiny", mesh_traces=12,
            bf_vehicles=16, bf_points=120)

# sweep-kernel arm -> (arm, lowp) of the kernel plan pinned for it
ARMS = {
    "block": ("block", "off"),
    "subcull": ("subcull", "off"),
    "mxu": ("mxu", "off"),
}


class _CompileClock:
    """Compile seconds from JAX's own monitoring events: ``seconds`` is
    the backend compile (a persistent-cache hit shows here as a short
    retrieval), ``trace_lower_seconds`` the jaxpr trace + MLIR lowering
    (Pallas kernels are lowered here, and it is paid on a cache hit too)."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    FRONT = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration")

    def __init__(self):
        self.seconds = 0.0
        self.trace_lower_seconds = 0.0
        self.programs = 0

    def __call__(self, event, duration, **_):
        if event == self.BACKEND:
            self.seconds += duration
            self.programs += 1
        elif event in self.FRONT:
            self.trace_lower_seconds += duration


def _emit(doc: dict) -> None:
    print(json.dumps(doc), flush=True)


def _phase(name: str, clock: _CompileClock, fn):
    t0 = time.perf_counter()
    c0, f0, n0 = (clock.seconds, clock.trace_lower_seconds,
                  clock.programs)
    out = fn() or {}
    _emit({"phase": name, "wall_s": time.perf_counter() - t0,
           "compile_s": clock.seconds - c0,
           "trace_lower_s": clock.trace_lower_seconds - f0,
           "programs_compiled": clock.programs - n0, **out})
    return out


def _tiles(city: str, rehearse: bool):
    from reporter_tpu.config import CompilerParams
    from reporter_tpu.netgen.synthetic import generate_city
    from reporter_tpu.tiles.compiler import compile_network

    # the tiny city's default OSMLR chaining matches ~zero reports end to
    # end; the test fixtures' compile shape keeps its segments observable
    cp = (CompilerParams(reach_radius=500.0, osmlr_max_length=250.0)
          if city == "tiny" else CompilerParams())
    return compile_network(generate_city(city), cp)


def _traces(ts, n: int, points: int, seed: int = 7):
    import numpy as np

    from reporter_tpu.matcher.api import Trace
    from reporter_tpu.netgen.traces import synthesize_fleet

    return [Trace(uuid=p.uuid, xy=p.xy.astype(np.float32), times=p.times)
            for p in synthesize_fleet(ts, n, num_points=points, seed=seed)]


def _config(**matcher):
    from reporter_tpu.config import Config, MatcherParams

    # candidate_backend pinned to the sweep: on the chip "auto" resolves
    # to it anyway, and the rehearsal must exercise the same kernels
    return Config(matcher_backend="jax", matcher=MatcherParams(
        candidate_backend="dense", **matcher))


def _same_records(a, b) -> bool:
    import numpy as np

    return a.n_records == b.n_records and all(
        np.array_equal(getattr(a.columns, f), getattr(b.columns, f))
        for f in a.columns._fields)


def phase_batch(size: dict, rehearse: bool, state: dict) -> dict:
    import bench
    from reporter_tpu import native
    from reporter_tpu.matcher import autotune
    from reporter_tpu.matcher.api import SegmentMatcher

    t0 = time.perf_counter()
    ts = state["ts"] = _tiles(size["city"], rehearse)
    tile_s = time.perf_counter() - t0
    traces = _traces(ts, size["traces"], size["points"])
    out: dict = {"tile": ts.name, "edges": int(ts.num_edges),
                 "traces": len(traces), "points": size["points"],
                 "tile_s": tile_s,
                 "native_lib_loaded": native.lib is not None}
    batches = {}
    for arm, (kernel, lowp) in ARMS.items():
        plan = autotune.TunedPlan(arm=kernel, lowp=lowp)
        m = SegmentMatcher(ts, _config(), staged_tables=autotune.pin_plan(
            ts.device_tables("dense"), plan))
        if m.tuned_plan != plan:
            raise AssertionError(f"arm {arm}: pinned {plan.label}, "
                                 f"served {m.tuned_plan.label}")
        t1 = time.perf_counter()
        batches[arm] = m.match_many(traces)
        out[f"{arm}_first_call_s"] = time.perf_counter() - t1
        t1 = time.perf_counter()
        again = m.match_many(traces)
        out[f"{arm}_warm_call_s"] = time.perf_counter() - t1
        if not _same_records(batches[arm], again):
            raise AssertionError(f"arm {arm}: two calls disagree")
        if arm == "subcull":
            # the default arm against the oracle on a few traces of THIS
            # tile (exact Dijkstra is ~13 s/trace at bayarea-xl)
            n = size["xl_oracle_traces"]
            dis, _, _, _ = bench._oracle_audit(ts, m, traces, n,
                                               force_fresh=True)
            out["oracle_same_tile"] = {"traces": n, "disagreement": dis}
            if not dis < ORACLE_TOLERANCE:
                raise AssertionError(
                    f"{ts.name} oracle disagreement {dis} >= "
                    f"{ORACLE_TOLERANCE}")
        del m
    out["records"] = int(batches["block"].n_records)
    if out["records"] == 0:
        raise AssertionError("the batch path matched no records")
    for arm in ("subcull", "mxu"):
        if not _same_records(batches["block"], batches[arm]):
            raise AssertionError(f"arm {arm} records differ from block")
    out["arms_identical"] = True

    # fidelity on the bench's oracle tile, same kernel path
    ots = _tiles(size["oracle_city"], rehearse)
    otraces = _traces(ots, size["oracle_traces"], size["points"])
    om = SegmentMatcher(ots, _config(sweep_autotune=False))
    dis, cpu_pps, n, _ = bench._oracle_audit(ots, om, otraces,
                                             len(otraces), force_fresh=True)
    out["oracle"] = {"tile": ots.name, "traces": n, "disagreement": dis,
                     "tolerance": ORACLE_TOLERANCE,
                     "cpu_oracle_pps": cpu_pps}
    if not dis < ORACLE_TOLERANCE:
        raise AssertionError(
            f"{ots.name} oracle disagreement {dis} >= {ORACLE_TOLERANCE}")
    return out


def phase_autotune(size: dict, rehearse: bool, state: dict) -> dict:
    from reporter_tpu.matcher.api import SegmentMatcher

    m = SegmentMatcher(state["ts"], _config(sweep_autotune=True))
    rep = m.tuned_report
    plan = m.tuned_plan
    out = {"source": rep.get("source"), "errors": rep.get("errors") or {},
           "plan": None if plan is None else plan.label,
           "candidates": rep.get("candidates"),
           "calibration_s": rep.get("calibration_seconds")}
    if out["errors"]:
        raise AssertionError(f"autotune candidate errors: {out['errors']}")
    # the CPU rehearsal short-circuits the tuner by design
    want = "cpu" if rehearse else "measured"
    if out["source"] != want:
        raise AssertionError(f"autotune source {out['source']!r}, "
                             f"expected {want!r}")
    return out


def _http(url: str, body: "dict | None" = None):
    import urllib.request

    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as r:
        return r.status, r.read()


def phase_service(size: dict, rehearse: bool, state: dict) -> dict:
    from reporter_tpu.netgen.traces import synthesize_probe
    from reporter_tpu.service.app import make_app
    from reporter_tpu.service.server import serve

    ts = state["ts"]
    app = make_app(ts, _config())
    server = serve(app, "127.0.0.1", 0)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{server.server_address[1]}"
    bodies = [synthesize_probe(ts, seed=100 + i, num_points=size["points"],
                               gps_sigma=3.0).to_report_json()
              for i in range(size["probes"])]
    results: list = [None] * len(bodies)

    def post(i):
        try:
            results[i] = _http(base + "/report", bodies[i])
        except Exception as exc:  # noqa: BLE001 — re-raised below
            results[i] = exc

    try:
        t0 = time.perf_counter()
        workers = [threading.Thread(target=post, args=(i,))
                   for i in range(len(bodies))]
        for w in workers:
            w.start()
        for w in workers:
            w.join()
        report_s = time.perf_counter() - t0
        segs = []
        for r in results:
            if isinstance(r, Exception):
                raise r
            status, raw = r
            if status != 200:
                raise AssertionError(f"/report answered {status}")
            segs.append(len(json.loads(raw)["segments"]))
        if min(segs) == 0:
            raise AssertionError(f"a /report matched no segments: {segs}")
        health_status, health_raw = _http(base + "/health")
        metrics_status, _ = _http(base + "/metrics")
        if (health_status, metrics_status) != (200, 200):
            raise AssertionError(
                f"/health {health_status}, /metrics {metrics_status}")
        sched = json.loads(health_raw).get("scheduler") or {}
    finally:
        server.shutdown()
        server.server_close()
        app.close()
    return {"reports_posted": len(bodies), "segments_min": min(segs),
            "segments_total": sum(segs), "report_burst_s": report_s,
            "sched_batches": sched.get("batches"),
            "sched_submissions": sched.get("submissions")}


def phase_streaming(size: dict, rehearse: bool, state: dict) -> dict:
    import bench
    from reporter_tpu.config import Config, ServiceConfig, StreamingConfig
    from reporter_tpu.distributed import ReportSink
    from reporter_tpu.streaming.columnar import (ColumnarIngestQueue,
                                                 ColumnarStreamPipeline)

    ts = state["ts"]
    probes = _traces(ts, size["stream_vehicles"], size["points"], seed=21)
    batches, V, n_pts = bench._stage_round_batches(
        ts, probes, len(probes), steps_per_batch=10)
    sink = ReportSink()
    base = _config()
    cfg = Config(matcher_backend="jax", matcher=base.matcher,
                 service=ServiceConfig(datastore_url=sink.url),
                 streaming=StreamingConfig(flush_min_points=40,
                                           poll_max_records=50_000,
                                           hist_flush_interval=0.0))
    queue = ColumnarIngestQueue(4)
    pipe = ColumnarStreamPipeline(ts, cfg, queue=queue)
    try:
        for b in batches:
            queue.append_columns(b)
            pipe.step()
        pipe.drain()
        lag = int(queue.lag(pipe.committed))
        st = pipe.stats()
    finally:
        pipe.close()
    published = int(pipe.publisher.published)
    rows = int(sink.stats()["rows"])
    sink.close()
    out = {"vehicles": V, "probes": int(V * n_pts), "waves": st["steps"],
           "lag": lag, "published": published, "sink_rows": rows,
           "publish_dropped": int(st["publish_dropped"])}
    if lag != 0:
        raise AssertionError(f"streaming lag {lag} after drain")
    if published == 0 or rows != published:
        raise AssertionError(f"published {published} rows, sink got {rows}")
    return out


def phase_mesh(size: dict, rehearse: bool, state: dict) -> dict:
    """The data-parallel mesh path vs one device, same fleet and spool."""
    import jax
    import numpy as np

    import bench
    from reporter_tpu.backfill import BackfillConfig, BackfillEngine
    from reporter_tpu.config import (CompilerParams, Config, ServiceConfig,
                                     StreamingConfig)
    from reporter_tpu.matcher.api import SegmentMatcher
    from reporter_tpu.netgen.synthetic import generate_city
    from reporter_tpu.netgen.traces import synthesize_fleet
    from reporter_tpu.parallel.mesh import make_mesh
    from reporter_tpu.streaming.durable_columnar import (
        DurableColumnarIngestQueue)
    from reporter_tpu.tiles.compiler import compile_network

    ndev = 4
    if len(jax.devices()) < ndev:
        raise AssertionError(f"--chips 4 needs 4 devices, "
                             f"have {len(jax.devices())}")
    mesh = make_mesh(dp=ndev, devices=jax.devices()[:ndev])
    out: dict = {"devices": ndev}

    def spans(arr) -> int:
        return len(arr.sharding.device_set)

    # mesh-constructed matcher vs single device, record streams
    ts = _tiles(size["mesh_city"], rehearse)
    traces = _traces(ts, size["mesh_traces"], 120, seed=13)
    one = SegmentMatcher(ts, _config(sweep_autotune=False))
    meshm = SegmentMatcher(ts, _config(sweep_autotune=False), mesh=mesh)
    b1 = one.match_many(traces)
    bm = meshm.match_many(traces)
    out["match_records"] = int(b1.n_records)
    if b1.n_records == 0 or not _same_records(b1, bm):
        raise AssertionError("mesh match_many differs from single device")
    out["tables_device_set"] = min(spans(a) for a in
                                   jax.tree.leaves(meshm._wire.tables))
    padded = traces[:32]
    w, slices = meshm.plan_submit(padded)
    wire = meshm.submit_prepared(
        meshm.prepare_submit_slice(padded, w, *slices[0]))
    out["wire_device_set"] = spans(wire)

    # backfill: the same spool through a mesh engine and a single one
    net = generate_city(size["mesh_city"])
    net.name = "bf"
    bts = compile_network(net, CompilerParams(reach_radius=500.0,
                                              osmlr_max_length=200.0))
    probes = synthesize_fleet(bts, size["bf_vehicles"],
                              num_points=size["bf_points"], seed=11,
                              gps_sigma=3.0)
    batches, _, _ = bench._stage_round_batches(
        bts, probes, len(probes), steps_per_batch=40)
    with tempfile.TemporaryDirectory() as work:
        spool = os.path.join(work, "spool")
        q = DurableColumnarIngestQueue(spool, 4)
        for b in batches:
            q.append_columns(b)
        q.close()
        cfg = Config(
            matcher_backend="jax",
            matcher=_config(sweep_autotune=False).matcher,
            service=ServiceConfig(datastore_url="http://sink.invalid/"),
            streaming=StreamingConfig(num_partitions=4))
        bf = BackfillConfig(slice_traces=64, max_inflight=4,
                            poll_records=4096, k_anonymity=2)
        eng = BackfillEngine(bts, cfg, bf)
        eng.enable_shadow_reference()
        s1 = eng.run(spool)
        meng = BackfillEngine(bts, cfg, bf, mesh=mesh)
        meng.enable_shadow_reference()
        sm = meng.run(spool)
    out["backfill_reports"] = int(s1["reports"])
    out["hist_device_set"] = spans(meng.hist._grid._grid)
    out["backfill_shadow_identical"] = bool(meng.shadow_identical()
                                            and eng.shadow_identical())
    out["backfill_grids_equal"] = bool(
        np.array_equal(eng.hist.snapshot(), meng.hist.snapshot())
        and np.array_equal(eng.qhist.snapshot(), meng.qhist.snapshot()))
    out["store_equal"] = (json.dumps(eng.store.snapshot(), sort_keys=True)
                          == json.dumps(meng.store.snapshot(),
                                        sort_keys=True))
    out["mesh_krows_per_s"] = sm.get("krows_per_s")
    out["single_krows_per_s"] = s1.get("krows_per_s")
    if s1["reports"] == 0:
        raise AssertionError("backfill spool produced no reports")
    for key in ("tables_device_set", "wire_device_set", "hist_device_set"):
        if out[key] != ndev:
            raise AssertionError(f"{key} spans {out[key]} devices, "
                                 f"expected {ndev}")
    for key in ("backfill_shadow_identical", "backfill_grids_equal",
                "store_equal"):
        if not out[key]:
            raise AssertionError(f"mesh backfill: {key} is False")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the data-parallel mesh path vs one "
                         "device (run by hand on a four-chip host)")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on CPU with the Pallas interpreter "
                         "(a control-flow check, never a chip result)")
    args = ap.parse_args(argv)

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["RTPU_PALLAS_INTERPRET"] = "1"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()
    # keep the CPU oracle audits and background quality audits out of
    # the device phases' way (the bench's own pin)
    os.environ.setdefault("RTPU_QUALITY_AUDIT_RATE", "0")
    tune_dir = tempfile.mkdtemp(prefix="rtpu_autotune_")
    os.environ["RTPU_AUTOTUNE_CACHE"] = tune_dir  # phase 3 must measure

    import jax

    from reporter_tpu.utils.compile_cache import enable_compilation_cache

    cache_dir = enable_compilation_cache()      # before any compile
    clock = _CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    size = TINY if args.rehearse else FULL
    state: dict = {}

    def phase_device():
        devs = jax.devices()
        d = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
        state["device"] = d
        want = "cpu" if args.rehearse else "tpu"
        if d["platform"] != want:
            raise SystemExit(f"chip_smoke: platform {d['platform']!r}, "
                             f"need {want!r} (no CPU branch; --rehearse is "
                             "the only CPU run)")
        return {**d, "compile_cache": cache_dir, "rehearse": args.rehearse}

    try:
        _phase("device", clock, phase_device)
        if args.chips == 4:
            _phase("mesh", clock, lambda: phase_mesh(size, args.rehearse,
                                                     state))
        else:
            for name, fn in (("batch", phase_batch),
                             ("autotune", phase_autotune),
                             ("service", phase_service),
                             ("streaming", phase_streaming)):
                _phase(name, clock, lambda fn=fn: fn(size, args.rehearse,
                                                     state))
    finally:
        import shutil

        shutil.rmtree(tune_dir, ignore_errors=True)
    _emit({"ok": True, "device": state["device"]})
    return 0


if __name__ == "__main__":
    sys.exit(main())
