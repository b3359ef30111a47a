#!/usr/bin/env python
"""Benchmark harness (driver hook): BASELINE.md configs 2-4 in one run.

Default run measures FOUR tiles with the jax backend and one shared
process:
  - "sf" (BASELINE config 2, the headline number + latency/concurrency),
  - "bayarea" (config 3, metro scale in HBM) in detail.metro,
  - "sf+r" (sf with ~8% junction turn-restriction density) in
    detail.restricted — banned_turn_pairs > 0 with the oracle audit on,
  - "bayarea-xl" (~0.5M directed edges, the SURVEY §7 HBM-budget stressor)
    in detail.xl with the replicated-vs-sharded staging plan.
The fidelity audit totals ≥500 traces across the first three tiles against
the in-repo exact-Dijkstra CPU oracle (the Meili stand-in, config 1's
anchor), reported per tile.

Prints ONE JSON line:
  {"metric": "probes_per_sec_e2e", "value": ..., "unit": "probes/s",
   "vs_baseline": <sf jax throughput / cpu-reference throughput>, ...detail}

"e2e" = the full SegmentMatcher.match_many path: host batching, device
decode, segment association, report-ready records (columnar MatchBatch) —
the same work the reference's segment_matcher.Match does per trace.

Manual runs: `python bench.py [n_traces] [city]` bench exactly one tile
(skips the metro/restricted extras).

Round 15 — the composite is a DAG of journaled legs (bench_journal.jsonl,
atomic per-leg appends stamped with git sha + wall time + the
contemporaneous link-health window from utils/linkhealth.py):
`--resume` (or RTPU_BENCH_RESUME=1) serves already-journaled legs
instead of re-measuring, so a run cut mid-way keeps everything
captured; `--legs sweep_ab,fleet` (or RTPU_BENCH_LEGS) runs a subset
that fits a short chip call, writing BENCH_DETAIL*_PARTIAL.json so
a sparse composite never clobbers the committed full capture. Every
run's tail self-reports a schema-aware delta vs the committed capture
(analysis/bench_delta.py) with regressions attributed against the
recorded link mood.

Tiles and fleets are cached on disk (.bench_tiles_*.npz /
.bench_fleet_*.npz) so repeat runs exercise the operational
load-from-npz restart path instead of recompiling; detail.setup_split
reports where the setup time went either way.
"""

import json
import os
import sys
import time

_RESTRICT_FRACTION = 0.08   # ~8% of junctions carry a no_turn (VERDICT r2 #5)
_RESTRICT_SEED = 13


def _time_best(fn, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _repo_path(name: str) -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)), name)


def _cached_tileset(city: str, restricted: bool = False):
    """Compile-or-load a bench tileset. Returns (ts, info) where info
    records the source ("npz-cache" vs "compiled") and seconds — the
    load path is the same TileSet.load a restarted service worker uses."""
    from reporter_tpu.config import CompilerParams
    from reporter_tpu.netgen.synthetic import (add_random_restrictions,
                                               generate_city)
    from reporter_tpu.tiles.compiler import compile_network
    from reporter_tpu.tiles.tileset import TileSet

    key = f"{city}_r{int(_RESTRICT_FRACTION * 100)}" if restricted else city
    t0 = time.perf_counter()
    # Generating the RoadNetwork is cheap (~1 s even for bayarea-xl); the
    # compile + reach build is what the cache buys. Fingerprinting the
    # generated net (topology + attributes + restrictions, the shared
    # RoadNetwork.fingerprint) keys the cache by CONTENT, so generator
    # changes can never serve a stale tileset.
    net = generate_city(city)
    if restricted:
        net = add_random_restrictions(net, fraction=_RESTRICT_FRACTION,
                                      seed=_RESTRICT_SEED)
    fp = net.fingerprint()
    path = _repo_path(f".bench_tiles_{key}_v4_{fp & 0xFFFFFFFF:08x}.npz")
    if os.path.exists(path):
        try:
            ts = TileSet.load(path)
            return ts, {"source": "npz-cache",
                        "seconds": round(time.perf_counter() - t0, 2)}
        except Exception:
            pass                    # stale schema: fall through to compile
    ts = compile_network(net, CompilerParams())
    ts.save(path)
    return ts, {"source": "compiled",
                "seconds": round(time.perf_counter() - t0, 2)}


def _cached_fleet(ts, n_traces: int, n_points: int):
    """Synthesizing 16k probe traces costs ~40s of single-core host time —
    cache the fleet on disk so repeat bench runs skip it."""
    import zlib

    import numpy as np

    from reporter_tpu.matcher.api import Trace
    from reporter_tpu.netgen.traces import synthesize_fleet

    # cache key includes a tileset content fingerprint + the synthesis
    # seed, so geometry/compiler/restriction changes invalidate stale
    # fleets (crc32, not hash(): python hashing is per-process randomized;
    # ban arrays are empty on unrestricted tiles, so their keys are stable
    # across this change)
    crc = zlib.crc32(ts.edge_len.tobytes())
    crc = zlib.crc32(ts.ban_from.tobytes(), crc)
    crc = zlib.crc32(ts.ban_to.tobytes(), crc)
    fp = f"{crc & 0xFFFFFFFF:08x}-s7t"   # t: cache carries ground truth
    path = _repo_path(f".bench_fleet_{ts.name}_{n_traces}x{n_points}_{fp}.npz")
    if os.path.exists(path):
        with np.load(path) as z:
            xy, times, true_edges = z["xy"], z["times"], z["true_edges"]
        return [Trace(uuid=f"bench-{i}", xy=xy[i], times=times[i])
                for i in range(len(xy))], true_edges
    fleet = synthesize_fleet(ts, n_traces, num_points=n_points, seed=7)
    xy = np.stack([p.xy for p in fleet]).astype(np.float32)
    times = np.stack([p.times for p in fleet])
    true_edges = np.stack([p.true_edges for p in fleet]).astype(np.int32)
    np.savez(path, xy=xy, times=times, true_edges=true_edges)
    return [Trace(uuid=f"bench-{i}", xy=xy[i], times=times[i])
            for i in range(len(xy))], true_edges


def _truth_rates(ts, matcher, traces, true_edges, n: int):
    """Per-point agreement with the SYNTHESIS ground truth (the fleet's
    driven edge per sample) — independent of the CPU oracle. Point-level
    truth is intrinsically ambiguous near junctions under 5 m GPS noise
    (a point can legally project onto the next edge of the same route),
    so these rates complement — not replace — the length-weighted
    segment-agreement headline."""
    import numpy as np

    dec = matcher._decode_many(traces[:n])
    row = ts.edge_osmlr
    pts = edge_ok = seg_ok = 0
    for (e, _, _), te in zip(dec, true_edges[:n]):
        te = te[:len(e)].astype(np.int64)
        e = e.astype(np.int64)
        matched = e >= 0
        pts += len(e)
        edge_ok += int((matched & (e == te)).sum())
        seg_ok += int((matched & (row[np.maximum(e, 0)] == row[te])
                       & (row[te] >= 0)).sum())
    return {"traces": n,
            "point_edge_rate": round(edge_ok / max(pts, 1), 4),
            "point_segment_rate": round(seg_ok / max(pts, 1), 4)}


def _throughput(ts, traces, repeats: int):
    """(matcher, e2e_pps, decode_pps, batch_seconds) for one tile."""
    from reporter_tpu.config import Config
    from reporter_tpu.matcher.api import SegmentMatcher

    m = SegmentMatcher(ts, Config(matcher_backend="jax"))
    m.match_many(traces)                    # compile + stage HBM (full shape)
    dt, dt_dec = _timed_pair(m, traces, repeats)
    probes = sum(len(t.xy) for t in traces)
    return m, probes / dt, probes / dt_dec, dt


def _timed_pair(m, traces, repeats: int) -> tuple[float, float]:
    """Best-of-N (e2e seconds, decode-only seconds), reps INTERLEAVED:
    the link's throughput drifts minute to minute (~2x day swing), so
    phase-separated measurements would compare different moods and skew
    the e2e/decode ratio. The single timing discipline for every window."""
    dt = dt_dec = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        m.match_many(traces)
        dt = min(dt, time.perf_counter() - t0)
        t0 = time.perf_counter()
        m._decode_many(traces)
        dt_dec = min(dt_dec, time.perf_counter() - t0)
    return dt, dt_dec


def _oracle_audit(ts, jax_matcher, traces, n: int, config=None,
                  force_fresh: bool = False):
    """Fidelity vs the exact-Dijkstra CPU oracle on n traces. Returns
    (disagreement, cpu_pps, n, source) — source is "cache" when the
    oracle records were replayed from disk, "fresh" when recomputed
    (VERDICT r3 weak #3: fidelity provenance must be visible in the
    capture). ``force_fresh`` skips the cache read (the per-run fresh
    rotation leg, VERDICT r4 weak #2 — every capture must contain at
    least one freshly computed oracle comparison). ``config`` carries
    mode presets (bicycle audit); the matcher params are part of the
    cache key either way.

    The oracle's output is a PURE function of (tile, traces, params), so
    its (segment_id, length) pairs — all the fidelity metric reads — are
    cached on disk keyed by tile + trace content; the oracle pass was
    ~half the composite bench's wall time. The jax side is always matched
    fresh, and the CPU throughput anchor is re-measured on a small
    subsample on cache hits so every published number is a measurement.
    """
    import zlib

    import numpy as np

    from reporter_tpu.config import Config
    from reporter_tpu.matcher.api import SegmentMatcher
    from reporter_tpu.matcher.fidelity import mean_disagreement
    from reporter_tpu.matcher.segments import SegmentRecord

    import reporter_tpu.matcher.cpu_reference as _cpu_mod
    import reporter_tpu.matcher.fidelity as _fid_mod
    import reporter_tpu.matcher.segments as _seg_mod

    import dataclasses

    cfg = config or Config()
    crc = zlib.crc32(ts.edge_len.tobytes())
    crc = zlib.crc32(ts.ban_from.tobytes(), crc)
    crc = zlib.crc32(ts.ban_to.tobytes(), crc)
    # the oracle's CODE and params key the cache too: editing the CPU
    # matcher (or MatcherParams defaults/presets) must invalidate, or the
    # bench would publish fidelity vs a stale oracle's output
    for mod in (_cpu_mod, _seg_mod, _fid_mod):
        with open(mod.__file__, "rb") as f:
            crc = zlib.crc32(f.read(), crc)
    crc = zlib.crc32(repr(cfg.matcher).encode(), crc)
    for t in traces[:n]:
        crc = zlib.crc32(np.ascontiguousarray(t.xy).tobytes(), crc)
    path = _repo_path(f".bench_oracle_{ts.name}_{n}_"
                      f"{crc & 0xFFFFFFFF:08x}.npz")
    cpu = SegmentMatcher(ts, dataclasses.replace(
        cfg, matcher_backend="reference_cpu"))
    rc = None
    if not force_fresh and os.path.exists(path):
        try:
            with np.load(path) as z:
                seg, length, bounds = z["seg"], z["length"], z["bounds"]
            rc = [[SegmentRecord(int(s), [], -1.0, -1.0, float(ln), s < 0)
                   for s, ln in zip(seg[a:b], length[a:b])]
                  for a, b in zip(bounds[:-1], bounds[1:])]
            # fresh throughput anchor on a subsample (the cached records
            # settle fidelity; throughput must be measured, not replayed);
            # untimed warm-up first so lazy init stays out of the window
            n_sub = min(16, n)
            cpu.match_many(traces[:1])
            t0 = time.perf_counter()
            cpu.match_many(traces[:n_sub])
            cpu_pps = (sum(len(t.xy) for t in traces[:n_sub])
                       / (time.perf_counter() - t0))
        except Exception:
            rc = None               # stale/corrupt cache: recompute
    source = "cache"
    if rc is None:
        source = "fresh"
        t0 = time.perf_counter()
        rc = cpu.match_many(traces[:n])
        cpu_pps = (sum(len(t.xy) for t in traces[:n])
                   / (time.perf_counter() - t0))
        if not force_fresh:
            # rotation legs never read their cache back (the window moves
            # every run) — don't litter the repo with orphan npz files
            bounds = np.cumsum([0] + [len(r) for r in rc])
            np.savez(path,
                     seg=np.asarray([x.segment_id for r in rc for x in r],
                                    np.int64),
                     length=np.asarray([x.length for r in rc for x in r]),
                     bounds=bounds.astype(np.int64))
    rj = jax_matcher.match_many(traces[:n])
    return mean_disagreement(rj, rc), cpu_pps, n, source


def _reach_audit_cached(ts, traces_xy, label: str) -> dict:
    """Reach-table miss-rate audit (tiles/reach_audit) with a disk cache:
    the audit is a pure function of (tile, traces, params, audit code) and
    costs ~16 s/trace at xl scale on this one-core host. Summary dict
    gains a ``source`` field (cache|fresh) like the oracle's."""
    import json as _json
    import zlib

    import numpy as np

    import reporter_tpu.tiles.reach_audit as _ra_mod
    from reporter_tpu.config import MatcherParams
    from reporter_tpu.tiles.reach_audit import audit_reach

    crc = zlib.crc32(ts.edge_len.tobytes())
    crc = zlib.crc32(ts.reach_dist.tobytes(), crc)
    with open(_ra_mod.__file__, "rb") as f:
        crc = zlib.crc32(f.read(), crc)
    crc = zlib.crc32(repr(MatcherParams()).encode(), crc)
    for xy in traces_xy:
        crc = zlib.crc32(np.ascontiguousarray(xy).tobytes(), crc)
    path = _repo_path(f".bench_reach_{label}_{len(traces_xy)}_"
                      f"{crc & 0xFFFFFFFF:08x}.json")
    if os.path.exists(path):
        try:
            with open(path) as f:
                return {**_json.load(f), "source": "cache"}
        except Exception:
            pass
    summary = audit_reach(ts, traces_xy).summary()
    with open(path, "w") as f:
        _json.dump(summary, f)
    return {**summary, "source": "fresh"}


def _streaming_bench(ts, traces, n_stream: int) -> dict:
    """BASELINE config 5: sustained probes/s through the full streaming
    worker (ingest queue poll → per-uuid buffers → device match → report
    build → histogram update → delta flush). The producer side (payload
    dicts, queue appends) is pre-staged untimed — the pipeline's consume/
    flush/commit loop is the measured system, as it would be with an
    external broker feeding it."""
    import numpy as np

    from reporter_tpu.config import Config, StreamingConfig
    from reporter_tpu.geometry import xy_to_lonlat
    from reporter_tpu.streaming.pipeline import StreamPipeline
    from reporter_tpu.streaming.queue import IngestQueue

    sub = traces[:n_stream]
    queue = IngestQueue(4)
    # firehose interleaving: every vehicle's point k before any point k+1
    # (the shape a real broker delivers a city's probes in). 40-point
    # flush waves keep the matcher fed with mid-size chunks instead of
    # re-running the batch bench.
    cfg = Config(matcher_backend="jax",
                 streaming=StreamingConfig(flush_min_points=40,
                                           poll_max_records=65536,
                                           hist_flush_interval=0.0))
    origin = np.asarray(ts.meta.origin_lonlat)
    n_pts = len(sub[0].xy)
    lonlat = [xy_to_lonlat(np.asarray(t.xy, np.float64), origin)
              for t in sub]
    for k in range(n_pts):
        for i, t in enumerate(sub):
            queue.append({"uuid": t.uuid, "lat": float(lonlat[i][k, 1]),
                          "lon": float(lonlat[i][k, 0]),
                          "time": float(t.times[k])})
    pipe = StreamPipeline(ts, cfg, queue=queue)
    t0 = time.perf_counter()
    reports = 0
    while queue.lag(pipe.committed) > 0:
        before = queue.lag(pipe.committed)
        reports += pipe.step()
        if queue.lag(pipe.committed) >= before:
            # residual sub-flush_min_points buffers pin the commit floor;
            # don't busy-spin until flush_max_age — drain now
            break
    reports += pipe.drain()
    flush_t0 = time.perf_counter()
    flushed = pipe.flush_histograms()
    dt_flush = time.perf_counter() - flush_t0
    dt = time.perf_counter() - t0
    probes = len(sub) * n_pts
    return {
        "config": f"{len(sub)} vehicles x {n_pts}pt firehose, tile={ts.name}",
        "probes_per_sec": round(probes / dt, 1),
        "reports": int(reports),
        "steps": pipe.steps,
        "hist_segments_flushed": int(flushed),
        "hist_flush_ms": round(dt_flush * 1e3, 2),
        "hist_rows_nonzero": int(len(pipe.hist.nonzero_rows())),
        "seconds": round(dt, 3),
    }


def _stage_round_batches(ts, traces, n_stream: int, steps_per_batch: int):
    """Pre-stage the firehose as ProbeColumns round batches (producer side,
    untimed): every vehicle's point k before any point k+1, steps_per_batch
    time-steps per batch."""
    import numpy as np

    from reporter_tpu.geometry import xy_to_lonlat
    from reporter_tpu.streaming.columnar import ProbeColumns

    sub = traces[:n_stream]
    origin = np.asarray(ts.meta.origin_lonlat)
    n_pts = len(sub[0].xy)
    V = len(sub)
    uuids = np.array([t.uuid for t in sub])
    lonlat = np.stack([xy_to_lonlat(np.asarray(t.xy, np.float64), origin)
                       for t in sub])                      # [V, T, 2]
    times = np.stack([np.asarray(t.times, np.float64) for t in sub])
    batches = []
    for lo in range(0, n_pts, steps_per_batch):
        hi = min(n_pts, lo + steps_per_batch)
        k = hi - lo
        u = np.repeat(uuids[None, :], k, 0).ravel()
        ll = lonlat[:, lo:hi].transpose(1, 0, 2).reshape(-1, 2)
        tt = times[:, lo:hi].T.ravel()
        batches.append(ProbeColumns(u, ll[:, 1].copy(), ll[:, 0].copy(),
                                    tt.copy(),
                                    np.full(k * V, np.nan, np.float32)))
    return batches, V, n_pts


def _drive_columnar_workers(ts, traces, n_stream: int,
                            subsets: "tuple[tuple[int, ...], ...]",
                            ) -> "tuple[float, int, list]":
    """Shared columnar-firehose pump: pre-stage the batches (untimed),
    then drain the broker with one ColumnarStreamPipeline per partition
    subset — concurrently when there are several (threads: each worker's
    device dispatches overlap the others' host legs). ONE config and one
    drive loop, so the 1-vs-2-worker comparison can never drift apart.
    Returns (seconds, reports, pipes); a worker exception fails the leg
    (re-raised after join), never a silently-short pump."""
    import threading

    from reporter_tpu.config import Config, StreamingConfig
    from reporter_tpu.streaming.columnar import (ColumnarIngestQueue,
                                                 ColumnarStreamPipeline)

    batches, V, n_pts = _stage_round_batches(ts, traces, n_stream,
                                             steps_per_batch=10)
    queue = ColumnarIngestQueue(4)
    for b in batches:
        queue.append_columns(b)
    cfg = Config(matcher_backend="jax",
                 streaming=StreamingConfig(flush_min_points=40,
                                           poll_max_records=300_000,
                                           hist_flush_interval=0.0))
    pipes = [ColumnarStreamPipeline(ts, cfg, queue=queue, partitions=sub)
             for sub in subsets]
    reports = [0] * len(pipes)
    failures: list = []

    def drive(i):
        try:
            pipe = pipes[i]
            while queue.lag(pipe.committed) > 0:
                before = queue.lag(pipe.committed)
                reports[i] += pipe.step()
                st = pipe.stats()
                if (queue.lag(pipe.committed) >= before
                        and st["inflight_waves"] == 0
                        and st["publish_pending"] == 0):
                    # no progress with nothing in flight: only residual
                    # sub-flush_min_points buffers pin the commit floor;
                    # don't busy-spin until flush_max_age — drain now
                    break
            reports[i] += pipe.drain()
            pipe.close()
        except BaseException as exc:     # re-raised below: a dead worker
            failures.append(exc)         # must fail the leg, not shorten it

    t0 = time.perf_counter()
    if len(pipes) == 1:
        drive(0)
    else:
        threads = [threading.Thread(target=drive, args=(i,))
                   for i in range(len(pipes))]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    dt = time.perf_counter() - t0
    if failures:
        raise failures[0]
    return dt, int(sum(reports)), pipes


def _streaming_columnar_bench(ts, traces, n_stream: int) -> dict:
    """config 5, columnar worker (streaming/columnar.py — VERDICT r4 #2):
    the same firehose as _streaming_bench through ColumnarStreamPipeline.
    Producer pre-staged untimed; the measured system is batch poll →
    columnar consume → flush (device match → vectorized report build →
    histograms)."""
    dt, reports, pipes = _drive_columnar_workers(
        ts, traces, n_stream, subsets=((0, 1, 2, 3),))
    pipe = pipes[0]
    t0 = time.perf_counter()
    flushed = pipe.flush_histograms()
    dt += time.perf_counter() - t0       # the flush stays in the window,
    #                                      as the r4 dict leg counted it
    V = min(n_stream, len(traces))
    n_pts = len(traces[0].xy)
    probes = V * n_pts
    st = pipe.stats()
    return {
        "config": (f"{V} vehicles x {n_pts}pt "
                   f"columnar firehose, tile={ts.name}"),
        "probes_per_sec": round(probes / dt, 1),
        "reports": int(reports),
        "steps": pipe.steps,
        "match_seconds": round(st["match_seconds"], 3),
        "host_seconds": round(dt - st["match_seconds"], 3),
        "hist_segments_flushed": int(flushed),
        "hist_rows_nonzero": st["hist_rows"],
        "seconds": round(dt, 3),
    }


def _streaming_two_workers(ts, traces, n_stream: int) -> dict:
    """Consumer-group scale-out, columnar flavor: TWO workers over
    disjoint partition subsets of one broker drain the same firehose
    (shared pump — _drive_columnar_workers — so config and drive loop
    are identical to the single-worker leg). The measured question: does
    a second worker on the same chip add throughput over one (it shares
    the device but not the host-side consume/flush/walk)?"""
    dt, reports, pipes = _drive_columnar_workers(
        ts, traces, n_stream, subsets=((0, 1), (2, 3)))
    V = min(n_stream, len(traces))
    n_pts = len(traces[0].xy)
    return {
        "config": (f"2 workers x 2 partitions, {V} vehicles x {n_pts}pt, "
                   f"tile={ts.name}"),
        "probes_per_sec": round(V * n_pts / dt, 1),
        "reports": int(reports),
        "seconds": round(dt, 3),
        "per_worker_match_seconds": [round(p.stats()["match_seconds"], 3)
                                     for p in pipes],
    }


def _soak_point(ts, traces, n_stream: int, seconds: float,
                offered_pps: int, wave_points: int,
                autotune: bool = False, drain_timeout: float = 30.0,
                queue_bound: "int | None" = None,
                overload_policy: str = "reject",
                collect_stages: bool = False) -> dict:
    """One live operating point: a paced producer THREAD offers
    ``offered_pps`` into the columnar broker (a real broker keeps
    receiving during a flush — a slow flush shows up as LAG, never as a
    silently reduced offer) while ONE PIPELINED columnar worker
    (pipeline_depth=1: wave N on the device, wave N−1 on the publisher
    thread, wave N+1 consuming) polls, flushes, and truncates retention.
    When the offer window closes the producer stops and the worker gets
    ``drain_timeout`` to take lag to zero — "keeping up" is end lag 0
    after a bounded drain, measured, not asserted.

    Shared by the soak (one long autotuned point), the capacity grid
    (offer × wave sweep), and the overload leg (bounded broker at 2× the
    sustainable rate, counted shedding). Single-worker shape on purpose:
    the host's ONE CORE runs producer and consumer together, and a
    second consumer thread regresses here (r5 measurement); scale-out is
    partition reassignment to more hosts."""
    import threading

    import numpy as np

    from reporter_tpu.config import Config, StreamingConfig
    from reporter_tpu.streaming.columnar import (ColumnarIngestQueue,
                                                 ColumnarStreamPipeline)

    batches, V, n_pts = _stage_round_batches(ts, traces, n_stream,
                                             steps_per_batch=2)
    cycle_span = float(n_pts)       # shift times each replay cycle so a
    #                                 vehicle's stream keeps moving forward
    queue = ColumnarIngestQueue(4, max_records_per_partition=queue_bound,
                                overload_policy=overload_policy)
    cfg = Config(matcher_backend="jax",
                 streaming=StreamingConfig(flush_min_points=wave_points,
                                           poll_max_records=300_000,
                                           hist_flush_interval=0.0,
                                           pipeline_depth=1,
                                           wave_autotune=autotune,
                                           wave_min_points=40,
                                           wave_max_points=960,
                                           wave_target_latency=2.0))
    # collect_stages: read the pipeline's per-probe stage components at
    # the end (the latency-attribution leg's traced arm — the CALLER
    # enables the global tracer, under try/finally, so an exception
    # mid-soak can't leave every later leg silently traced).
    pipe = ColumnarStreamPipeline(ts, cfg, queue=queue)
    lat_chunks: list = []

    def _take_latency():
        if pipe.last_flush_latency is not None:
            lat_chunks.append(pipe.last_flush_latency)
            pipe.last_flush_latency = None

    max_lag = 0
    max_retained = 0
    state = {"offered": 0, "accepted": 0}
    failures: list = []
    t0 = time.perf_counter()
    deadline = t0 + seconds

    def producer():
        try:
            bi = 0
            while True:
                now = time.perf_counter()
                if now >= deadline:
                    return
                while state["offered"] < (now - t0) * offered_pps:
                    b = batches[bi % len(batches)]
                    cyc = bi // len(batches)
                    if cyc:
                        b = b._replace(time=b.time + cyc * cycle_span)
                    state["accepted"] += queue.append_columns(b)
                    state["offered"] += b.n
                    bi += 1
                time.sleep(0.005)
        except BaseException as exc:
            failures.append(exc)

    prod = threading.Thread(target=producer)
    prod.start()
    try:
        while time.perf_counter() < deadline:
            pipe.step()
            _take_latency()
            max_lag = max(max_lag, queue.lag(pipe.committed))
            if queue_bound is not None and pipe.steps % 8 == 0:
                max_retained = max(max_retained, sum(
                    queue.end_offset(p) - queue.retention_floor(p)
                    for p in range(queue.num_partitions)))
            if pipe.steps % 32 == 0:
                queue.truncate(pipe.committed)   # broker retention
    finally:
        prod.join()
    if failures:
        raise failures[0]
    offer_dt = time.perf_counter() - t0
    consumed_at_offer_end = int(sum(pipe.committed))
    lag_at_offer_end = int(queue.lag(pipe.committed))

    # drain phase: offer stopped; a keeping-up worker reaches lag 0 fast
    drain_t0 = time.perf_counter()
    while (queue.lag(pipe.committed) > 0
           and time.perf_counter() - drain_t0 < drain_timeout):
        pipe.drain()
        _take_latency()
    drain_s = time.perf_counter() - drain_t0
    end_lag = int(queue.lag(pipe.committed))
    st = pipe.stats()
    pipe.close()
    stage_samples = pipe.take_stage_samples() if collect_stages else None
    # exact probes taken off the broker (committed floor); counting
    # matched+buffered instead would double-count cache-tail points that
    # re-enter each flush's merged trace
    lat = (np.concatenate(lat_chunks) if lat_chunks
           else np.zeros(1))
    out = {
        "config": (f"{V} vehicles, offered {offered_pps / 1e3:.0f}k pps "
                   f"for {seconds:.0f}s, threaded producer, pipelined "
                   f"wave={wave_points}{'+auto' if autotune else ''}, "
                   f"tile={ts.name}"),
        "seconds": round(offer_dt, 1),
        "offered_pps": offered_pps,
        "offered_probes": int(state["offered"]),
        "produced_probes": int(state["accepted"]),
        "consumed_probes": consumed_at_offer_end,
        "sustained_pps": round(consumed_at_offer_end / offer_dt, 1),
        "lag_at_offer_end": lag_at_offer_end,
        "end_lag": end_lag,
        "drain_seconds": round(drain_s, 1),
        "max_lag": int(max_lag),
        "reports": st["reports"],
        "p50_probe_to_report_ms": round(float(np.median(lat)) * 1e3, 1),
        "p99_probe_to_report_ms": round(
            float(np.percentile(lat, 99)) * 1e3, 1),
        "latency_samples": int(lat.size),
        "match_seconds": round(st["match_seconds"], 2),
        "wave_points_end": st["wave_points"],
    }
    if collect_stages:
        out["stage_attribution"] = _attribution_from_samples(stage_samples)
    if queue_bound is not None:
        out.update({
            "broker_bound_per_partition": queue_bound,
            "broker_policy": overload_policy,
            "broker_rejected": st.get("broker_rejected", 0),
            "broker_dropped_oldest": st.get("broker_dropped_oldest", 0),
            "consumer_overrun": st.get("overrun", 0),
            "max_retained_records": int(max_retained),
        })
    return out


def _streaming_soak(ts, traces, n_stream: int, seconds: float = 32.0,
                    offered_pps: int = 100_000) -> dict:
    """Steady-arrival soak at the held 100k offer (VERDICT r5 missing
    #1): the pipelined worker with the adaptive wave controller. The
    acceptance shape: sustained ≥ the offer, lag at offer end bounded and
    drained to 0 within the drain window, p50 probe→report under the 2 s
    controller target + wave fill time."""
    return _soak_point(ts, traces, n_stream, seconds, offered_pps,
                       wave_points=120, autotune=True)


def _soak_prepare_ab(ts, traces, n_vehicles: int = 96,
                     wave_pts: int = 48, n_waves: int = 8,
                     draws: int = 3) -> dict:
    """detail.streaming_soak.prepare_ab (r22): the closed-loop
    pipelined-vs-serial prepare A/B, journaled inside the soak leg so
    --resume/--legs semantics are unchanged. Two claims:

      - IDENTITY: both arms drive the SAME wave schedule (each wave is a
        code-disjoint vehicle group appended and staged one at a time,
        so wave composition is schedule-determined, never harvest-thread
        timing) and every dispatched slice is hashed at the ONE
        ``submit_prepared`` seam both arms funnel through — equal
        digests + equal published report streams = bit-identity.
      - SPEEDUP: mechanism validation in the r17-autotune injected-timer
        style, on EVERY composite. The timing draws REPLAY the identity
        runs' device results (keyed by the same wire digest — a miss
        falls back to the real call) and a calibrated sleep stands in
        for device flight, so the device leg is a pure GIL-release the
        way a chip/link is: on this one-core host a real CPU match
        timeshares the core and can never overlap host work. Flight is
        0.8x the replayed serial arm's per-wave host time — large
        enough to cover the read-ahead prepare, small enough that the
        hidden host share stays visible in the ratio. The pipelined arm
        must hide wave N+1's prepare AND wave N-1's report build behind
        that flight (the three-stage overlap; best-of ``draws``). The
        ratio validates the OVERLAP MECHANISM; it is never a throughput
        claim (the soak's sustained_pps carries those).

    The first wave of every run is a WARM wave outside the timed window
    (compile + lazy thread start); all waves share one compiled shape."""
    import hashlib
    from types import SimpleNamespace

    import numpy as np

    from reporter_tpu.config import Config, ServiceConfig, StreamingConfig
    from reporter_tpu.streaming.columnar import (ColumnarIngestQueue,
                                                 ColumnarStreamPipeline)

    sub = traces[:n_vehicles]
    V = len(sub)
    P = min(wave_pts, len(sub[0].xy))
    W = n_waves
    wave_batches = []
    for w in range(W):
        wtr = [SimpleNamespace(uuid=f"{t.uuid}|ab{w}",
                               xy=np.asarray(t.xy)[:P],
                               times=np.asarray(t.times)[:P])
               for t in sub]
        b, _, _ = _stage_round_batches(ts, wtr, V, steps_per_batch=P)
        wave_batches.append(b[0])

    replay_cache: dict = {}

    def _run(pipelined: bool, flight_s: float = 0.0, replay: bool = False,
             wires: "list | None" = None,
             reports: "list | None" = None) -> dict:
        queue = ColumnarIngestQueue(4)
        # always a stub transport: the A/B must never touch a real
        # socket (the URL is a placeholder, and a DNS stall would time
        # the resolver, not the loop)
        transport = ((lambda u, b: 200) if reports is None
                     else (lambda u, b: reports.append(json.loads(b))
                           or 200))
        cfg = Config(
            matcher_backend="jax",
            service=ServiceConfig(datastore_url="http://prepare-ab.bench/",
                                  pipeline_prepare=pipelined),
            streaming=StreamingConfig(flush_min_points=P,
                                      poll_max_records=300_000,
                                      hist_flush_interval=0.0,
                                      pipeline_depth=1))
        pipe = ColumnarStreamPipeline(ts, cfg, queue=queue,
                                      transport=transport)
        calls = [0]
        real = pipe.matcher.submit_prepared

        def tapped(ps):
            calls[0] += 1
            h = hashlib.sha256()
            h.update(np.int64([ps.b, ps.mode]).tobytes())
            h.update(np.asarray(ps.ws, np.int64).tobytes())
            payload = ps.payload if ps.mode else ps.pts
            h.update(np.ascontiguousarray(payload).tobytes())
            h.update(np.ascontiguousarray(ps.origins).tobytes()
                     if ps.origins is not None else b"-")
            h.update(np.ascontiguousarray(ps.lens).tobytes())
            h.update(np.ascontiguousarray(ps.scale).tobytes()
                     if ps.scale is not None else b"-")
            key = h.hexdigest()
            if wires is not None:
                wires.append(key)
            # timing draws replay the identity runs' results — the
            # device leg becomes the sleep alone. The wire digest is the
            # key, so a replay hit is ALSO a wire-identity check: any
            # deviation misses and pays the real (still correct) call.
            if replay and key in replay_cache:
                out = replay_cache[key]
            else:
                out = real(ps)
                replay_cache[key] = out
            if flight_s:
                time.sleep(flight_s)
            return out

        pipe.matcher.submit_prepared = tapped

        def pump(batch):
            # append ONE wave group, poll it (the first step takes the
            # whole group — the poll bound exceeds any wave here), then
            # step until its rows leave the column buffer (staged on the
            # read-ahead path, or submitted once the serial arm's single
            # slot frees) — waves never merge, so composition is
            # identical in both arms
            queue.append_columns(batch)
            pipe.step()
            while pipe.stats()["buffered_points"] > 0:
                pipe.step()
                time.sleep(0.0002)

        pump(wave_batches[0])
        while pipe.waves_completed < 1:          # warm wave: compile +
            pipe.step()                          # thread start, untimed
            time.sleep(0.0002)
        calls0 = calls[0]
        t0 = time.perf_counter()
        for b in wave_batches[1:]:
            pump(b)
        while queue.lag(pipe.committed) > 0:
            pipe.drain()
        elapsed = time.perf_counter() - t0
        st = pipe.stats()
        waves = int(pipe.waves_completed)
        pipe.close()
        return {"elapsed": elapsed, "timed_calls": calls[0] - calls0,
                "waves": waves, "stats": st}

    def _rows(reports):
        out = []
        for payload in reports:
            for r in payload.get("reports", []):
                out.append((r["id"],
                            r["next_id"] if r["next_id"] is not None
                            else -1, round(r["t0"], 6), round(r["t1"], 6),
                            round(r["length"], 4)))
        return sorted(out)

    # identity: both arms at zero flight with the REAL matcher, wires +
    # reports compared (these runs also fill the replay cache)
    w_ser, r_ser = [], []
    _run(False, wires=w_ser, reports=r_ser)
    w_pp, r_pp = [], []
    _run(True, wires=w_pp, reports=r_pp)
    wire_ok = bool(w_pp == w_ser and len(w_ser) > 0)
    reports_ok = bool(_rows(r_pp) == _rows(r_ser) and len(r_ser) > 0)

    # calibration: a replayed zero-flight serial run measures the pure
    # per-wave host time H0; flight = 0.8*H0 (see docstring)
    cal = _run(False, replay=True)
    h0 = cal["elapsed"] / max(1, cal["waves"] - 1)
    flight = min(0.25, max(0.002, 0.8 * h0))
    serial_draws = [_run(False, flight_s=flight, replay=True)
                    for _ in range(draws)]
    pipelined_draws = [_run(True, flight_s=flight, replay=True)
                       for _ in range(draws)]
    best_s = min(d["elapsed"] for d in serial_draws)
    best_p = min(d["elapsed"] for d in pipelined_draws)
    overlap = max(d["stats"]["prepare_overlap_pct"]
                  for d in pipelined_draws)
    return {
        "config": (f"{V} vehicles x {P} pts per wave, {W} waves (1 warm),"
                   f" injected flight {flight * 1e3:.1f} ms/dispatch"
                   f" over replayed device results, tile={ts.name}"),
        "records": W * V * P,
        "waves": W,
        "injected_flight_s": round(flight, 4),
        "wire_bytes_identical": wire_ok,
        "reports_identical": reports_ok,
        "serial_draw_s": [round(d["elapsed"], 3) for d in serial_draws],
        "pipelined_draw_s": [round(d["elapsed"], 3)
                             for d in pipelined_draws],
        "pipelined_speedup": round(best_s / best_p, 2) if best_p else None,
        "prepare_overlap_pct": round(float(overlap), 1),
    }


def _streaming_capacity(ts, traces, n_stream: int) -> dict:
    """detail.streaming_capacity: the offered-rate × wave-size grid the
    soak's operating point is chosen FROM (VERDICT r5 advice #1 — the
    throughput/latency trade as a recorded curve, not prose). Each point
    is a short held-offer soak reporting sustained pps, end/max lag, and
    p50/p99 probe→report. Dwell is env-tunable
    (REPORTER_BENCH_CAP_SECONDS, default 6 s per point)."""
    dwell = float(os.environ.get("REPORTER_BENCH_CAP_SECONDS", "6"))
    offers = (25_000, 50_000, 100_000, 150_000, 250_000)
    waves = (120, 360)
    grid = []
    for wave in waves:
        for offer in offers:
            r = _soak_point(ts, traces, n_stream, dwell, offer,
                            wave_points=wave, autotune=False,
                            drain_timeout=10.0)
            grid.append({
                "offered_pps": offer,
                "wave_points": wave,
                "sustained_pps": r["sustained_pps"],
                "lag_at_offer_end": r["lag_at_offer_end"],
                "end_lag": r["end_lag"],
                "drain_seconds": r["drain_seconds"],
                "max_lag": r["max_lag"],
                "p50_probe_to_report_ms": r["p50_probe_to_report_ms"],
                "p99_probe_to_report_ms": r["p99_probe_to_report_ms"],
            })
    held = [g for g in grid if g["sustained_pps"] >= 0.97 * g["offered_pps"]
            and g["end_lag"] == 0]
    return {
        "config": (f"{min(n_stream, len(traces))} vehicles, "
                   f"{dwell:.0f}s/point, offers × waves = "
                   f"{[o // 1000 for o in offers]}k × {list(waves)}, "
                   f"pipelined, tile={ts.name}"),
        "grid": grid,
        "best_held_pps": (max(g["sustained_pps"] for g in held)
                          if held else 0.0),
    }


def _streaming_overload(ts, traces, n_stream: int,
                        sustainable_pps: float) -> dict:
    """Overload soak at 2× the sustainable rate against a BOUNDED broker
    (VERDICT r5 missing #2): retained records are capped per partition,
    overflow is counted producer-side rejection — memory stays flat by
    construction and the leg records the measured max backlog + every
    shed count as the worker's /stats would surface them."""
    offer = int(2 * max(sustainable_pps, 50_000))
    return _soak_point(ts, traces, n_stream, seconds=12.0,
                       offered_pps=offer, wave_points=360, autotune=False,
                       drain_timeout=20.0, queue_bound=150_000,
                       overload_policy="reject")


# ---------------------------------------------------------------------------
# Latency attribution (ISSUE 5 tentpole): the per-stage decomposition of
# probe→report time as a RECORDED, reconciled artifact — the round-5
# verdict's "where do the 2.5-20 s go" answered by spans, not prose.

_ATTRIBUTION_STAGES = ("broker_dwell", "prepare", "device_match",
                      "report_build")


def _attribution_from_samples(samples: "dict | None") -> dict:
    """Per-stage decomposition of the e2e p50/p99 + the reconciliation
    ratio, from the pipeline's take_stage_samples() arrays. Pure numpy
    (schema-tested without a pipeline).

    The four attribution stages partition each probe's arrival→report
    timeline at the wave's recorded boundaries, so their per-probe sum
    equals the e2e sample EXACTLY. Each stage's reported p50_ms/p99_ms
    is that stage's MEAN over the probes whose e2e lands in a narrow
    quantile window around the e2e p50/p99 — "what the median (p99)
    probe's time was spent on" — NOT the stage's marginal quantile:
    marginal p50s of right-skewed stages do not sum to the p50 of the
    sum (measured 0.54× on a CPU validation run), while the conditional
    decomposition sums to the window's mean e2e exactly, leaving only
    window-mean-vs-percentile slack in the recorded ratio (the ±15%
    acceptance bound absorbs it). 'publish' (the async POST attempt,
    per wave) lands after the probe→report cut and is reported
    alongside as marginal quantiles, excluded from the reconciling
    sum."""
    import numpy as np

    if not samples or "e2e" not in samples or not len(samples["e2e"]):
        return {"samples": 0, "stages": {}, "e2e_p50_ms": None,
                "e2e_p99_ms": None, "stage_sum_p50_ms": None,
                "stage_sum_over_e2e_p50": None,
                "reconciles_within_15pct": None}
    e2e = samples["e2e"]
    order = np.argsort(e2e, kind="stable")

    def _window(lo_q, hi_q):
        lo = int(np.floor(lo_q * (len(order) - 1)))
        hi = int(np.ceil(hi_q * (len(order) - 1))) + 1
        return order[lo:max(lo + 1, hi)]

    w50 = _window(0.45, 0.55)
    w99 = _window(0.985, 0.995)
    stages = {}
    sum_p50 = 0.0
    for name in _ATTRIBUTION_STAGES:
        comp = samples[name]
        p50 = round(float(comp[w50].mean()) * 1e3, 2)
        p99 = round(float(comp[w99].mean()) * 1e3, 2)
        stages[name] = {"p50_ms": p50, "p99_ms": p99}
        sum_p50 += p50
    if "publish" in samples and len(samples["publish"]):
        pub = samples["publish"]
        stages["publish"] = {
            "p50_ms": round(float(np.percentile(pub, 50)) * 1e3, 2),
            "p99_ms": round(float(np.percentile(pub, 99)) * 1e3, 2),
            "note": "async POST attempt, per wave; "
                    "after the probe->report cut"}
    e_p50 = round(float(np.percentile(e2e, 50)) * 1e3, 2)
    e_p99 = round(float(np.percentile(e2e, 99)) * 1e3, 2)
    ratio = round(sum_p50 / e_p50, 4) if e_p50 else None
    return {
        "samples": int(len(e2e)),
        "stages": stages,
        "e2e_p50_ms": e_p50,
        "e2e_p99_ms": e_p99,
        "stage_sum_p50_ms": round(sum_p50, 2),
        "stage_sum_over_e2e_p50": ratio,
        "reconciles_within_15pct": (None if ratio is None
                                    else bool(abs(ratio - 1.0) <= 0.15)),
    }


def _service_face_attribution(ts, traces, n_req: int = 24,
                              conc: int = 4) -> dict:
    """The serving twin: stage p50s from the metrics series a scheduler
    deployment already exports — queue age (admission→dispatch), device
    match, report build, publish — against the measured request p50 of a
    small concurrent closed loop. Component p50s come from per-BATCH /
    per-submission series while the e2e is per request, so this
    decomposition is indicative (recorded ratio, not acceptance-gated);
    the soak-side attribution above is the reconciled one."""
    import threading

    import numpy as np

    from reporter_tpu.config import Config
    from reporter_tpu.service.app import ReporterApp

    app = ReporterApp(ts, Config(matcher_backend="jax"),
                      transport=lambda u, b: 200)
    payloads = _service_payloads(ts, traces, n_req, tag="lattr")
    if not payloads:
        return {"requests": 0}
    app.report_many([payloads[0]])      # compile warmup, untimed
    durs: list = []
    errors: list = []
    lock = threading.Lock()

    def worker(chunk):
        for p in chunk:
            t0 = time.perf_counter()
            try:
                app.report_many([p])
            except Exception as exc:          # recorded, not fatal
                with lock:
                    errors.append(repr(exc))
                continue
            with lock:
                durs.append(time.perf_counter() - t0)

    threads = [threading.Thread(target=worker, args=(payloads[i::conc],))
               for i in range(conc)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = app.matcher.metrics.snapshot()
    app.close()

    def _ms(key):
        v = snap.get(key)
        return (None if v is None or not np.isfinite(v)
                else round(float(v) * 1e3, 2))

    stages = {
        "sched_queue": _ms("sched_queue_age_seconds_p50"),
        "device_match": _ms("match_seconds_p50"),
        "report_build": _ms("report_build_seconds_p50"),
        "publish": _ms("publish_seconds_p50"),
    }
    e2e_p50 = (round(float(np.median(durs)) * 1e3, 2) if durs else None)
    known = [v for v in stages.values() if v is not None]
    return {
        "requests": len(durs),
        "concurrency": conc,
        "stages_p50_ms": stages,
        "request_p50_ms": e2e_p50,
        "stage_sum_over_request_p50": (
            round(sum(known) / e2e_p50, 4) if e2e_p50 and known else None),
        **({"errors": errors[:4]} if errors else {}),
    }


def _latency_attribution(ts, traces, n_stream: int, offered_pps: int,
                         seconds: float = 8.0) -> dict:
    """detail.latency_attribution: two back-to-back soak points at the
    SAME held offer — tracing ON (stage spans + per-probe attribution)
    vs tracing OFF — so the capture carries (a) the per-stage
    decomposition of probe→report p50/p99 with its reconciliation
    against the independently accumulated e2e samples, and (b) the
    measured throughput cost of leaving tracing on (the <3% acceptance
    A/B), under the same link mood. Plus the service-face decomposition
    from the metrics series."""
    from reporter_tpu.utils import tracing

    # untimed warm point first: the arm that runs cold pays first-compile
    # for its whole window (measured: a cold traced arm recorded 0
    # sustained pps) — the A/B must compare tracing cost, not compile
    # order
    _soak_point(ts, traces, n_stream, min(3.0, seconds), offered_pps,
                wave_points=120)
    prev_traced = tracing.tracer().enabled
    try:
        tracing.configure(enabled=True)
        on = _soak_point(ts, traces, n_stream, seconds, offered_pps,
                         wave_points=120, collect_stages=True)
        # the OFF arm must force-disable, not restore: under RTPU_TRACE=1
        # prev_traced is True and the "untraced" soak would run traced —
        # a traced-vs-traced A/B reading ~0% while labeled an A/B
        tracing.configure(enabled=False)
        off = _soak_point(ts, traces, n_stream, seconds, offered_pps,
                          wave_points=120)
    finally:
        # an exception mid-soak must not leave the process-global tracer
        # in the wrong state for every later leg (ON would silently tax
        # the composite's perf numbers; OFF would void an env-requested
        # trace)
        tracing.configure(enabled=prev_traced)
    s_on, s_off = on["sustained_pps"], off["sustained_pps"]
    overhead = (round((s_off - s_on) / s_off * 100.0, 2) if s_off else None)
    attribution = on.pop("stage_attribution")
    return {
        "config": on["config"] + ", traced-vs-untraced A/B",
        "offered_pps": offered_pps,
        **attribution,
        "soak_p50_probe_to_report_ms": on["p50_probe_to_report_ms"],
        "soak_p99_probe_to_report_ms": on["p99_probe_to_report_ms"],
        "sustained_pps_traced": s_on,
        "sustained_pps_untraced": s_off,
        "tracing_overhead_pct": overhead,
        "overhead_note": ("negative = noise in the traced arm's favor; "
                          "both arms held the same offer under the same "
                          "link mood"),
        "service_face": _service_face_attribution(ts, traces),
    }


def _prepare_bench(ts, traces, n: int = 2048, reps: int = 5) -> dict:
    """detail.prepare_bench (ISSUE 7 satellite): standalone host-prepare
    throughput — the submit leg's pad → i16 quantize → i8 delta pack on
    a real trace slice — as a native-vs-Python A/B, in krows/s of probe
    points through the prepare. Also re-proves the byte-identity
    contract on EVERY composite (the sweep_ab discipline): same wire
    mode, same buffer bytes, Morton keys included — so a native drift
    shows up as ``bytes_identical: false`` in the capture, not as a
    silent result fork in production."""
    import numpy as np

    from reporter_tpu.matcher import native_prepare
    from reporter_tpu.matcher.api import _bucket_len

    xys = [t.xy for t in traces[:n]]
    b = max(_bucket_len(len(xy)) for xy in xys)
    total = sum(len(xy) for xy in xys)
    t_py = _time_best(lambda: native_prepare.prepare_slice_python(xys, b),
                      reps)
    out = {
        "config": f"{len(xys)} traces x bucket {b}, tile={ts.name}",
        "rows": int(total),
        "bucket": int(b),
        "python_krows_per_s": round(total / t_py / 1e3, 1),
        "native_available": bool(native_prepare.available()),
    }
    if not native_prepare.available():
        out.update({"native_krows_per_s": None, "speedup": None,
                    "bytes_identical": None})
        return out
    t_nat = _time_best(lambda: native_prepare.prepare_slice(xys, b), reps)
    pm, ppts, plens, porg, ppay = native_prepare.prepare_slice_python(xys, b)
    nm, npts, nlens, norg, npay = native_prepare.prepare_slice(xys, b)
    same = (pm == nm and ppts.tobytes() == npts.tobytes()
            and plens.tobytes() == nlens.tobytes()
            and porg.tobytes() == norg.tobytes()
            and ((ppay is None and npay is None)
                 or ppay.tobytes() == npay.tobytes()))
    first = np.zeros((len(xys), 2), np.float64)
    for w, xy in enumerate(xys):
        if len(xy):
            first[w] = xy[0]
    same = same and bool(np.array_equal(
        native_prepare.morton_keys(first),
        native_prepare.morton_keys_python(first)))
    out.update({
        "native_krows_per_s": round(total / t_nat / 1e3, 1),
        "speedup": round(t_py / t_nat, 2),
        "wire_mode": int(nm),
        "bytes_identical": bool(same),
    })
    return out


# ---------------------------------------------------------------------------
# Chaos legs (ISSUE 4): kill-and-recover at soak scale, live multi-process
# consumer group, fault-injected publisher outage. The worker under test
# is a real SUBPROCESS of `python -m reporter_tpu.streaming` over a
# durable columnar broker dir, publishing to a local HTTP sink — so the
# SIGKILL is a real SIGKILL and the replay is the product path's replay.


def _rss_mb() -> "float | None":
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) / 1024.0
    except OSError:
        pass
    return None


def _report_sink():
    """Local datastore stand-in — delegates to the package's ONE fake
    datastore (distributed/supervisor.ReportSink, round 19) so the
    report multiset key and the r9 duplicates-vs-losses accounting can
    never fork between bench and the topology plane. Returns (server,
    state) in the historical shape the chaos legs consume: ``state``
    is a live mapping view over the sink (``reports`` multiset, row/
    post counters, perf_counter first/last timestamps); callers shut
    the server down via ``shutdown()``."""
    from reporter_tpu.distributed import ReportSink

    sink = ReportSink()

    class _State:
        """Read-only dict-shaped view over the live sink."""

        def __getitem__(self, key):
            if key == "reports":
                return sink.reports
            return sink.stats()[key]

    class _Srv:
        server_address = sink._server.server_address

        def shutdown(self):
            sink.close()

    return _Srv(), _State()


def _stage_durable_broker(ts, traces, n_stream: int, dirpath: str,
                          cycles: int = 1) -> int:
    """Pre-fill a durable columnar broker dir with the round-robin
    firehose (time-shifted per replay cycle, like the soak) — the
    immutable log every chaos worker run replays from offset 0 (or its
    checkpoint floor). Returns total probes appended."""
    from reporter_tpu.streaming.durable_columnar import (
        DurableColumnarIngestQueue,
    )

    batches, V, n_pts = _stage_round_batches(ts, traces, n_stream,
                                             steps_per_batch=4)
    q = DurableColumnarIngestQueue(dirpath, 4)
    total = 0
    for c in range(cycles):
        for b in batches:
            bb = b if c == 0 else b._replace(time=b.time + c * float(n_pts))
            q.append_columns(bb)
            total += bb.n
    q.close()
    return total


def _chaos_worker_config(dirpath: str) -> str:
    """One worker config for every chaos leg: count-triggered waves only
    (flush_max_age effectively off), pipelined, no interval histogram
    flush — so two runs over the same log flush the same waves and their
    report multisets are comparable."""
    path = os.path.join(dirpath, "worker_config.json")
    with open(path, "w") as f:
        json.dump({"streaming": {
            "flush_min_points": 40,
            # small polls on purpose: many waves per run, so the SIGKILL
            # lands mid-stream with waves in every state (in flight,
            # publish-pending, buffered) instead of around one giant wave
            "poll_max_records": 2_000,
            "hist_flush_interval": 0.0,
            "flush_max_age": 1e6,
            "pipeline_depth": 1,
        }}, f)
    return path


def _spawn_worker(tiles: str, broker: str, ckpt: str, cfg: str, url: str,
                  partitions: "list[int] | None" = None):
    import subprocess

    cmd = [sys.executable, "-m", "reporter_tpu.streaming",
           "--tiles", tiles, "--broker-dir", broker, "--columnar",
           "--checkpoint", ckpt, "--checkpoint-interval", "0.5",
           "--config", cfg, "--poll-interval", "0.01", "--exit-on-drain"]
    if partitions is not None:
        cmd += ["--partitions"] + [str(p) for p in partitions]
    env = dict(os.environ)
    env["DATASTORE_URL"] = url
    # CPU workers, always: this parent process holds the chip, and a
    # child that reached for it would fail or hang (one process per chip)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.DEVNULL, text=True)


def _wait_worker(proc, timeout: float) -> "dict | None":
    """Join a worker subprocess; its final stdout line is the stats JSON
    (None on timeout — the worker is killed and the leg records it)."""
    import subprocess

    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        return None
    for line in reversed((out or "").strip().splitlines()):
        try:
            return json.loads(line)
        except json.JSONDecodeError:
            continue
    return None


def _coverage_diff(a, b, tol: float = 30.0) -> "tuple[int, int]":
    """(lost, duplicated) between two report multisets, at TRAVERSAL
    granularity: a reference report is COVERED if the other run delivered
    a report for the same segment whose [t0, t1] interval overlaps it
    (or starts within ``tol`` seconds). Replay from a checkpoint cut
    legally re-merges boundary waves from a different first point, which
    shifts INTERPOLATED entry/exit times by a few samples — coverage of
    the traversal, not byte-equality of its timestamps, is the
    at-least-once claim. ``duplicated`` = deliveries beyond one per
    covered traversal (the replay tax). Exact-key diffs ride alongside
    in the detail for honesty."""
    from collections import defaultdict

    A: dict = defaultdict(list)
    B: dict = defaultdict(list)
    for (i, _nx, t0, t1), c in a.items():
        A[i].extend([(t0, t1)] * c)
    for (i, _nx, t0, t1), c in b.items():
        B[i].extend([(t0, t1)] * c)
    lost = matched = 0
    for i, al in A.items():
        bl = sorted(B.get(i, ()))
        used = [False] * len(bl)
        for t0, t1 in sorted(al):
            hit = -1
            for j, (bt0, bt1) in enumerate(bl):
                if used[j]:
                    continue
                if bt0 > t1 + tol:
                    break
                if min(t1, bt1) - max(t0, bt0) > 0 or abs(bt0 - t0) <= tol:
                    hit = j
                    break
            if hit >= 0:
                used[hit] = True
                matched += 1
            else:
                lost += 1
    return lost, sum(b.values()) - matched


def _recovery_bench(ts, tiles_path: str, traces, n_stream: int,
                    workdir: str, kill_frac: float = 0.4,
                    timeout: float = 600.0) -> dict:
    """detail.recovery — crash-and-resume as DEMONSTRATED behavior
    (VERDICT r5 demand #7): one reference worker run over a durable
    broker establishes the uninterrupted report multiset; a second run is
    SIGKILLed mid-soak (a real kill -9: no drain, no final checkpoint, at
    most a torn in-progress one — which the atomic checkpoint write makes
    survivable), restarted on the same checkpoint, and replayed to
    drained. Reports are compared as multisets: ``lost_reports`` pins the
    at-least-once bound (must be 0), ``duplicated_reports`` prices the
    replay window — duplicates are the at-least-once TAX, counted, not
    hidden."""
    broker = os.path.join(workdir, "rec_broker")
    cfg = _chaos_worker_config(workdir)
    probes = _stage_durable_broker(
        ts, traces, n_stream, broker,
        cycles=int(os.environ.get("REPORTER_BENCH_REC_CYCLES", "2")))

    # reference (uninterrupted) run
    srv_a, state_a = _report_sink()
    url_a = f"http://127.0.0.1:{srv_a.server_address[1]}/"
    t0 = time.perf_counter()
    proc = _spawn_worker(tiles_path, broker, os.path.join(workdir, "ref"),
                         cfg, url_a)
    ref_exit = _wait_worker(proc, timeout)
    ref_s = time.perf_counter() - t0
    srv_a.shutdown()
    if ref_exit is None or state_a["rows"] == 0:
        return {"note": "reference worker run failed/timed out",
                "exit": ref_exit, "rows": state_a["rows"]}

    # kill run: SIGKILL once the sink has seen kill_frac of the reference
    srv_b, state_b = _report_sink()
    url_b = f"http://127.0.0.1:{srv_b.server_address[1]}/"
    ckpt_b = os.path.join(workdir, "kill")
    proc = _spawn_worker(tiles_path, broker, ckpt_b, cfg, url_b)
    target = max(1, int(kill_frac * state_a["rows"]))
    t_kill0 = time.perf_counter()
    killed = False
    while time.perf_counter() - t_kill0 < timeout:
        if state_b["rows"] >= target:
            proc.kill()                      # SIGKILL: no drain, no flush
            proc.communicate()
            killed = True
            break
        if proc.poll() is not None:
            break                            # drained before the target
        time.sleep(0.02)
    if not killed:
        proc.kill()
        proc.communicate()
        srv_b.shutdown()
        return {"note": "worker drained before the kill target — raise "
                        "REPORTER_BENCH_REC_CYCLES", "rows_at_exit":
                state_b["rows"], "target": target}
    rows_at_kill = state_b["rows"]

    # committed floor the restart will replay from (the kill run's last
    # completed checkpoint — read directly, the worker is dead)
    committed = None
    try:
        import numpy as np
        with np.load(ckpt_b + ".npz") as z:
            committed = json.loads(bytes(z["state"]).decode())["committed"]
    except Exception:
        pass                                 # killed before 1st checkpoint

    # restart on the same checkpoint + broker: replay to drained
    t1 = time.perf_counter()
    proc = _spawn_worker(tiles_path, broker, ckpt_b, cfg, url_b)
    rec_exit = _wait_worker(proc, timeout)
    recovery_s = time.perf_counter() - t1
    srv_b.shutdown()

    a, b = state_a["reports"], state_b["reports"]
    lost, dup = _coverage_diff(a, b)         # traversal coverage (the
    #                                          at-least-once contract)
    lost_exact = sum((a - b).values())       # byte-equal keys only: drifts
    dup_exact = sum((b - a).values())        # at replayed wave boundaries
    lost_segments = len({k[0] for k in a} - {k[0] for k in b})
    return {
        "config": (f"{min(n_stream, len(traces))} vehicles, "
                   f"{probes} probes durable-broker soak, SIGKILL at "
                   f"~{int(kill_frac * 100)}% of reference reports, "
                   f"CPU worker subprocesses, tile={ts.name}"),
        "broker_probes": int(probes),
        "reference": {"seconds": round(ref_s, 1),
                      "reports": int(state_a["rows"]),
                      "posts": int(state_a["posts"]),
                      "startup_s": (None if state_a["t_first"] is None
                                    else round(state_a["t_first"] - t0, 1))},
        "reports_at_kill": int(rows_at_kill),
        "committed_at_restart": committed,
        "recovery_seconds": round(recovery_s, 1),
        "recovered_exit": rec_exit,
        "reports_total": int(state_b["rows"]),
        "duplicated_reports": int(dup),
        "lost_reports": int(lost),
        "lost_reports_exact_key": int(lost_exact),
        "duplicated_reports_exact_key": int(dup_exact),
        "lost_segments": int(lost_segments),
        "match_tolerance_s": 30.0,
        "at_least_once_ok": bool(lost == 0),
        "note": ("lost = reference traversals with no covering report in "
                 "the killed+recovered stream (same segment, overlapping "
                 "interval). Delivery is at-least-once by construction "
                 "(offset replay from the commit floor); a nonzero lost "
                 "count here means a traversal at a replayed WAVE CUT "
                 "decoded onto a neighboring segment — decode drift, "
                 "bounded by the cut count, visible in "
                 "lost_reports_exact_key either way"),
    }


def _streaming_soak_mp(ts, tiles_path: str, traces, n_stream: int,
                       workdir: str, timeout: float = 600.0) -> dict:
    """detail.streaming_soak_mp — the LIVE multi-process consumer group
    (VERDICT r5 demand #5): the same durable broker drained once by one
    worker subprocess (all 4 partitions) and once by TWO concurrent
    worker subprocesses over disjoint partition pairs, each with its own
    checkpoint, all publishing to the sink. The measured question is the
    honest one: on a one-core host sharing one device, does a second
    PROCESS add throughput? (A wash is an acceptable measured answer —
    scale-out is partition reassignment to more hosts.)"""
    broker = os.path.join(workdir, "mp_broker")
    cfg = _chaos_worker_config(workdir)
    probes = _stage_durable_broker(ts, traces, n_stream, broker, cycles=1)

    def _run(subsets, tag):
        srv, state = _report_sink()
        url = f"http://127.0.0.1:{srv.server_address[1]}/"
        t0 = time.perf_counter()
        procs = [_spawn_worker(tiles_path, broker,
                               os.path.join(workdir, f"mp_{tag}_{i}"),
                               cfg, url, partitions=sub)
                 for i, sub in enumerate(subsets)]
        exits = [_wait_worker(p, timeout) for p in procs]
        wall = time.perf_counter() - t0
        srv.shutdown()
        active = (None if state["t_first"] is None
                  else max(state["t_last"] - state["t_first"], 1e-6))
        return {"wall_seconds": round(wall, 1),
                "active_seconds": (None if active is None
                                   else round(active, 1)),
                "probes_per_sec_wall": round(probes / wall, 1),
                "probes_per_sec_active": (None if active is None else
                                          round(probes / active, 1)),
                "reports": int(state["rows"]),
                "exits": exits}

    one = _run([None], "one")                # None = all partitions
    two = _run([[0, 1], [2, 3]], "two")
    speedup = (round(one["wall_seconds"] / two["wall_seconds"], 3)
               if two["wall_seconds"] else None)
    return {
        "config": (f"{min(n_stream, len(traces))} vehicles, {probes} "
                   f"probes, 1-vs-2 CPU worker subprocesses over one "
                   f"durable broker, tile={ts.name}"),
        "broker_probes": int(probes),
        "one_worker": one,
        "two_workers": two,
        "speedup_2v1": speedup,
    }


def _publish_outage_soak(ts, traces, n_stream: int, workdir: str) -> dict:
    """Fault-injected datastore outage under load: the pipelined columnar
    worker keeps matching while every POST in the fault window fails; the
    publisher pays its counted retries, dead-letters the exhausted
    batches to the durable spool, and — once the outage lifts — replays
    the spool to empty. Recorded: every count, plus max RSS growth (the
    outage must shed to DISK, not to memory)."""
    from reporter_tpu import faults
    from reporter_tpu.config import Config, ServiceConfig, StreamingConfig
    from reporter_tpu.streaming.columnar import (ColumnarIngestQueue,
                                                 ColumnarStreamPipeline)

    batches, V, n_pts = _stage_round_batches(ts, traces, n_stream,
                                             steps_per_batch=4)
    # incremental feed (one staged batch per step, 2 replay cycles): the
    # wave cadence follows flush_min_points, so the leg publishes MANY
    # real batches and the outage window spans several of them — a
    # pre-filled broker collapses into one drain-everything wave and the
    # fault window never fires
    feed = [b if c == 0 else b._replace(time=b.time + c * float(n_pts))
            for c in range(2) for b in batches]
    queue = ColumnarIngestQueue(4)
    dl_dir = os.path.join(workdir, "dead_letter")
    cfg = Config(
        matcher_backend="jax",
        service=ServiceConfig(datastore_url="http://datastore.invalid/",
                              publish_retries=2, publish_backoff_ms=10.0,
                              publish_backoff_cap_ms=50.0,
                              dead_letter_dir=dl_dir),
        streaming=StreamingConfig(flush_min_points=40,
                                  poll_max_records=50_000,
                                  hist_flush_interval=0.0,
                                  pipeline_depth=1))
    pipe = ColumnarStreamPipeline(ts, cfg, queue=queue,
                                  transport=lambda url, body: 200)
    rss0 = _rss_mb()
    max_rss = rss0 or 0.0
    # outage = transport ATTEMPTS 1..8 (0-based; the fault site counts
    # attempts, so retries burn through the window too): the first wave
    # lands, the datastore goes dark across several waves' attempt
    # bursts, then comes back — deterministic in the attempt counter
    plan = faults.FaultPlan.parse("publish:fail@1-9", seed=11)
    t0 = time.perf_counter()
    with faults.use(plan):
        for b in feed:
            queue.append_columns(b)
            pipe.step()
            r = _rss_mb()
            if r is not None:
                max_rss = max(max_rss, r)
        while queue.lag(pipe.committed) > 0:
            before = queue.lag(pipe.committed)
            pipe.step()
            r = _rss_mb()
            if r is not None:
                max_rss = max(max_rss, r)
            st = pipe.stats()
            if (queue.lag(pipe.committed) >= before
                    and st["inflight_waves"] == 0
                    and st["publish_pending"] == 0):
                break
        pipe.drain()
    # outage OVER (plan uninstalled): batches still spooled — e.g. the
    # last report wave failed and no later success triggered the
    # auto-replay — drain explicitly, the operator/restart action
    replayed, remaining = pipe.publisher.replay_dead_letters()
    dt = time.perf_counter() - t0
    st = pipe.stats()
    pub = pipe.publisher
    out = {
        "config": (f"{V} vehicles x {n_pts}pt x2 cycles paced feed, POST "
                   f"outage over transport attempts 1-8, retries=2, "
                   f"tile={ts.name}"),
        "seconds": round(dt, 1),
        "probes": int(V * n_pts * 2),
        "reports": int(st["reports"]),
        "publish_requests": int(pub.requests),
        "publish_retried": int(pub.retried),
        "dead_lettered": int(pub.dead_lettered),
        "dead_letter_replayed": int(pub.dead_letter_replayed),
        "dead_letter_final_replay": int(replayed),
        "dead_letter_pending_end": int(pub.dead_letter_pending),
        "spool_drained": bool(pub.dead_letter_pending == 0),
        "published_rows": int(pub.published),
        "dropped_rows": int(pub.dropped),
        "fault_stats": plan.stats(),
        "rss_start_mb": (None if rss0 is None else round(rss0, 1)),
        "rss_max_delta_mb": (None if rss0 is None
                             else round(max_rss - rss0, 1)),
    }
    pipe.close()
    return out


_V5E_HBM_BYTES_PER_S = 819e9    # v5e public peak HBM bandwidth
_V5E_VPU_F32_PER_S = 3.9e12     # ≈ (8, 128) lanes × 4 ALUs × 940 MHz — the
#                                 sweep is elementwise VPU work, not MXU
_V5E_MXU_BF16_PER_S = 197e12    # v5e public peak bf16 matmul throughput —
#                                 the round-13 mxu arm's coarse pass rides
#                                 the MXU, so its flops compare against
#                                 THIS peak, not the VPU's
_SWEEP_PAIR_FLOPS = 25          # f32 ops per point-segment pair in
#                                 _block_geometry (clamped projection + d2 +
#                                 offset); _select_topk adds ~2x more on the
#                                 blocks that pass the radius test, so the
#                                 reported utilization is a floor
_SELECT_FLOPS_PER_COL_PASS = 9  # VPU ops per candidate column per
#                                 _select_topk pass (min, tie compare,
#                                 masked edge-min, select mask, masked
#                                 offset-min, kill) — the "selection
#                                 roughly doubles the true number" prose
#                                 note, now a counted work level


def _sweep_culling_stats(bbox: "np.ndarray", sub: "np.ndarray | None",
                         flat: "np.ndarray", radius: float) -> dict:
    """Host replication of BOTH kernel culling levels for one dispatch's
    points (pure numpy — unit-testable, schema-pinned by
    tests/test_bench_schema.py). Level 1 mirrors _chunk_block_ids (chunk
    sub-range bboxes vs block bboxes → block visit list); level 2 mirrors
    the round-8 in-kernel test (min over the chunk's ACTUAL points of the
    point-to-sub-slice-bbox distance vs the dilated radius) — so the
    reported pair counts are exactly what the active kernel computes."""
    import numpy as np

    from reporter_tpu.ops import dense_candidates as dc

    P, NSUB = dc._P, dc._NSUB
    flat = flat.astype(np.float64)
    n = len(flat)
    nchunks = (n + P - 1) // P
    pad = nchunks * P - n
    if pad:                       # bench slices are uniform/full — pad with
        flat = np.concatenate([flat, flat[-1:].repeat(pad, 0)])   # last pt
    sr = flat.reshape(nchunks * NSUB, P // NSUB, 2)
    lo = sr.min(axis=1) - radius                       # [nc*NSUB, 2]
    hi = sr.max(axis=1) + radius
    hit = ((bbox[None, :, 0] <= hi[:, 0:1])
           & (bbox[None, :, 2] >= lo[:, 0:1])
           & (bbox[None, :, 1] <= hi[:, 1:2])
           & (bbox[None, :, 3] >= lo[:, 1:2]))         # NaN pad rows: False
    hit = hit.reshape(nchunks, NSUB, -1).any(axis=1)   # [nchunks, nblocks]
    hits_per_chunk = hit.sum(axis=1)
    nvisits = int(hits_per_chunk.sum())
    out = {
        "blocks_total": int(bbox.shape[0]),
        "block_visits_per_dispatch": nvisits,
        "mean_blocks_per_chunk": round(float(hits_per_chunk.mean()), 1),
        "culled_fraction": round(
            1.0 - nvisits / max(nchunks * bbox.shape[0], 1), 4),
        "sub_slices_per_block": 1,
        "sub_visits_per_dispatch": nvisits,
        "sub_fraction_of_block_cols": 1.0,
    }
    if sub is None:
        return out
    nsub = sub.shape[1] // 4
    quads = sub.reshape(-1, nsub, 4)                   # [nblocks, nsub, 4]
    rc = dc.cull_radius(radius)                        # kernel's dilation
    chunks = flat.reshape(nchunks, P, 2)
    sub_visits = 0
    for c in range(nchunks):
        blks = np.nonzero(hit[c])[0]
        if not len(blks):
            continue
        q = quads[blks]                                # [nh, nsub, 4]
        px = chunks[c, :, 0][:, None, None]
        py = chunks[c, :, 1][:, None, None]
        dx = np.maximum(np.maximum(q[None, :, :, 0] - px,
                                   px - q[None, :, :, 2]), 0.0)
        dy = np.maximum(np.maximum(q[None, :, :, 1] - py,
                                   py - q[None, :, :, 3]), 0.0)
        d2 = dx * dx + dy * dy                         # [P, nh, nsub]
        d2 = np.where(np.isnan(d2), np.inf, d2)        # NaN quad = no slice
        sub_visits += int((d2.min(axis=0) <= rc * rc).sum())
    out["sub_slices_per_block"] = nsub
    out["sub_visits_per_dispatch"] = sub_visits
    out["sub_fraction_of_block_cols"] = round(
        sub_visits / max(nvisits * nsub, 1), 4)
    return out


def _sweep_roofline(m, pts: "np.ndarray", per_dispatch_s: float) -> dict:
    """Calibrate one dispatch against the chip (VERDICT r4 next #4): the
    culling passes (host-replicated in _sweep_culling_stats) are exactly
    reproducible from the slice's points + the staged bbox tables, so
    swept HBM bytes and pair FLOPs per dispatch are exactly knowable —
    achieved vs peak says what fraction of a v5e the sweep actually
    uses, instead of 'fast relative to round N-1'. Round 8: pair FLOPs
    follow the ACTIVE kernel (sub-slice visits when the two-level kernel
    runs); the whole-block number stays as pair_flops_block_level so the
    before/after utilization comparison lives in every capture."""
    import numpy as np

    from reporter_tpu.matcher.autotune import TunedPlan
    from reporter_tpu.ops import dense_candidates as dc

    # lint: allow[staged-layout] 2026-08-04 roofline calibration READS the
    # culling tables (bbox/sub/feat) only; it stages nothing — seg_pack
    # geometry is swept on device, not consulted host-side here
    if "seg_bbox" not in m._tables:
        return {"note": "grid backend staged — no dense sweep to calibrate"}
    bbox = np.asarray(m._tables["seg_bbox"])           # [nblocks, 4]
    sub = (np.asarray(m._tables["seg_sub"])
           if "seg_sub" in m._tables else None)
    plan = getattr(m, "tuned_plan", None) or TunedPlan()
    subcull = plan.arm != "block" and sub is not None
    mxu = plan.arm == "mxu" and subcull and "seg_feat" in m._tables
    stats = _sweep_culling_stats(bbox, sub if subcull else None,
                                 pts.reshape(-1, 2),
                                 float(m.params.search_radius))
    P = dc._P
    K = m.params.max_candidates
    nvisits = stats["block_visits_per_dispatch"]
    block_bytes = dc.SP_NCOMP * dc._SBLK * 4
    if mxu:                        # feature rows DMA alongside the pack
        block_bytes += dc.SF_NCOMP * dc._SBLK * 4
    bytes_swept = nvisits * block_bytes                # DMA is whole blocks
    subw = dc._SBLK // stats["sub_slices_per_block"]
    flops_block = nvisits * P * dc._SBLK * _SWEEP_PAIR_FLOPS
    flops = stats["sub_visits_per_dispatch"] * P * subw * _SWEEP_PAIR_FLOPS
    # round-13 third work level: the mxu arm's coarse pass is one
    # [P, 8] x [8, subw] dot per sub-visit — 2*8 flops per output element
    # on the MXU (vs the VPU pair flops it gates)
    mxu_flops = (stats["sub_visits_per_dispatch"] * P * subw * 2
                 * dc.SF_NCOMP if mxu else 0)
    # selection-reduction ceiling: K passes over [P, subw + K] per
    # radius-passing slice; the host can't replicate the in-kernel radius
    # gate, so sub-visits bound it from above ("selection roughly doubles
    # the true number" — now a recorded field instead of a prose note)
    select_flops_ceiling = (stats["sub_visits_per_dispatch"] * K
                            * (subw + K) * P * _SELECT_FLOPS_PER_COL_PASS
                            if subcull else
                            nvisits * K * (dc._SBLK + K) * P
                            * _SELECT_FLOPS_PER_COL_PASS)
    bw = bytes_swept / per_dispatch_s
    fl = flops / per_dispatch_s
    return {
        "kernel": ("subcull" if subcull else "block")
                  + ("+mxu" if mxu else "")
                  + ("+bf16" if subcull and plan.lowp == "bf16" else ""),
        **stats,
        "hbm_bytes_swept": int(bytes_swept),
        "pair_flops": int(flops),
        "pair_flops_block_level": int(flops_block),
        "mxu_flops": int(mxu_flops),
        "select_flops_ceiling": int(select_flops_ceiling),
        "topk_width": (subw if subcull else dc._SBLK)
                      + m.params.max_candidates,
        "achieved_GBps": round(bw / 1e9, 1),
        "achieved_Gflops": round(fl / 1e9, 1),
        "pct_of_v5e_hbm_peak": round(100 * bw / _V5E_HBM_BYTES_PER_S, 1),
        "pct_of_v5e_vpu_f32_peak": round(100 * fl / _V5E_VPU_F32_PER_S, 1),
        "pct_vpu_block_level": round(
            100 * (flops_block / per_dispatch_s) / _V5E_VPU_F32_PER_S, 1),
        "pct_of_v5e_mxu_bf16_peak": (
            round(100 * (mxu_flops / per_dispatch_s)
                  / _V5E_MXU_BF16_PER_S, 2) if mxu else None),
        "note": ("pair-geometry FLOPs of the ACTIVE kernel — a floor for "
                 "non-mxu kernels, an UPPER bound under +mxu (the matmul "
                 "gate skips exact geometry on slices the host stats "
                 "can't see, same caveat as select_flops_ceiling); "
                 "select_flops_ceiling bounds the top-K reductions at "
                 "sub-visit granularity (the in-kernel radius gate can "
                 "only shrink it); pair_flops_block_level = what the "
                 "whole-block kernel would compute for the same dispatch; "
                 "mxu_flops = the matmul coarse pass, vs the MXU peak"),
    }


def _stage_uniform_slice(m, traces):
    """Stage ONE uniform-length slice's quantized infeed on the device —
    the shared staging of every device-dispatch probe (compute probe,
    sweep-variant A/B), so the probes can never drift onto different
    wire conventions than each other. Returns (args, pts, sub, T) with
    the uploads synced; args feeds match_batch_wire_q."""
    import numpy as np

    import jax

    from reporter_tpu.matcher.api import _bucket_len
    from reporter_tpu.ops.match import OFFSET_QUANTUM

    B = m.slice_rows
    sub = [t for t in traces if len(t.xy) == len(traces[0].xy)][:B]
    T = len(sub[0].xy)
    b = _bucket_len(T)
    pts = np.zeros((len(sub), b, 2), np.float32)
    pts[:, :T] = np.stack([t.xy for t in sub])
    pts[:, T:] = pts[:, :1]
    lens = np.full(len(sub), T, np.int32)
    origins = pts[:, 0, :].copy()
    dq = np.round((pts - origins[:, None, :]) * np.float32(1 / OFFSET_QUANTUM))
    args = (jax.device_put(dq.astype(np.int16)), jax.device_put(origins),
            jax.device_put(lens))
    np.asarray(args[0][0, 0])                   # sync the uploads
    return args, pts, sub, T


def _sweep_variants_probe(m, traces, link_rtt: float, K: int = 12,
                          windows: int = 2) -> dict:
    """Same-mood A/B of the sweep kernel arms, the ISSUE-3 discipline:
    ONE staged slice, three static kernel plans of the SAME executable
    family — "subcull" (two-level culling + fused narrow top-K, the r8
    default), "block" (the round-7 whole-block kernel), "mxu" (round 13:
    matmul-form coarse pass on the MXU, bf16 operands — the promoted
    home of the r8 lowp="bf16" dtype, which now gets its chip
    numbers here instead of a fourth leg) — dispatched in interleaved
    windows so every arm sees the same link mood. Also asserts the three
    arms' result wires are BYTE-identical on this slice (the exactness
    contract, proven on-chip every run), INCLUDING through an
    evict→promote paging cycle of the matcher's tables (unstage + fresh
    host_tables device_put — the fleet promotion seam, stale-layout
    check live). Each arm's number is the best window (same best-of-N
    convention as every tile).
    """
    import numpy as np

    from reporter_tpu.matcher.autotune import TunedPlan
    from reporter_tpu.ops.match import match_batch_wire_q

    if "seg_sub" not in m._tables:
        return {"note": "no dense seg_sub staged — sweep variants n/a"}
    args, _, sub, T = _stage_uniform_slice(m, traces)
    spec = getattr(m, "_wire_spec", None)
    arms = {
        "subcull": TunedPlan(arm="subcull", lowp="off"),
        "block": TunedPlan(arm="block", lowp="off"),
        "mxu": TunedPlan(arm="mxu", lowp="bf16"),
    }
    warm = {}
    errors: dict = {}
    for a, p in list(arms.items()):  # compile + one readback per arm,
        try:                         # outside the windows
            warm[a] = np.asarray(match_batch_wire_q(
                *args, m._tables, m.ts.meta, m.params, None, spec=spec,
                plan=p))
        except Exception as exc:     # an arm that fails to lower must not
            del arms[a]              # sink the whole capture — record it
            errors[a] = repr(exc)[:200]
    if "subcull" not in warm:
        return {"note": "subcull arm failed to compile/dispatch",
                "arm_errors": errors}
    # None (not a vacuous True) when comparison arms are missing — the
    # identity claim must mean an actual cross-kernel comparison ran
    identical = (all(np.array_equal(warm["subcull"], w)
                     for w in warm.values())
                 if len(warm) >= 2 else None)
    # whether the mxu arm actually PARTICIPATED in the comparisons: the
    # summary's r13 acceptance token folds this tile's identity bits
    # only when True — a lowering failure must read as "not exercised",
    # never as a green three-arm contract proven by the two legacy arms
    mxu_compared = "mxu" in warm
    # paging-cycle identity (acceptance: byte-identity holds through a
    # fleet evict→promote): drop the matcher's device tables, restage a
    # FRESH host_tables build through the same device_put + version-check
    # seam the fleet promotion uses, and re-harvest one arm. Values are
    # deterministic, so bytes must match the pre-paging harvest exactly.
    paged_identical = None
    if hasattr(m, "unstage_tables"):
        orig_tables = m._tables
        try:
            import jax as _jax
            ref_arm = "mxu" if "mxu" in warm else "subcull"
            host = m.ts.host_tables(m.params.candidate_backend)
            m.unstage_tables()
            m.restage_tables(_jax.device_put(host))
            w2 = np.asarray(match_batch_wire_q(
                *args, m._tables, m.ts.meta, m.params, None, spec=spec,
                plan=arms[ref_arm]))
            paged_identical = bool(np.array_equal(w2, warm[ref_arm]))
            del w2
        except Exception as exc:
            errors["paging"] = repr(exc)[:200]
            # a failure between unstage and restage (link dying mid-
            # transfer) must not leave the matcher paged out — the
            # timing windows below and every later leg sharing this
            # matcher dispatch through m._tables
            if not m.tables_staged:
                m.restage_tables(orig_tables)
    del warm
    best: dict = dict.fromkeys(arms)
    for _ in range(windows):
        for a, p in arms.items():
            t0 = time.perf_counter()
            for _ in range(K):
                wire = match_batch_wire_q(*args, m._tables, m.ts.meta,
                                          m.params, None, spec=spec, plan=p)
            np.asarray(wire)
            dt = max((time.perf_counter() - t0 - link_rtt) / K, 1e-6)
            if best[a] is None or dt < best[a]:
                best[a] = dt
    probes = len(sub) * T
    out: dict = {a: {"device_ms_per_dispatch": round(best[a] * 1e3, 2),
                     "device_probes_per_sec": round(probes / best[a], 1)}
                 for a in arms}
    out["dispatch_shape"] = f"{len(sub)}x{T}pts"
    out["wires_bit_identical"] = (None if identical is None
                                  else bool(identical))
    out["wires_identical_after_paging"] = paged_identical
    out["mxu_compared"] = mxu_compared
    if errors:
        out["arm_errors"] = errors
    if "block" in best:
        out["speedup_subcull_vs_block"] = round(
            best["block"] / best["subcull"], 3)
    if "mxu" in best:
        out["speedup_mxu_vs_subcull"] = round(
            best["subcull"] / best["mxu"], 3)
    return out


def _sweep_ab_cpu_validate() -> dict:
    """No-chip stand-in for _sweep_variants_probe (manual / CPU-forced
    composites): the SAME three kernel arms — subcull / block / mxu —
    through the pallas INTERPRETER at tiny scale, wire bytes compared
    across arms AND through an evict→promote paging cycle of a real
    SegmentMatcher (the fleet restage seam, stale-layout version check
    live). Interpreter timings are meaningless, so the per-arm pps slots
    record None — the acceptance artifact here is the identity bits,
    re-proven on every composite the way detail.fleet's tiny-scale run
    validates paging (the r7 BENCH_DETAIL_CPU.json convention)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from reporter_tpu.config import CompilerParams, Config, MatcherParams
    from reporter_tpu.matcher.api import SegmentMatcher
    from reporter_tpu.matcher.autotune import TunedPlan
    from reporter_tpu.netgen.synthetic import generate_city
    from reporter_tpu.netgen.traces import synthesize_fleet
    from reporter_tpu.ops import dense_candidates as dc
    from reporter_tpu.ops.match import match_batch_wire
    from reporter_tpu.tiles.compiler import compile_network

    base = MatcherParams(candidate_backend="dense")
    cfg = Config(matcher_backend="jax", matcher=base)
    ts = compile_network(generate_city("tiny", seed=23), CompilerParams())
    m = SegmentMatcher(ts, cfg)
    fleet = synthesize_fleet(ts, 6, num_points=40, seed=4)
    pts = np.stack([p.xy for p in fleet]).astype(np.float32)
    lens = np.full(len(fleet), pts.shape[1], np.int32)
    arms = {
        "subcull": TunedPlan(arm="subcull", lowp="off"),
        "block": TunedPlan(arm="block", lowp="off"),
        "mxu": TunedPlan(arm="mxu", lowp="bf16"),
    }
    wires: dict = {}
    errors: dict = {}
    paged_identical = None
    prev = dc._INTERPRET
    dc._INTERPRET = True
    try:
        for a, p in arms.items():
            try:
                wires[a] = np.asarray(match_batch_wire(
                    jnp.asarray(pts), jnp.asarray(lens), m._tables,
                    ts.meta, base, None, spec=None, plan=p))
            except Exception as exc:
                errors[a] = repr(exc)[:200]
        if "mxu" in wires:
            try:
                m.unstage_tables()
                m.restage_tables(jax.device_put(ts.host_tables("dense")))
                w2 = np.asarray(match_batch_wire(
                    jnp.asarray(pts), jnp.asarray(lens), m._tables,
                    ts.meta, base, None, spec=None, plan=arms["mxu"]))
                paged_identical = bool(np.array_equal(w2, wires["mxu"]))
            except Exception as exc:
                errors["paging"] = repr(exc)[:200]
    finally:
        dc._INTERPRET = prev
    identical = (all(np.array_equal(wires["subcull"], w)
                     for w in wires.values())
                 if "subcull" in wires and len(wires) >= 2 else None)
    out: dict = {a: {"device_ms_per_dispatch": None,
                     "device_probes_per_sec": None} for a in arms}
    out["config"] = (f"interpret-mode validation, {len(fleet)}x"
                     f"{pts.shape[1]}pt traces, tile={ts.name} (no chip)")
    out["wires_bit_identical"] = identical
    out["wires_identical_after_paging"] = paged_identical
    out["mxu_compared"] = "mxu" in wires    # same honesty rule as the
    #                                         chip probe's token folding
    if errors:
        out["arm_errors"] = errors
    return out


def _autotune_probe(m, traces, link_rtt: float, K: int = 8,
                    windows: int = 2) -> dict:
    """Chip leg (round 17): the dispatch plan the matcher resolved at
    construction (measured on this metro's staged tables, or served from
    the plan cache), its per-candidate calibration timings, and a
    same-mood tuned-vs-default interleaved A/B on ONE staged slice (the
    sweep_ab window discipline) — the measured value of self-tuning, in
    every chip capture. Matchers serving the static default (tuner off /
    timeout degradation) record why instead of a vacuous 1.0x."""
    import numpy as np

    from reporter_tpu.matcher import autotune
    from reporter_tpu.ops.match import match_batch_wire_q

    plan = getattr(m, "tuned_plan", None)
    report = dict(getattr(m, "tuned_report", None) or {})
    out: dict = {
        "plan": autotune.plan_json(plan),
        "source": report.get("source"),
        "candidates": report.get("candidates"),
        "calibration_seconds": report.get("calibration_seconds"),
        "calibration_dispatches": report.get("calibration_dispatches"),
        "cache_hit": report.get("source") == "cache",
    }
    if report.get("errors"):
        out["arm_errors"] = report["errors"]
    if plan is None or not plan.chosen:
        out["note"] = (f"matcher untuned (source="
                       f"{report.get('source')!r}) — no A/B to run")
        return out
    args, _, sub, T = _stage_uniform_slice(m, traces)
    spec = getattr(m, "_wire_spec", None)
    arms = {"tuned": plan, "default": autotune.default_plan()}
    for p in arms.values():         # compile + one readback, untimed
        np.asarray(match_batch_wire_q(*args, m._tables, m.ts.meta,
                                      m.params, None, spec=spec, plan=p))
    best: dict = dict.fromkeys(arms)
    for _ in range(windows):
        for a, p in arms.items():
            t0 = time.perf_counter()
            for _ in range(K):
                wire = match_batch_wire_q(*args, m._tables, m.ts.meta,
                                          m.params, None, spec=spec, plan=p)
            np.asarray(wire)
            dt = max((time.perf_counter() - t0 - link_rtt) / K, 1e-6)
            if best[a] is None or dt < best[a]:
                best[a] = dt
    probes = len(sub) * T
    for a in arms:
        out[a] = {"device_ms_per_dispatch": round(best[a] * 1e3, 2),
                  "device_probes_per_sec": round(probes / best[a], 1)}
    out["dispatch_shape"] = f"{len(sub)}x{T}pts"
    out["tuned_vs_default_speedup"] = round(
        best["default"] / best["tuned"], 3)
    return out


def _autotune_cpu_validate() -> dict:
    """No-chip stand-in for _autotune_probe (every CPU-forced / outage
    composite): the tuner MECHANISM at tiny scale with an injected
    deterministic timer — zero device access, self-contained (builds its
    own tiny tile), so ``--legs autotune`` fits a short chip call.
    Validates: the CPU short-circuit on a real SegmentMatcher, arm/rung
    selection + two-run determinism under synthetic timings, a
    plan-cache round trip whose hit skips re-measurement, and the
    staged-layout v3 guard at both injection seams (the r13 stale-dict
    discipline extended over tuned plans)."""
    import shutil
    import tempfile

    import numpy as np

    from reporter_tpu.config import CompilerParams, Config, MatcherParams
    from reporter_tpu.matcher import autotune
    from reporter_tpu.matcher.api import SegmentMatcher
    from reporter_tpu.netgen.synthetic import generate_city
    from reporter_tpu.tiles.compiler import compile_network

    ts = compile_network(generate_city("tiny", seed=29), CompilerParams())
    cfg = Config(matcher_backend="jax")
    m = SegmentMatcher(ts, cfg)
    cpu_short_circuit = (m.tuned_plan == autotune.default_plan()
                         and m.tuned_report.get("source") == "cpu")

    # synthetic per-candidate cost model (mxu+bf16 fastest, 256 rung
    # best): selection + determinism under a fully injected timer
    def timer(plan):
        base = {"block": 3.0, "subcull": 2.0, "mxu": 1.4}[plan.arm]
        if plan.lowp == "bf16":
            base *= 0.9
        base *= {64: 1.1, 128: 1.0, 256: 0.95}[plan.nj_cap]
        return base / 1e3

    p1, rep1 = autotune.calibrate(timer)
    p2, _ = autotune.calibrate(timer)

    dense = MatcherParams(candidate_backend="dense")
    cache_workdir = tempfile.mkdtemp(prefix="rtpu_autotune_bench_")
    calls = {"n": 0}

    def counting(plan):
        calls["n"] += 1
        return timer(plan)

    try:
        plan_a, info_a = autotune.resolve_plan(
            dense, ts, ts.host_tables("dense"), counting,
            directory=cache_workdir, backend="tpu", devkey="validate")
        measured_calls = calls["n"]
        plan_b, info_b = autotune.resolve_plan(
            dense, ts, ts.host_tables("dense"), counting,
            directory=cache_workdir, backend="tpu", devkey="validate")
        cache_hit = (info_b.get("source") == "cache"
                     and calls["n"] == measured_calls)
        # label comparison: the cache round-trip changes only the
        # source tag, the plan point itself must be identical
        cache_identical = (plan_a is not None and plan_b is not None
                           and plan_a.label == plan_b.label)
    finally:
        shutil.rmtree(cache_workdir, ignore_errors=True)

    # staged-layout v3 guard at both seams: a v2 dict (no tuned_plan)
    # must refuse loudly at construction AND at the restage/promote seam
    stale = dict(ts.host_tables("dense"), staged_layout=np.int32(2))
    stale.pop("tuned_plan")
    try:
        SegmentMatcher(ts, cfg, staged_tables=stale)
        v2_refused_construct = False
    except ValueError:
        v2_refused_construct = True
    try:
        m.restage_tables(stale)
        v2_refused_restage = False
    except ValueError:
        v2_refused_restage = True

    mechanism_ok = bool(cpu_short_circuit and p1 == p2
                        and cache_identical and cache_hit
                        and info_a.get("source") == "measured"
                        and v2_refused_construct and v2_refused_restage)
    return {
        "config": (f"injected-timer validation, tile={ts.name} "
                   "(no chip — plan selection, cache, layout guard)"),
        "source": "cpu-validate",
        "plan": autotune.plan_json(p1),
        "candidates": rep1.get("candidates"),
        "cpu_short_circuit": cpu_short_circuit,
        "deterministic": p1 == p2,
        "cache_hit": cache_hit,
        "plan_from_cache_identical": cache_identical,
        "v2_refused_at_construction": v2_refused_construct,
        "v2_refused_at_restage": v2_refused_restage,
        "mechanism_ok": mechanism_ok,
    }


def _quality_overhead_ab(m, batches, default_rate: float,
                         forced_audits: int = 2) -> dict:
    """Shadow-audit overhead at the DEFAULT sampling rate, two ways
    (shared by the chip probe and the CPU validation so the capture
    always carries the acceptance number):

      - a direct off-vs-default A/B over the same batch list (audits
        drain inside the timed window — on a one-core host the audit
        thread's cost IS steady-wave host cost);
      - the per-audited-batch oracle cost from FORCED audits
        (rate=1.0), which prices the implied steady-state overhead
        ``default_rate × audit_s_per_batch / wave_s`` — deterministic
        where the direct A/B at a 1/256 rate is noise-dominated over a
        bench-sized batch count.

    ``audit_overhead_pct`` — the headline, acceptance <2% — is the
    implied steady-state projection BOUNDED BY THE ENFORCED LIMITS
    (audits/s = min(rate / wave_s, 1 / min_interval_s), then the
    measured-duty cap): the auditor really sheds past both, counted, so
    the bound is enforcement, not assumption. The raw direct A/B rides
    along unheadlined (at a 1/256 rate over a bench-sized batch count
    it is one-core noise). The process auditor is swapped per arm and
    restored — the r10 global-state discipline."""
    from reporter_tpu.quality import audit as quality_audit

    prev = quality_audit._global
    arms: dict = {}
    try:
        m.match_many(batches[0])    # warm: both arms must time steady
        #                             waves, not first-compile (the r10
        #                             warm-arm discipline)
        for name, rate in (("off", 0.0), ("on", default_rate)):
            a = quality_audit.ShadowAuditor(rate=rate,
                                            duty_pct_cap=100.0)
            quality_audit.configure(a)
            t0 = time.perf_counter()
            for b in batches:
                m.match_many(b)
            a.drain(60.0)
            arms[name] = (time.perf_counter() - t0, a.stats())
            a.stop()
        # forced arm prices ONE audit (min_interval_s=0: this arm
        # measures per-audit cost, not the production schedule)
        forced = quality_audit.ShadowAuditor(rate=1.0,
                                             duty_pct_cap=100.0,
                                             min_interval_s=0.0)
        quality_audit.configure(forced)
        for b in batches[:forced_audits]:
            # the in-path hook samples (rate 1.0) — no explicit call,
            # or every batch would audit twice
            m.match_many(b)
        forced.drain(120.0)
        fstats = forced.stats()
        forced.stop()
    finally:
        quality_audit.configure(prev)
    defaults = quality_audit.ShadowAuditor(rate=default_rate)
    probes = sum(len(t.xy) for b in batches for t in b)
    dt_off, _ = arms["off"]
    dt_on, on_stats = arms["on"]
    wave_s = dt_off / max(len(batches), 1)
    audited = fstats["audited_batches"]
    s_per_audit = (fstats["audit_seconds"] / audited if audited
                   else None)
    if s_per_audit is None or wave_s <= 0:
        implied = capped = None
    else:
        audits_per_s = min(default_rate / wave_s,
                           1.0 / max(defaults.min_interval_s, 1e-9))
        implied = 100.0 * audits_per_s * s_per_audit
        capped = min(implied, defaults.duty_pct_cap)
    direct = 100.0 * (dt_on - dt_off) / dt_off if dt_off else None
    return {
        "off_pps": round(probes / dt_off, 1) if dt_off else None,
        "on_pps": round(probes / dt_on, 1) if dt_on else None,
        "audit_rate": default_rate,
        "min_interval_s": defaults.min_interval_s,
        "duty_pct_cap": defaults.duty_pct_cap,
        "audited_batches": int(on_stats["audited_batches"]),
        "audit_s_per_batch": (None if s_per_audit is None
                              else round(s_per_audit, 4)),
        "direct_overhead_pct": (None if direct is None
                                else round(direct, 3)),
        "uncapped_overhead_pct": (None if implied is None
                                  else round(implied, 3)),
        "audit_overhead_pct": (None if capped is None
                               else round(capped, 3)),
        # the bar gates on the UNCAPPED projection: min(x, duty_cap=1)
        # can never exceed 2, so a bar on the capped number would be
        # vacuous green for any measurement (the r13 mxu-token rule —
        # an acceptance bit must be able to fail); the duty cap stays
        # reported as the separate enforcement bound
        "meets_2pct_bar": (None if implied is None
                           else bool(implied < 2.0)),
    }


def _quality_probe(m, traces, n_batches: int = 6,
                   batch_traces: int = 256) -> dict:
    """Chip leg (round 18, reporter_tpu/quality/): steady-wave quality
    signals on the primary tile's matcher (the same per-metro window
    serving reads at /health), a forced shadow-oracle audit's measured
    disagreement, the audit-overhead A/B at the default sampling rate
    (acceptance: recorded and <2% of steady-wave host cost), and the
    drift-sentinel state after the leg's waves. Host-side only — the
    wire programs and compile manifest are untouched by construction
    (the r16 device-contract suite re-proves that every CI run)."""
    from reporter_tpu.quality import audit as quality_audit

    k = min(batch_traces, len(traces))
    batches = [traces[i * k:(i + 1) * k]
               for i in range(max(1, min(n_batches,
                                         len(traces) // max(k, 1))))]
    batches = [b for b in batches if b]
    # the SHIPPED default rate, not the env view: main() pins the
    # process env rate to 0 so audits can't poison other legs, and the
    # overhead claim is about the rate a default deployment serves at
    default_rate = quality_audit._DEFAULT_RATE
    overhead = _quality_overhead_ab(m, batches, default_rate)

    # a forced audit's measured disagreement (the production gt_edge
    # proxy, on this tile's real traffic)
    prev = quality_audit._global
    try:
        forced = quality_audit.ShadowAuditor(rate=1.0,
                                             duty_pct_cap=100.0,
                                             min_interval_s=0.0)
        quality_audit.configure(forced)
        m.match_many(batches[0])    # the in-path hook audits at rate 1
        forced.drain(120.0)
        audit_stats = forced.stats()
        forced.stop()
    finally:
        quality_audit.configure(prev)

    agg = m.quality.window_rates()
    health = m.quality.health()
    return {
        "config": (f"{len(batches)}x{k} trace waves, tile={m.ts.name}, "
                   f"default audit rate {default_rate:.4f}"),
        "signals": {
            **{name: (None if agg[name] is None
                      else round(agg[name], 4))
               for name in agg},
            "window_waves": health["window_waves"],
        },
        "audit": {
            "audited_batches": audit_stats["audited_batches"],
            "audited_traces": audit_stats["audited_traces"],
            "audit_timeouts": audit_stats["audit_timeouts"],
            "audit_seconds": audit_stats["audit_seconds"],
            "disagreement_rate": audit_stats["disagreement_rate"],
        },
        "audit_overhead": overhead,
        "drift": {"drift_events": health["drift_events"]},
    }


def _quality_cpu_validate() -> dict:
    """No-chip stand-in for _quality_probe (every CPU-forced / outage
    composite, the r17 autotune pattern): the quality MECHANISM at tiny
    scale, self-contained (builds its own tile/fleet), so ``--legs
    quality`` fits a short chip call. Validates: signal extraction
    + per-metro publication on real matcher output, the deterministic
    seeded audit schedule, a real shadow-oracle audit round trip, the
    audit-overhead A/B shape (recorded at tiny scale), and the
    quality_drift chaos contract — an injected ``quality`` fault rule
    fires EXACTLY one post-mortem and a clean twin run fires none."""
    import shutil
    import tempfile

    import numpy as np

    from reporter_tpu.config import CompilerParams, Config
    from reporter_tpu.matcher.api import SegmentMatcher, Trace
    from reporter_tpu.netgen.synthetic import generate_city
    from reporter_tpu.netgen.traces import synthesize_fleet
    from reporter_tpu.quality import audit as quality_audit
    from reporter_tpu.tiles.compiler import compile_network
    from reporter_tpu.utils import tracing
    from reporter_tpu.utils.metrics import labeled

    ts = compile_network(generate_city("tiny", seed=31), CompilerParams())
    fleet = synthesize_fleet(ts, 6, num_points=40, seed=6)
    traces = [Trace(uuid=p.uuid, xy=p.xy.astype(np.float32),
                    times=p.times) for p in fleet]
    cfg = Config(matcher_backend="jax")
    m = SegmentMatcher(ts, cfg)
    batches = [traces] * 4
    # shipped default, not the env view (see _quality_probe)
    default_rate = quality_audit._DEFAULT_RATE
    overhead = _quality_overhead_ab(m, batches, default_rate,
                                    forced_audits=1)
    signals_recorded = bool(
        m.quality.health()["window_waves"] >= len(batches) * 2
        and m.metrics.value(labeled("quality_batches",
                                    metro=ts.name)) > 0)

    # deterministic seeded schedule (the faults.py replay discipline)
    seqs = []
    for _ in range(2):
        a = quality_audit.ShadowAuditor(rate=0.3, seed=17)
        seqs.append([a._rng.random() < a.rate for _ in range(64)])
        a.stop()
    sampler_deterministic = seqs[0] == seqs[1]

    # one real audit round trip against the exact oracle
    prev = quality_audit._global
    try:
        forced = quality_audit.ShadowAuditor(rate=1.0, max_traces=2,
                                             duty_pct_cap=100.0,
                                             min_interval_s=0.0)
        quality_audit.configure(forced)
        m.match_many(traces)        # the in-path hook audits at rate 1
        forced.drain(60.0)
        audit_stats = forced.stats()
        forced.stop()
    finally:
        quality_audit.configure(prev)
    # exactly one batch served while configured ⇒ exactly one audit —
    # proving the HOOK fired, not a hand-called auditor
    audit_ran = (audit_stats["audited_batches"] == 1
                 and audit_stats["disagreement_rate"] is not None)

    # drift chaos: injected rule -> ONE post-mortem; clean twin -> none
    tr = tracing.tracer()
    prev_tr = (tr.enabled, tr.dump_dir, tr.capacity, tr.max_dumps)
    prev_written = tr.dumps_written
    workdir = tempfile.mkdtemp(prefix="rtpu_quality_bench_")
    try:
        tr.configure(enabled=True, dump_dir=workdir, max_dumps=4)

        def drive():
            dm = SegmentMatcher(ts, cfg)
            dm.quality.min_waves = 99       # isolate the injected path
            for _ in range(3):
                dm.match_many(traces)
            return dm

        from reporter_tpu import faults
        with faults.use(faults.FaultPlan.parse("quality:fail@1")):
            chaos_m = drive()
        dumps = sorted(os.listdir(workdir))
        one_event_one_dump = (
            chaos_m.quality.drift_events == 1
            and len([d for d in dumps if "quality_drift" in d]) == 1)
        twin_m = drive()
        dumps2 = sorted(os.listdir(workdir))
        clean_twin_ok = (twin_m.quality.drift_events == 0
                         and dumps2 == dumps)
    finally:
        tr.configure(enabled=prev_tr[0], dump_dir=prev_tr[1],
                     capacity=prev_tr[2], max_dumps=prev_tr[3])
        tr.dumps_written = prev_written
        shutil.rmtree(workdir, ignore_errors=True)

    agg = m.quality.window_rates()
    mechanism_ok = bool(signals_recorded and sampler_deterministic
                        and audit_ran and one_event_one_dump
                        and clean_twin_ok
                        and overhead["audit_overhead_pct"] is not None)
    return {
        "config": (f"tiny-scale mechanism validation, tile={ts.name} "
                   "(no chip — signals, audit, sampler, drift chaos)"),
        "source": "cpu-validate",
        "signals": {
            **{name: (None if agg[name] is None
                      else round(agg[name], 4))
               for name in agg},
            "window_waves": m.quality.health()["window_waves"],
        },
        "audit": {
            "audited_batches": audit_stats["audited_batches"],
            "audited_traces": audit_stats["audited_traces"],
            "audit_timeouts": audit_stats["audit_timeouts"],
            "audit_seconds": audit_stats["audit_seconds"],
            "disagreement_rate": audit_stats["disagreement_rate"],
        },
        "audit_overhead": overhead,
        "drift": {"drift_events": 1},   # the injected event, by contract
        "signals_recorded": signals_recorded,
        "sampler_deterministic": sampler_deterministic,
        "audit_ran": audit_ran,
        "one_event_one_dump": one_event_one_dump,
        "clean_twin_ok": clean_twin_ok,
        "mechanism_ok": mechanism_ok,
    }


def _service_overload_boundary(curve: list, arm: str = "scheduler") -> dict:
    """First client level where the serving face shows overload — errors,
    p99 blowup, or req/s REGRESSION vs the previous level (queue growth
    shows up as both). The p99 threshold is scaled by the CLIENT RATIO
    between levels: closed-loop p99 grows ~linearly with clients once
    req/s plateaus (that's healthy saturation, not overload), so a 4x
    client jump legitimately quadruples p99 — only growth well beyond
    the client ratio marks the boundary. VERDICT weak #6: the boundary
    should be a measured number, not 'never observed'; the closed-loop
    curve now extends past 256 clients so this can fire."""
    prev = None
    for lvl in curve:
        sub = lvl.get(arm, {})
        if sub.get("errors"):
            return {"clients": lvl["clients"], "reason": "errors"}
        if prev is not None:
            pp, cp = prev[1].get("p99_ms"), sub.get("p99_ms")
            pr, cr = prev[1].get("req_per_sec"), sub.get("req_per_sec")
            ratio = lvl["clients"] / max(prev[0], 1)
            if pp and cp and cp > 3 * ratio * pp:
                return {"clients": lvl["clients"], "reason": "p99_blowup"}
            # rps threshold sits BELOW the link's documented ~2x mood
            # swing: adjacent levels run minutes apart in different mood
            # windows (only the arms within a level are interleaved), so
            # a 20%-style drop is indistinguishable from link noise —
            # demand a regression past the noise floor
            if pr and cr and cr < 0.45 * pr:
                return {"clients": lvl["clients"],
                        "reason": "rps_regression"}
        prev = (lvl["clients"], sub)
    return {"clients": None,
            "reason": (f"not reached at {curve[-1]['clients']} clients"
                       if curve else "no curve")}


def _device_compute_probe(m, traces, link_rtt: float,
                          roofline: bool = True) -> dict:
    """Per-leg decode attribution (VERDICT r3 #6 / r4 next #3, #4): stage
    one full uniform slice's quantized inputs on the device, dispatch the
    match kernel K times back-to-back, sync ONCE via a host readback (the
    one sync the timing trusts):
        device_s_per_dispatch ≈ (elapsed - link_rtt) / K.
    Then decompose the rest of the pipeline for THIS tile: wire readback
    (transfer-only: harvest a wire whose compute was already forced by a
    2-byte sync), host C++ walk of the slice, and host-side submit of the
    full batch. The slowest leg names the optimization target; the
    roofline block calibrates the sweep against v5e peaks."""
    import numpy as np

    from reporter_tpu.ops.match import match_batch_wire_q, unpack_wire

    K = 24
    args, pts, sub, T = _stage_uniform_slice(m, traces)
    spec = getattr(m, "_wire_spec", None)       # probe the PRODUCTION wire
    wire = match_batch_wire_q(*args, m._tables, m.ts.meta, m.params, None,
                              spec=spec)
    np.asarray(wire)                            # warm executable + readback
    t0 = time.perf_counter()
    for _ in range(K):
        wire = match_batch_wire_q(*args, m._tables, m.ts.meta,
                                  m.params, None, spec=spec)
    np.asarray(wire)
    per_dispatch = max((time.perf_counter() - t0 - link_rtt) / K, 1e-6)

    # wire readback, transfer-only: force the dispatch's compute with a
    # 2-byte sync first, so the timed full harvest measures link transfer
    # (+1 RTT), not compute (jax caches the host copy after a harvest, so
    # this needs a FRESH dispatch, not a re-asarray of `wire`)
    w2 = match_batch_wire_q(*args, m._tables, m.ts.meta, m.params, None,
                            spec=spec)
    np.asarray(w2[0, 0, :1])
    t0 = time.perf_counter()
    host_wire = np.asarray(w2)
    dt_readback = time.perf_counter() - t0

    # host walk of the slice (the post-harvest leg of the e2e path)
    edges, offs, starts = unpack_wire(host_wire, spec)
    times = np.zeros(edges.shape, np.float64)
    times[:] = np.arange(edges.shape[1])[None, :]
    dt_walk = None
    if m._native_walker is not None:
        m._native_walker.walk_columns(edges, offs, starts, times,
                                      m.params.backward_slack)   # warm
        t0 = time.perf_counter()
        m._native_walker.walk_columns(edges, offs, starts, times,
                                      m.params.backward_slack)
        dt_walk = time.perf_counter() - t0

    t0 = time.perf_counter()
    work, inflight = m._submit_many(traces)
    dt_submit = time.perf_counter() - t0        # host leg, dispatches async
    np.asarray(inflight[-1][1])                 # let the queue drain
    del work, inflight

    probes_slice = len(sub) * T
    probes_all = sum(len(t.xy) for t in traces)
    scale = probes_all / probes_slice
    device_s_batch = per_dispatch * scale
    walk_s_batch = None if dt_walk is None else dt_walk * scale
    legs = {"device_sweep_s": round(device_s_batch, 3),
            "host_submit_s": round(dt_submit, 3),
            "host_walk_s": (None if walk_s_batch is None
                            else round(walk_s_batch, 3)),
            # transfers scale per-slice; the link RTT is paid once per
            # batched harvest, not per slice
            "readback_s": round(
                max(dt_readback - link_rtt, 0.0) * scale + link_rtt, 3)}
    # readback overlaps device compute at batch size (measured r4: i8-vs-
    # i16 interleave showed zero wall difference); submit and walk share
    # the one host core — the e2e bound is the slower of (host legs,
    # device leg)
    host_s = dt_submit + (walk_s_batch or 0.0)
    binding = ("host_submit+walk" if host_s >= device_s_batch
               else "device_sweep")
    out = {
        "device_ms_per_dispatch": round(per_dispatch * 1e3, 2),
        "dispatch_shape": f"{len(sub)}x{T}pts",
        "device_probes_per_sec": round(probes_slice / per_dispatch, 1),
        "readback_ms_per_slice": round(dt_readback * 1e3, 2),
        "wire_bytes_per_slice": int(host_wire.nbytes),
        "readback_MBps": round(
            host_wire.nbytes / max(dt_readback - link_rtt, 1e-6) / 1e6, 1),
        "host_walk_ms_per_slice": (None if dt_walk is None
                                   else round(dt_walk * 1e3, 2)),
        "host_submit_s_per_batch": round(dt_submit, 3),
        "device_s_per_batch": round(device_s_batch, 3),
        "legs_s_per_batch": legs,
        "binding_leg": binding,
        # co-located = no link in the loop: the slower pipeline leg rules
        "colocated_probes_per_sec": round(
            probes_all / max(dt_submit, device_s_batch), 1),
        "colocated_e2e_probes_per_sec": round(
            probes_all / max(host_s, device_s_batch), 1),
    }
    if roofline:
        out["roofline"] = _sweep_roofline(m, pts, per_dispatch)
    return out


def _near_tie_stats(m, traces, n: int = 400) -> dict:
    """Cross-road candidate near-tie density (VERDICT r4 weak #6): the
    fraction of points whose nearest two candidates on DIFFERENT roads
    (fwd/rev twins of one street always tie exactly — excluded via
    edge_opp; where the top pair is a twin, the gap is to candidate 3)
    sit within f32-flippable distance of each other. The organic residual
    disagreement is attributed to near-tie resolution + path ambiguity in
    prose; this makes the tie density a measured field the residual can
    be compared against (organic vs sf)."""
    import jax.numpy as jnp
    import numpy as np

    from reporter_tpu.ops.match import batch_candidates

    T0 = len(traces[0].xy)
    sub = [t for t in traces[:n] if len(t.xy) == T0]
    pts = np.stack([t.xy for t in sub]).astype(np.float32)
    valid = np.ones(pts.shape[:2], bool)
    c = batch_candidates(jnp.asarray(pts), jnp.asarray(valid), m._tables,
                         m.ts.meta, m.params, m.tuned_plan)
    d = np.asarray(c.dist)
    v = np.asarray(c.valid)
    e = np.asarray(c.edge)
    opp = m.ts.edge_opp
    twin = v[..., 1] & (e[..., 1] == opp[np.maximum(e[..., 0], 0)])
    alt = np.where(twin, 2, 1)                  # first non-twin rival
    has = np.take_along_axis(v, alt[..., None], -1)[..., 0] & v[..., 0]
    gap = (np.take_along_axis(d, alt[..., None], -1)[..., 0]
           - d[..., 0])[has]
    return {
        "points": int(has.sum()),
        "exact_tie_fraction": round(float((gap == 0.0).mean()), 5),
        "lt_1cm_fraction": round(float((gap < 0.01).mean()), 5),
        "lt_1m_fraction": round(float((gap < 1.0).mean()), 5),
    }


def _matcher_only_latency(m, trace, link_rtt: float,
                          K: int = 16) -> "float | None":
    """Co-located B=1 decode latency (VERDICT r4 next #8): K chained B=1
    wire dispatches, ONE sync, so (window - RTT)/K is the device's own
    per-trace time with the link amortized out. Median of 3 windows."""
    import jax
    import numpy as np

    from reporter_tpu.matcher.api import _bucket_len
    from reporter_tpu.ops.match import OFFSET_QUANTUM, match_batch_wire_q

    T = len(trace.xy)
    b = _bucket_len(T)
    pts = np.zeros((1, b, 2), np.float32)
    pts[0, :T] = trace.xy
    pts[0, T:] = pts[0, :1]
    lens = np.full(1, T, np.int32)
    origins = pts[:, 0, :].copy()
    dq = np.round((pts - origins[:, None, :]) * np.float32(1 / OFFSET_QUANTUM))
    args = (jax.device_put(dq.astype(np.int16)), jax.device_put(origins),
            jax.device_put(lens))
    np.asarray(args[0][0, 0])
    spec = getattr(m, "_wire_spec", None)
    wire = match_batch_wire_q(*args, m._tables, m.ts.meta, m.params, None,
                              spec=spec)
    np.asarray(wire)                            # warm the B=1 executable
    windows = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(K):
            wire = match_batch_wire_q(*args, m._tables, m.ts.meta,
                                      m.params, None, spec=spec)
        np.asarray(wire)
        windows.append(max((time.perf_counter() - t0 - link_rtt) / K, 1e-6))
    return sorted(windows)[1]


def _service_payloads(ts, traces, n_max, tag="conc"):
    import numpy as np

    from reporter_tpu.geometry import xy_to_lonlat

    origin = np.asarray(ts.meta.origin_lonlat)
    payloads = []
    for i, t in enumerate(traces[:n_max]):
        lonlat = xy_to_lonlat(np.asarray(t.xy, np.float64), origin)
        payloads.append({"uuid": f"{tag}-{i}", "trace": [
            {"lat": float(la), "lon": float(lo), "time": float(tt)}
            for (lo, la), tt in zip(lonlat, t.times)]})
    return payloads


def _sched_delta(before: "dict | None", after: "dict | None") -> dict:
    """Scheduler-snapshot delta for one measured window: counters
    subtract, histogram dicts subtract key-wise (dropping zeros)."""
    if not after:
        return {}
    before = before or {}

    def _dhist(key):
        b = before.get(key, {})
        d = {k: v - b.get(k, 0) for k, v in after.get(key, {}).items()}
        return {k: v for k, v in d.items() if v}

    # no "device_batches" here: both arms report it uniformly from
    # app.stats at the call sites (the scheduler's own batch counter
    # would shadow that shared-key computation)
    return {
        "padded_traces": (after["padded_traces"]
                          - before.get("padded_traces", 0)),
        "deferred": after["deferred"] - before.get("deferred", 0),
        "rejected": after["rejected"] - before.get("rejected", 0),
        "inflight_hist": _dhist("inflight_hist"),
        "padding_by_bucket": _dhist("padding_by_bucket"),
    }


def _service_saturation_curve(apps: dict, ts, traces, levels=(16, 64, 256),
                              rounds: int = 2) -> list:
    """Serving face under increasing concurrency, interleaved A/B
    (round-7 tentpole): ``apps`` maps arm name → ReporterApp (e.g.
    "scheduler" = continuous in-flight batching, "legacy" =
    queue-and-combine). For each client level the arms alternate
    round-by-round so both see the SAME link mood; per arm per level:
    req/s, p50/p99 request latency, device batches, and — scheduler arm —
    the in-flight-batch dispatch histogram and padding waste per bucket
    (snapshot deltas over the measured rounds only)."""
    import threading

    n_max = min(max(levels), len(traces))
    payloads = _service_payloads(ts, traces, n_max)

    def _round(app, record: "list | None", errors: list, n: int):
        barrier = threading.Barrier(n)

        def worker(p):
            barrier.wait()
            t0 = time.perf_counter()
            try:
                app.report_one(p)
            except Exception as exc:   # a dead thread must not
                errors.append(repr(exc))   # silently skew the p50
                return
            if record is not None:
                record.append(time.perf_counter() - t0)

        threads = [threading.Thread(target=worker, args=(p,))
                   for p in payloads[:n]]
        for th in threads:
            th.start()
        for th in threads:
            th.join()

    curve = []
    for level in levels:
        n = min(level, len(payloads))
        entry: dict = {"clients": n, "rounds": rounds}
        # warm BOTH arms first (pays combined/padded-shape jit), then
        # interleave measured rounds arm-by-arm: per-round alternation
        # keeps the two arms inside the same link mood window, so the
        # A/B ratio is same-mood by construction
        for app in apps.values():
            _round(app, None, [], n)
        lats: dict = {a: [] for a in apps}
        walls: dict = {a: 0.0 for a in apps}
        draw_walls: dict = {a: [] for a in apps}
        errors: dict = {a: [] for a in apps}
        before = {a: (app.stats["batches"],
                      app.scheduler.snapshot() if app.scheduler else None)
                  for a, app in apps.items()}
        for _ in range(rounds):
            for arm, app in apps.items():
                t0 = time.perf_counter()
                _round(app, lats[arm], errors[arm], n)
                dt = time.perf_counter() - t0
                walls[arm] += dt
                draw_walls[arm].append(dt)
        for arm, app in apps.items():
            ls = sorted(lats[arm])
            batches0, snap0 = before[arm]
            # per-draw req/s (round 19): the r18 capture note's
            # "120-484 req/s across draws" bimodality class must be
            # diagnosable FROM the capture — per-round rates make a
            # bimodal arm visible without rediscovering it by rerunning
            draws = [round(n / w, 1) for w in draw_walls[arm] if w > 0]
            sub = {
                "req_per_sec": (round(len(ls) / walls[arm], 1)
                                if ls and walls[arm] > 0 else None),
                "round_rps": draws,
                "p50_ms": (round(ls[len(ls) // 2] * 1e3, 1) if ls else None),
                "p99_ms": (round(ls[min(len(ls) - 1,
                                        int(len(ls) * 0.99))] * 1e3, 1)
                           if ls else None),
                "errors": len(errors[arm]),
                "device_batches": app.stats["batches"] - batches0,
            }
            if app.scheduler is not None:
                sub.update(_sched_delta(snap0, app.scheduler.snapshot()))
            if errors[arm]:
                sub["error_samples"] = errors[arm][:3]
            entry[arm] = sub
        curve.append(entry)
    return curve


def _service_open_loop(apps: dict, ts, traces,
                       rates=(100, 250, 500, 1000),
                       seconds: float = 2.5) -> list:
    """Open-loop offered-rate sweep (round-7 satellite): submitters pace
    requests at a FIXED offered rate regardless of completions — unlike
    the closed-loop curve, latency inflation cannot throttle the offer,
    so saturation shows up as achieved < offered and p99 growth instead
    of a flattering self-limited req/s. Arms interleave per rate (same
    link mood). Scheduler-arm 503s from the bounded admission queue are
    counted as ``shed`` (explicit overload degradation), not errors."""
    import itertools
    import threading

    from reporter_tpu.service.scheduler import ServiceOverloaded

    base = _service_payloads(ts, traces, min(256, len(traces)), tag="ol")

    def _warm(arm, app):
        # pays the batch-shape jit OUTSIDE the paced window, so the first
        # rate point measures the link, not XLA: one report_many per
        # trace-count rung up through max_batch_traces covers the
        # scheduler's whole reachable padded-shape set (at 1000 rps ×
        # ~110 ms RTT a close can hold 100+ traces, so the big rungs DO
        # get hit; that the set is warmable at all is the point of the
        # rungs — the legacy arm still compiles odd Bs mid-measure when
        # combining, an honest cost of unpadded shapes)
        rungs = [1, 2, 4, 8, 16, 32, 64, 128, 256]
        cap = max(a.config.service.max_batch_traces
                  for a in apps.values())
        for w, k in enumerate([r for r in rungs if r <= max(cap, 1)]):
            k = min(k, len(base))
            batch = []
            for i in range(k):
                p = dict(base[i])
                p["uuid"] = f"olwarm-{arm}-{w}-{i}"
                batch.append(p)
            app.report_many(batch)

    for arm, app in apps.items():
        _warm(arm, app)
    out = []
    for rate in rates:
        entry: dict = {"offered_rps": rate}
        for arm, app in apps.items():
            n = max(1, int(rate * seconds))
            lats: list = []
            errors: list = []
            shed: list = []      # list.append is atomic; int += is not
            idx = itertools.count()
            n_workers = min(128, max(8, int(rate * 0.5)))
            start = time.perf_counter() + 0.05   # common epoch, post-spawn
            before = (app.stats["batches"],
                      app.scheduler.snapshot() if app.scheduler else None)

            def worker(arm=arm, app=app, n=n, rate=rate, start=start,
                       lats=lats, errors=errors, shed=shed, idx=idx):
                while True:
                    i = next(idx)
                    if i >= n:
                        return
                    target = start + i / rate
                    now = time.perf_counter()
                    if target > now:
                        time.sleep(target - now)
                    p = dict(base[i % len(base)])
                    p["uuid"] = f"ol-{arm}-{rate}-{i}"
                    t0 = time.perf_counter()
                    try:
                        app.report_one(p)
                    except ServiceOverloaded:
                        shed.append(1)
                        continue
                    except Exception as exc:
                        errors.append(repr(exc))
                        continue
                    lats.append(time.perf_counter() - t0)

            threads = [threading.Thread(target=worker)
                       for _ in range(n_workers)]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            wall = time.perf_counter() - start
            ls = sorted(lats)
            sub = {
                "achieved_rps": (round(len(ls) / wall, 1)
                                 if ls and wall > 0 else 0.0),
                "p50_ms": (round(ls[len(ls) // 2] * 1e3, 1) if ls else None),
                "p99_ms": (round(ls[min(len(ls) - 1,
                                        int(len(ls) * 0.99))] * 1e3, 1)
                           if ls else None),
                "shed": len(shed),
                "errors": len(errors),
                "device_batches": app.stats["batches"] - before[0],
            }
            if app.scheduler is not None:
                sub.update(_sched_delta(before[1], app.scheduler.snapshot()))
            if errors:
                sub["error_samples"] = errors[:3]
            entry[arm] = sub
        out.append(entry)
    return out


def _run_chaos_legs(ts, traces, detail: dict, split: dict) -> None:
    """The three ISSUE-4 legs, shared by the chip composite and the
    CPU-forced validation path (REPORTER_BENCH_CHAOS=1): publisher
    outage (in-proc, fault-injected), kill-and-recover (subprocess
    SIGKILL), live 2-process consumer group."""
    import shutil
    import tempfile

    t0 = time.perf_counter()
    chaos_dir = tempfile.mkdtemp(prefix="rtpu_chaos_")
    try:
        tiles_path = os.path.join(chaos_dir, "tiles.npz")
        ts.save(tiles_path)
        n_chaos = min(int(os.environ.get("REPORTER_BENCH_CHAOS_VEHICLES",
                                         "2000")), len(traces))
        detail["publish_outage"] = _publish_outage_soak(ts, traces,
                                                        n_chaos, chaos_dir)
        split["publish_outage_s"] = round(time.perf_counter() - t0, 1)
        t0 = time.perf_counter()
        detail["recovery"] = _recovery_bench(ts, tiles_path, traces,
                                             n_chaos, chaos_dir)
        split["recovery_s"] = round(time.perf_counter() - t0, 1)
        t0 = time.perf_counter()
        detail["streaming_soak_mp"] = _streaming_soak_mp(
            ts, tiles_path, traces, n_chaos, chaos_dir)
        split["streaming_soak_mp_s"] = round(time.perf_counter() - t0, 1)
    finally:
        # multi-cycle durable broker logs for a 2000-vehicle fleet add
        # up run over run — the evidence lives in the detail, not /tmp
        shutil.rmtree(chaos_dir, ignore_errors=True)


def _fleet_bench(tpu_ok: bool, n_metros: int = 8) -> dict:
    """ISSUE 6 tentpole evidence: N>=8 generated metros served
    concurrently from ONE process through the fleet residency layer
    (reporter_tpu/fleet/). Three phases: (1) steady-state mixed traffic
    with the whole fleet resident (unbounded budget) — submitter threads
    round-robin every metro, each dispatch under a residency lease;
    (2) a cold-metro promotion storm — the budget shrinks to ~half the
    fleet's staged bytes, then cyclic touches make every request a miss
    (LRU's worst case), so each one pays a counted, traced promotion and
    an eviction; (3) a per-metro fidelity audit AFTER the storm's
    evict→promote cycles: harvested wire bytes must equal both a
    dedicated single-metro SegmentMatcher's and the metro's own
    pre-paging harvest, byte for byte. Metros get DISTINCT topologies
    (per-metro seeds) and disjoint bboxes (shifted centers) — clones
    would share compiled shapes and understate the fleet's real cost.
    CPU-forced runs validate the full leg at tiny scale (the r7
    BENCH_DETAIL_CPU.json convention)."""
    import threading as _threading

    import numpy as np

    from reporter_tpu.config import CompilerParams, Config
    from reporter_tpu.fleet import FleetResidency
    from reporter_tpu.matcher.api import SegmentMatcher, Trace
    from reporter_tpu.netgen.synthetic import generate_city
    from reporter_tpu.netgen.traces import synthesize_fleet
    from reporter_tpu.tiles.compiler import compile_network

    nx = ny = 8 if tpu_ok else 6
    n_tr = 24 if tpu_ok else 6          # traces per metro
    n_pt = 60 if tpu_ok else 40         # points per trace
    workers = 4 if tpu_ok else 2
    rounds = 3 if tpu_ok else 2
    storm_cycles = 2 if tpu_ok else 1

    cfg = Config(matcher_backend="jax")
    t0 = time.perf_counter()
    tilesets = []
    fleets: dict = {}
    for i in range(n_metros):
        net = generate_city("tiny", nx=nx, ny=ny, seed=60 + i,
                            center=(-125.0 + i * 0.7, 38.0))
        net.name = f"fleet{i:02d}"
        ts = compile_network(net, CompilerParams(reach_radius=500.0))
        tilesets.append(ts)
        probes = synthesize_fleet(ts, n_tr, num_points=n_pt, seed=9 + i)
        fleets[ts.name] = [Trace(uuid=f"f{i}-{j}", xy=p.xy, times=p.times)
                           for j, p in enumerate(probes)]
    build_s = time.perf_counter() - t0
    names = [ts.name for ts in tilesets]

    def _wire(m, traces) -> bytes:
        """Raw device wire bytes in submission order — the byte-level
        artifact the bit-identity contract pins (same harvest as
        tests/test_fleet.py)."""
        _, inflight = m._submit_many(traces)
        return b"".join(np.asarray(a).tobytes() for _, a in inflight)

    fr = FleetResidency(tilesets, cfg)      # unbounded: no paging yet
    # warm: promote every metro + compile its batch shape (untimed),
    # then harvest the pre-paging reference wires
    pre_wires = {}
    for n in names:
        with fr.lease(n) as m:
            m.match_many(fleets[n])
            pre_wires[n] = _wire(m, fleets[n])
    total_bytes = fr.resident_bytes

    # -- phase 1: steady-state mixed traffic, whole fleet resident ------
    jobs = [n for _ in range(rounds) for n in names]
    cursor = {"i": 0}
    lock = _threading.Lock()
    busy = {n: 0.0 for n in names}
    probes_done = {n: 0 for n in names}
    errors: list = []

    def _submitter():
        while True:
            with lock:
                if cursor["i"] >= len(jobs):
                    return
                name = jobs[cursor["i"]]
                cursor["i"] += 1
            try:
                t1 = time.perf_counter()
                with fr.lease(name) as m:
                    m.match_many(fleets[name])
                dt = time.perf_counter() - t1
                with lock:
                    busy[name] += dt
                    probes_done[name] += sum(len(t.xy)
                                             for t in fleets[name])
            except Exception as exc:    # recorded, not raised: the leg
                # must finish and report — and the worker moves on to
                # the next job (exiting would silently degrade measured
                # concurrency for the rest of the phase while the
                # artifact still records the nominal worker count)
                with lock:
                    if len(errors) < 32:
                        errors.append(repr(exc))

    t0 = time.perf_counter()
    threads = [_threading.Thread(target=_submitter)
               for _ in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    mixed_wall = time.perf_counter() - t0

    # -- phase 2: cold-metro promotion storm through a half-size budget -
    cap = max(1, int(total_bytes * 0.5))
    fr.set_capacity(cap)
    storm_lat: list = []
    storm_promote: list = []
    t0 = time.perf_counter()
    for _ in range(storm_cycles):
        for name in names:              # cyclic touches: every one a miss
            t1 = time.perf_counter()
            # page-in timed apart from the dispatch: the run-wide
            # fleet_promote_seconds histogram also holds the warm-phase
            # and audit promotions (first HBM placements — systematically
            # different), so the storm's paging quantiles must come from
            # the storm's own samples
            fr.promote(name)
            t2 = time.perf_counter()
            with fr.lease(name) as m:
                m.match_many(fleets[name])
            storm_promote.append(t2 - t1)
            storm_lat.append(time.perf_counter() - t1)
    storm_wall = time.perf_counter() - t0

    # -- phase 3: per-metro fidelity audit (post evict→promote cycles) --
    fidelity: dict = {}
    for ts in tilesets:
        name = ts.name
        dedicated = SegmentMatcher(ts, cfg)
        want = _wire(dedicated, fleets[name])
        with fr.lease(name) as m:
            got = _wire(m, fleets[name])
        occ_m = fr.occupancy()["metros"][name]
        fidelity[name] = {
            "wire_identical_to_dedicated": got == want,
            "wire_identical_after_paging": got == pre_wires[name],
            "promotions": occ_m["promotions"],
            "demotions": occ_m["demotions"],
            # round 17: the self-tuned plan serving this metro (None on
            # CPU composites — the short-circuit — or explicit knobs);
            # identity above already held THROUGH the plan, extending
            # the sweep_ab contract over tuned fleets
            "tuned_plan": occ_m["tuned_plan"],
        }
        del dedicated
    occ = fr.occupancy()

    def _pq(q, xs=storm_lat):
        # np.percentile, like every other quantile in the artifact —
        # mixing estimators across legs would skew cross-leg reads
        return round(float(np.percentile(xs, q * 100)) * 1e3, 2)

    return {
        "config": (f"{n_metros} metros ({nx}x{ny} grid, distinct "
                   f"topologies), {n_tr}x{n_pt}pt traces/metro, "
                   f"storm budget = 50% of staged bytes"),
        "n_metros": n_metros,
        "build_seconds": round(build_s, 1),
        "staged_bytes_total": int(total_bytes),
        "mixed": {
            "workers": workers,
            "rounds": rounds,
            "wall_seconds": round(mixed_wall, 2),
            # numerator = probes actually matched (an errored worker
            # leaves jobs unexecuted; the nominal count would inflate
            # the recorded rate)
            "probes_per_sec": round(
                sum(probes_done.values()) / mixed_wall, 1),
            # per-metro service rate over that metro's own busy time
            # (wall is shared by the round-robin); exact per-metro probe
            # counts ride along for reconstruction
            "per_metro_kpps": {
                n: (round(probes_done[n] / busy[n] / 1e3, 1)
                    if busy[n] else None) for n in names},
            **({"errors": errors[:4]} if errors else {}),
        },
        "storm": {
            "capacity_bytes": cap,
            "touches": n_metros * storm_cycles,
            "wall_seconds": round(storm_wall, 2),
            "promote_p50_ms": _pq(0.50, storm_promote),
            "promote_p99_ms": _pq(0.99, storm_promote),
            "promote_to_first_report_p50_ms": _pq(0.50),
            "promote_to_first_report_p99_ms": _pq(0.99),
        },
        "occupancy": occ,
        "fidelity": {
            # the acceptance bit: every metro's post-storm wires equal
            # BOTH its dedicated matcher's and its own pre-paging harvest
            "wires_bit_identical": all(
                f["wire_identical_to_dedicated"]
                and f["wire_identical_after_paging"]
                for f in fidelity.values()),
            "wires_identical_to_dedicated": all(
                f["wire_identical_to_dedicated"]
                for f in fidelity.values()),
            "wires_identical_after_paging": all(
                f["wire_identical_after_paging"]
                for f in fidelity.values()),
            "per_metro": fidelity,
        },
    }


def _topology_lease_arm(workdir: str, tiles_path: str, cfg_path: str,
                        batches, n_pt: int, cycles: int = 2,
                        timeout: float = 120.0) -> dict:
    """detail.topology.lease (round 23) — ELASTIC membership under
    in-worker chaos, riding the main arm's tile/config/compile-cache:
    lease-mode workers over a 4-partition broker through the
    epoch-fenced lease table (distributed/lease.py). lease-a bootstraps
    alone and takes every partition, and runs an RTPU_FAULTS plan
    INSIDE itself (dispatch hangs, then an injected mid-checkpoint
    crash that kills the process hard); lease-b joins and inherits the
    table; a cold lease-c JOINS mid-soak (supervisor rebalance →
    revoke toward the newcomer → adoption at committed floors); a
    leased lease-b takes a SIGKILL (lease expiry → orphan →
    reassignment); and lease-c, the survivor, drains the whole broker
    and exits on the GLOBAL drain (--exit-on-drain: table floors over
    every partition, sub-threshold tails published by the in-loop
    force-flush stall escape). Asserted: join→first-acquire and
    kill→reacquire latency, fencing (the killed worker's stale-epoch
    commit rejected), offset-granularity conservation (floors reach end
    offsets with commit spans never overlapping — zero lost, zero
    duplicated), the topology drained by its own exits, and per-worker
    fault stats surfaced through the snapshot gauges (the crashed
    incarnation prints no exit report — the spool is the surviving
    record).

    Every step waits on what the table, the supervisor's event log or
    the snapshot spool shows, never on a fixed delay, so the order
    holds on a loaded host. lease-a and lease-b serve until they die
    (an exit-on-drain there could end either before its death);
    neither flushes a sub-threshold tail, so while lease-b lives some
    floor stays below its end offset and lease-c cannot exit early,
    and lease-b is killed holding such a partition, which no live
    worker but lease-c can then adopt."""
    from reporter_tpu.distributed import Supervisor, worker_member
    from reporter_tpu.distributed.lease import LeaseTable, StaleLeaseError
    from reporter_tpu.streaming.durable_queue import DurableIngestQueue

    arm_dir = os.path.join(workdir, "lease_arm")
    broker_dir = os.path.join(arm_dir, "broker")
    lease_dir = os.path.join(arm_dir, "leases")
    os.makedirs(arm_dir, exist_ok=True)
    # ttl must comfortably exceed the worker's first-flush compile
    # stall on this one-core box (~2-4 s even cache-warm): a shorter
    # ttl makes every startup a lease-loss storm (measured at 1.2 s:
    # 12 lease_lost events, ~12 s of discard/reconsume churn)
    ttl_s = 2.4

    def _stage(cyc: int) -> "list[int]":
        # reopen-append: the durable log continues its offsets, so a
        # mid-soak tranche is indistinguishable from a live producer
        qq = DurableIngestQueue(broker_dir, 4)
        for b in batches:
            tt = b.time + cyc * float(n_pt)
            for i in range(b.n):
                qq.append({"uuid": str(b.uuid[i]),
                           "lat": float(b.lat[i]),
                           "lon": float(b.lon[i]),
                           "time": float(tt[i])})
        ends = [qq.end_offset(p) for p in range(4)]
        qq.close()
        return ends

    per_cycle = sum(b.n for b in batches)
    produced = cycles * per_cycle
    end_offsets = _stage(0)
    table = LeaseTable(lease_dir, num_partitions=4, ttl_s=ttl_s)
    # dispatch hangs at calls 0-1 are the RECOVERABLE chaos (a hang is
    # a plain sleep with the watchdog off); the first checkpoint call
    # at index >= 4 then raises InjectedCrash → the CLI dies via
    # os._exit(17), no exit report — the snapshot spool is the
    # surviving record. lease-a holds every partition from its first
    # sync, so it dispatches within its first steps (each uuid reaches
    # flush_min_points inside the first tranche), well before
    # checkpoint call 4, and the snapshot spool of that iteration
    # carries the fired count. The crash window is open-ended:
    # checkpoint calls are wall-clock gated and a worker that serves
    # until it dies always reaches one.
    fault_spec = "dispatch:hang(0.6)@0-2;checkpoint:crash@4-"

    def _member(name: str, env: "dict | None" = None,
                exit_on_drain: bool = False):
        return worker_member(name, tiles_path, broker_dir, arm_dir,
                             config=cfg_path, exit_on_drain=exit_on_drain,
                             lease_dir=lease_dir, lease_ttl_s=ttl_s,
                             env=env)

    # in-worker chaos rides MemberSpec.env: dispatch hangs + a
    # mid-checkpoint InjectedCrash → os._exit(17)
    members = [_member("lease-a", env={"RTPU_FAULTS": fault_spec,
                                       "RTPU_FAULT_SEED": "11"})]
    # restart=False: an elastically-leased topology survives by
    # REBALANCING onto the survivors, not restart-in-place — dead
    # members' leases expire and their partitions move
    sup = Supervisor(members, arm_dir, restart=False, max_restarts=0,
                     poll_s=0.05, lease_dir=lease_dir,
                     base_env={"JAX_PLATFORMS": "cpu",
                               "RTPU_TOPO_SNAPSHOT_INTERVAL_S": "0.3",
                               # r24: an in-worker SLO tick would add
                               # its own cost to every loop iteration
                               # of the chaos worker; SLO chaos claims
                               # live in detail.slo
                               "RTPU_SLO": "0"})
    note = None
    join_s = reacquire_s = None
    fenced = None
    try:
        sup.start()

        def _wait(pred, lim) -> bool:
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < lim:
                if pred():
                    return True
                time.sleep(0.03)
            return False

        def _acquires(member: str) -> "list[dict]":
            return [e for e in table.events()
                    if e["event"] == "acquire"
                    and e.get("member") == member]

        def _owned_by(member: str) -> "list[tuple[int, int]]":
            return sorted(
                (int(p), int(ent["epoch"]))
                for p, ent in table.state()["partitions"].items()
                if ent["owner"] == member)

        def _died(member: str) -> bool:
            return any(e["event"] == "member_death"
                       and e.get("member") == member
                       for e in sup.events())

        # ---- bootstrap: lease-a takes the table, lease-b joins ------
        if not _wait(lambda: bool(_acquires("lease-a")), 30.0):
            note = "lease-a never acquired"
        sup.add_member(_member("lease-b"))
        # the rebalancer revokes half toward lease-b — or, once lease-a
        # has crashed, hands it lease-a's expired leases
        if not _wait(lambda: bool(_owned_by("lease-b")), 30.0):
            note = (note or "") + " lease-b never owned a partition"

        # ---- lease-a's injected crash -------------------------------
        # its leases expire and lease-b adopts them at their floors
        if not _wait(lambda: _died("lease-a"), 30.0):
            note = (note or "") + " lease-a never crashed"

        # ---- second tranche, then a cold worker JOINS mid-soak ------
        # the tranche lands before the join, so the end offsets lease-c
        # drains toward are final from its first step on
        for c2 in range(1, cycles):
            end_offsets = _stage(c2)
        t_join = time.time()
        sup.add_member(_member("lease-c", exit_on_drain=True))
        # lease-b lives and holds every partition, so the plan revokes
        # half toward the newcomer
        if _wait(lambda: bool(_acquires("lease-c")), 30.0):
            join_s = round(max(0.0, _acquires("lease-c")[0]["t"]
                               - t_join), 2)
        else:
            note = (note or "") + " join never acquired"

        # ---- SIGKILL of a leased worker + zombie fencing probe ------
        # the victim partition still has uncommitted records (lease-b
        # never flushes its sub-threshold tails), so after the kill it
        # is pinned until lease-c adopts it: the drain cannot end first
        def _victim() -> "tuple[int, int] | None":
            floors = table.floors()
            return next(((p, e) for p, e in _owned_by("lease-b")
                         if floors[p] < end_offsets[p]), None)

        _wait(lambda: _victim() is not None, 30.0)
        vic = _victim()
        if vic is not None:
            p_vic, epoch_vic = vic
            t_kill = time.time()
            sup.kill_member("lease-b")

            def _reacquired():
                ent = table.state()["partitions"][str(p_vic)]
                return (ent["owner"] not in (None, "lease-b")
                        and int(ent["epoch"]) > epoch_vic)

            if _wait(_reacquired, 30.0):
                acq = [e for e in table.events()
                       if e["event"] == "acquire"
                       and e.get("partition") == p_vic
                       and e["t"] >= t_kill]
                reacquire_s = round(max(
                    0.0, (acq[0]["t"] if acq else time.time()) - t_kill),
                    2)
                # the zombie's stale-epoch commit MUST be fenced out —
                # the lease arm's whole point
                try:
                    table.commit("lease-b", p_vic, epoch_vic,
                                 table.committed(p_vic) + 1)
                    fenced = False
                except StaleLeaseError:
                    fenced = True
            else:
                note = (note or "") + " victim never reacquired"
        else:
            note = (note or "") + " lease-b held no uncommitted partition"

        # ---- drain + offset-granularity conservation ----------------
        # lease-c exits on the global drain; the topology has ended by
        # its own exits once every member is down and the floors are
        # at the end offsets
        def _drained():
            floors = table.floors()
            return (sup.drained()
                    and sum(max(0, end_offsets[p] - floors[p])
                            for p in range(4)) == 0)

        if not _wait(_drained, timeout):
            note = (note or "") + " drain timed out"
        sup.poll_once()
        # the survivor left on its own (exit code 0, not the stop below)
        drained_by_exit = sup.drained() and any(
            e["event"] == "member_exit" and e.get("member") == "lease-c"
            and e.get("returncode") == 0 for e in sup.events())
        floors = table.floors()
        lost = sum(max(0, end_offsets[p] - floors[p]) for p in range(4))
        levents = table.events()
        dup = commits = 0
        last_to = [0] * 4
        for e in levents:
            if e["event"] != "commit":
                continue
            commits += 1
            p = int(e["partition"])
            dup += max(0, last_to[p] - int(e["floor_from"]))
            last_to[p] = max(last_to[p], int(e["floor_to"]))
        stale_evts = sum(1 for e in levents
                         if e["event"] == "commit_rejected")
        lev_counts: dict = {}
        for e in levents:
            lev_counts[e["event"]] = lev_counts.get(e["event"], 0) + 1
        snaps = sup.snapshots()
        a_gauges = (((snaps.get("lease-a") or {}).get("metrics")
                     or {}).get("gauges") or {})
        fault_fired = a_gauges.get("fault_fired")
        health = sup.health()
        rebalances = sum(1 for e in sup.events()
                         if e["event"] == "rebalance")
        out = {
            "config": (f"1+2 lease-mode CPU workers over 4 leased "
                       f"partitions ({produced} probes, ttl {ttl_s}s): "
                       f"lease-a bootstraps, lease-b then lease-c "
                       f"join, SIGKILL lease-b, in-worker chaos in "
                       f"lease-a, lease-c exits on the global drain"),
            "ttl_s": ttl_s,
            "workers_start": 1,
            "workers_joined": 2,
            "broker_probes": int(produced),
            "deaths": int(health.get("deaths_total", 0)),
            "join_to_first_acquire_seconds": join_s,
            "kill_to_reacquire_seconds": reacquire_s,
            "stale_commit_rejected": fenced,
            "commit_rejected_events": int(stale_evts),
            "lost_records": int(lost),
            "zero_lost_ok": bool(lost == 0),
            "duplicate_commits": int(dup),
            "zero_dup_ok": bool(dup == 0),
            "drained_by_exit_ok": bool(drained_by_exit),
            "commits": int(commits),
            "rebalances": int(rebalances),
            "fault_spec": fault_spec,
            "fault_fired": (None if fault_fired is None
                            else int(fault_fired)),
            "fault_stats_surfaced": bool(fault_fired),
            "lease_event_counts": lev_counts,
        }
        if note:
            out["note"] = note.strip()
        return out
    finally:
        sup.stop()


def _topology_bench(tpu_ok: bool, timeout: float = 420.0) -> dict:
    """detail.topology (round 19) — ROADMAP item 4 as a measured,
    journaled artifact: a REAL supervised topology (1 supervisor × 2
    ``streaming.__main__`` worker subprocesses over disjoint partition
    pairs of one durable records broker + the supervisor's fake
    datastore sink + its /metrics+/health WSGI face), soaked with a
    mid-soak SIGKILL of worker-0. Recorded: the supervisor-observed
    death → restart → recovery path, zero-lost accounting at offset
    granularity across the replay, cross-worker aggregation FIDELITY
    (merged exposition == per-leaf sums over the spooled member
    snapshots, every counter and every histogram bucket), and one
    stitched cross-pid Chrome trace (producer → broker dwell → worker
    match, threaded by broker-propagated trace ids). Self-contained
    (builds + saves its own tiny tile) and CPU-WORKERED on every
    composite — the leg measures the topology plane, not the device, so
    a chip composite must not donate its chip to two subprocesses'
    startup compiles; ``aggregate.probes_per_sec_wall`` is one-core CPU
    throughput by construction and the config says so. Round 23 adds
    the LEASE arm (``_topology_lease_arm`` — detail.topology.lease):
    elastic membership over the epoch-fenced lease table with a
    mid-soak join, a leased-worker SIGKILL, and an in-worker
    RTPU_FAULTS plan, asserting rebalance latency, fencing, and
    offset-granularity conservation on every composite."""
    import shutil
    import tempfile

    from reporter_tpu.config import CompilerParams
    from reporter_tpu.distributed import (Supervisor, aggregate, stitch,
                                          worker_member)
    from reporter_tpu.matcher.api import Trace
    from reporter_tpu.netgen.synthetic import generate_city
    from reporter_tpu.netgen.traces import synthesize_fleet
    from reporter_tpu.streaming.durable_queue import DurableIngestQueue
    from reporter_tpu.tiles.compiler import compile_network
    from reporter_tpu.utils import tracing

    n_tr, n_pt, cycles, stamp_every = 12, 48, 3, 4
    workdir = tempfile.mkdtemp(prefix="rtpu_topology_")
    sup = None
    try:
        # ---- tile + fleet + staged records broker (producer side) ----
        net = generate_city("tiny", nx=6, ny=6, seed=77)
        net.name = "topo"
        ts = compile_network(net, CompilerParams(reach_radius=500.0))
        tiles_path = os.path.join(workdir, "topo_tiles.npz")
        ts.save(tiles_path)
        probes = synthesize_fleet(ts, n_tr, num_points=n_pt, seed=5)
        traces = [Trace(uuid=f"v{j}", xy=p.xy, times=p.times)
                  for j, p in enumerate(probes)]
        batches, V, _ = _stage_round_batches(ts, traces, n_tr,
                                             steps_per_batch=4)
        broker_dir = os.path.join(workdir, "broker")
        traces_dir = os.path.join(workdir, "traces")
        q = DurableIngestQueue(broker_dir, 4)
        # the producer's own flight-recorder ring (NOT the process
        # tracer: bench's global recorder stays whatever the operator
        # configured) — its ``produce`` spans carry the trace ids the
        # workers will inherit from the stamped records
        rec = tracing.FlightRecorder(capacity=8192).configure(enabled=True)
        produced = stamped = 0
        for c in range(cycles):
            for b in batches:
                tt = b.time + c * float(n_pt)
                for i in range(b.n):
                    r = {"uuid": str(b.uuid[i]), "lat": float(b.lat[i]),
                         "lon": float(b.lon[i]), "time": float(tt[i])}
                    if produced % stamp_every == 0:
                        tid = f"{r['uuid']}@{produced}"
                        tracing.stamp_record(r, tid)
                        with rec.span("produce", trace_id=tid):
                            q.append(r)
                        stamped += 1
                    else:
                        q.append(r)
                    produced += 1
        end_offsets = [q.end_offset(p) for p in range(4)]
        q.close()
        rec.dump(path=os.path.join(traces_dir, "ring_producer.json"),
                 reason="producer_done")

        # ---- the topology ------------------------------------------
        cfg_path = os.path.join(workdir, "worker_config.json")
        with open(cfg_path, "w") as f:
            json.dump({"streaming": {
                "flush_min_points": 40,
                # small polls: many steps per partition, so the SIGKILL
                # lands with real lag outstanding (the r9 mid-stream
                # discipline), not around one drain-everything poll
                "poll_max_records": 120,
                "hist_flush_interval": 0.0,
                "flush_max_age": 1e6,
            }}, f)
        members = [
            worker_member("worker-0", tiles_path, broker_dir, workdir,
                          partitions=[0, 1], config=cfg_path),
            worker_member("worker-1", tiles_path, broker_dir, workdir,
                          partitions=[2, 3], config=cfg_path),
        ]
        sup = Supervisor(
            members, workdir, restart=True, max_restarts=2, poll_s=0.05,
            base_env={
                # CPU-pinned workers on EVERY composite (see docstring)
                "JAX_PLATFORMS": "cpu",
                "RTPU_TRACE": "1", "RTPU_TRACE_DIR": traces_dir,
                "RTPU_TOPO_SNAPSHOT_INTERVAL_S": "0.3",
                # r24: keep this leg's timing/dump budget exactly r19 —
                # a worker SLO alert would share the bounded post-mortem
                # budget the death/stitch assertions draw on; SLO chaos
                # claims live in detail.slo
                "RTPU_SLO": "0",
            })
        t_soak0 = time.perf_counter()
        sup.start()
        http = sup.serve_http()
        note = None

        def _sink_rows() -> int:
            return sup.sink.stats()["rows"]

        def _w0_lag() -> "int | None":
            doc = sup.snapshots().get("worker-0") or {}
            return (doc.get("stats") or {}).get("lag")

        def _w0_exited_clean() -> bool:
            return any(e["event"] == "member_exit"
                       and e.get("member") == "worker-0"
                       for e in sup.events())

        # ---- mid-soak SIGKILL of worker-0 ---------------------------
        # triggered by worker-0's OWN progress: its first spooled
        # snapshot (matcher built, first step served) with lag still
        # outstanding. The first sink row is no such signal: it can come
        # from worker-1 alone (on this fleet worker-1 publishes every
        # report), and worker-0 could drain and exit clean before it.
        t0 = time.perf_counter()
        while not _w0_lag():             # no snapshot yet, or lag 0
            if time.perf_counter() - t0 > timeout:
                note = "worker-0 never served before kill deadline"
                break
            if sup.drained():
                note = "topology drained before worker-0 spooled"
                break
            time.sleep(0.02)
        killed_pid = sup.kill_member("worker-0")
        t_kill = time.perf_counter()
        t_kill_wall = time.time()
        reports_at_kill = _sink_rows()
        snap0 = sup.snapshots().get("worker-0") or {}
        lag_at_kill = (snap0.get("stats") or {}).get("lag")

        # supervisor-observed death + restart (the monitor thread's own
        # detection — nothing here pre-acknowledges the kill)
        detect_s = recovery_s = None
        deaths_seen = 0
        if killed_pid is not None:
            while time.perf_counter() - t_kill < timeout:
                deaths = [e for e in sup.events()
                          if e["event"] == "member_death"
                          and e.get("member") == "worker-0"]
                if deaths:
                    deaths_seen = len(deaths)
                    # event timestamps are wall-clock: diff against the
                    # wall time taken at the kill, same axis
                    detect_s = round(max(0.0,
                                         deaths[0]["t"] - t_kill_wall),
                                     3)
                    break
                if _w0_exited_clean():
                    # the kill raced worker-0's own drain exit: no
                    # death will come, so do not wait out the deadline
                    note = "worker-0 exited clean before the kill landed"
                    break
                time.sleep(0.02)
            # recovery = kill → the RESTARTED worker-0 spooling again
            # (a new pid in its snapshot: matcher rebuilt, serving)
            while deaths_seen and time.perf_counter() - t_kill < timeout:
                doc = sup.snapshots().get("worker-0")
                if doc is not None and doc.get("pid") not in (None,
                                                              killed_pid):
                    recovery_s = round(time.perf_counter() - t_kill, 2)
                    break
                time.sleep(0.05)

        # ---- drain to completion ------------------------------------
        t0 = time.perf_counter()
        while not sup.drained():
            if time.perf_counter() - t0 > timeout:
                note = (note or "") + " drain timed out"
                break
            time.sleep(0.1)
        time.sleep(2 * sup.poll_s)
        sup.poll_once()                  # reap the final exits
        soak_wall = time.perf_counter() - t_soak0

        # ---- observability face + aggregation fidelity --------------
        import urllib.request
        port = http.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/health", timeout=10) as resp:
            health = json.loads(resp.read())
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as resp:
            exposition = resp.read().decode()
        snaps = sup.snapshots()
        merged = aggregate.merge_registry(snaps)
        exports = {m: (doc.get("metrics") or {})
                   for m, doc in snaps.items()}
        fidelity_ok = True
        counters_checked = buckets_checked = 0
        want_counters: dict = {}
        for exp in exports.values():
            for k, v in (exp.get("counters") or {}).items():
                want_counters[k] = want_counters.get(k, 0.0) + float(v)
        for k, v in want_counters.items():
            counters_checked += 1
            if abs(merged._counters.get(k, 0.0) - v) > 1e-9:
                fidelity_ok = False
        want_hist: dict = {}
        for exp in exports.values():
            for k, buckets in (exp.get("hist") or {}).items():
                w = want_hist.setdefault(k, [0] * len(buckets))
                for i, c in enumerate(buckets):
                    w[i] += int(c)
        for k, w in want_hist.items():
            for i, c in enumerate(w):
                buckets_checked += 1
                if merged._hist.get(k, [])[i:i + 1] != [c]:
                    fidelity_ok = False

        # ---- zero-lost accounting (offset granularity) --------------
        reports_by_member = sup.exit_reports()
        covered = [0] * 4
        for rep in reports_by_member.values():
            for p, off in enumerate((rep or {}).get("committed") or ()):
                covered[p] = max(covered[p], int(off))
        lost = sum(max(0, end_offsets[p] - covered[p]) for p in range(4))

        sink = sup.sink.stats()
        sup.stop()

        # ---- stitch the cross-pid trace -----------------------------
        dumps = {"producer": os.path.join(traces_dir,
                                          "ring_producer.json")}
        for name in ("worker-0", "worker-1"):
            dumps[name] = os.path.join(traces_dir, f"ring_{name}.json")
        stitched = stitch.stitch(
            dumps, out_path=os.path.join(workdir, "topology_trace.json"))
        st = stitched["stitched"]
        stitch_ok = bool(st["processes"] >= 2
                         and st["cross_pid_tracks"] >= 1)

        events = sup.events()
        event_counts: dict = {}
        for e in events:
            event_counts[e["event"]] = event_counts.get(e["event"], 0) + 1
        exit_reports = {
            name: (None if rep is None else {
                "reports": rep.get("reports"), "lag": rep.get("lag"),
                "traced_records": rep.get("traced_records"),
                "link_mood": (rep.get("link") or {}).get("mood"),
                "quality_drift_events": (rep.get("quality")
                                         or {}).get("drift_events"),
            }) for name, rep in reports_by_member.items()}
        out = {
            "config": (f"1 supervisor x 2 CPU worker subprocesses, "
                       f"{produced} probes ({stamped} trace-stamped) "
                       f"over a durable records broker, SIGKILL "
                       f"worker-0 mid-soak, tile={ts.name}"),
            "workers": 2,
            "broker_probes": int(produced),
            "stamped_records": int(stamped),
            "soak": {
                "wall_seconds": round(soak_wall, 2),
                "probes_per_sec_wall": round(produced / soak_wall, 1),
                "reports": int(sink["rows"]),
                "posts": int(sink["posts"]),
            },
            "deaths": int(health.get("deaths_total", deaths_seen)),
            "restarts": int(health.get("restarts_total", 0)),
            "reports_at_kill": (None if reports_at_kill is None
                                else int(reports_at_kill)),
            "lag_at_kill": lag_at_kill,
            "detect_seconds": detect_s,
            "recovery_seconds": recovery_s,
            "lost_records": int(lost),
            "zero_lost_ok": bool(lost == 0),
            "aggregation": {
                "members": len(snaps),
                "counters_checked": int(counters_checked),
                "buckets_checked": int(buckets_checked),
                "merged_series": len(merged._hist),
                "fidelity_ok": bool(fidelity_ok and counters_checked),
                "exposition_ok": bool(
                    exposition.startswith("# TYPE")
                    and "rtpu_topo_deaths" in exposition),
            },
            "health": {
                "status": health.get("status"),
                "deaths_total": health.get("deaths_total"),
                "restarts_total": health.get("restarts_total"),
            },
            "event_counts": event_counts,
            "exit_reports": exit_reports,
            # the r19 worker-CLI satellite, asserted in the artifact:
            # every member's exit JSON carried the link-health AND
            # quality counter blocks
            "worker_exit_reports_ok": all(
                rep is not None and "link" in rep and "quality" in rep
                for rep in reports_by_member.values()),
            "stitch": {**st, "ok": stitch_ok},
        }
        # ---- round 23: the elastic-leasing + in-worker chaos arm ----
        # (after the main arm's stop(): one CPU core — two live
        # topologies would time-share it and blur both measurements)
        out["lease"] = _topology_lease_arm(
            workdir, tiles_path, cfg_path, batches, n_pt,
            timeout=min(timeout, 60.0))
        if note:
            out["note"] = note.strip()
        return out
    finally:
        # teardown BEFORE the rmtree: an exception mid-soak must not
        # leave two live worker subprocesses + the monitor thread (and
        # its respawn logic) running over a deleted broker for the rest
        # of the composite. stop() is idempotent — the normal path
        # already stopped.
        if sup is not None:
            sup.stop()
        shutil.rmtree(workdir, ignore_errors=True)


def _backfill_bench(tpu_ok: bool) -> dict:
    """detail.backfill (round 20) — ROADMAP item 4's batch half as a
    measured, journaled artifact: the open-loop spool-replay engine
    (reporter_tpu/backfill) vs the closed-loop streaming worker draining
    the SAME durable columnar spool of the same tiny tile's fleet.
    Self-contained (builds + spools its own tile) so ``--legs backfill``
    fits a short chip call; on a no-chip composite the numbers are
    one-core CPU mechanism validation, never a throughput claim.
    Recorded: both arms' krows/s over the spool wall (each arm warmed
    untimed first — the first dispatch pays jit trace+lower, the r12
    discipline), their ratio ``vs_soak_x`` (the acceptance bar: open ≥
    closed on a CPU capture — the open loop never waits on the host
    between waves), the engine's device-vs-reference aggregate identity
    bit (shadow reference: the same flat_cells binning through np.add.at
    instead of the device scatter), and the k-anonymity harvest
    counts. Round 21 adds the mesh arm: the same engine data-parallel
    over every visible device, with its three identity bits (see the
    mesh-arm comment below)."""
    import shutil
    import tempfile

    from reporter_tpu.backfill import BackfillConfig, BackfillEngine
    from reporter_tpu.config import (CompilerParams, Config, ServiceConfig,
                                     StreamingConfig)
    from reporter_tpu.netgen.synthetic import generate_city
    from reporter_tpu.netgen.traces import synthesize_fleet
    from reporter_tpu.streaming.columnar import ColumnarStreamPipeline
    from reporter_tpu.streaming.durable_columnar import (
        DurableColumnarIngestQueue)
    from reporter_tpu.tiles.compiler import compile_network

    n_veh, n_pt = (96, 240) if tpu_ok else (32, 120)
    nparts = 4
    workdir = tempfile.mkdtemp(prefix="rtpu_backfill_")
    try:
        # short OSMLR segments (the streaming fixtures' compile shape):
        # segment-boundary transitions must be OBSERVABLE within a trace
        # or the spool yields no complete records to aggregate
        net = generate_city("tiny")
        net.name = "bf"
        ts = compile_network(net, CompilerParams(reach_radius=500.0,
                                                 osmlr_max_length=200.0))
        probes = synthesize_fleet(ts, n_veh, num_points=n_pt, seed=11,
                                  gps_sigma=3.0)
        batches, _, _ = _stage_round_batches(ts, probes, n_veh,
                                             steps_per_batch=40)
        broker_dir = os.path.join(workdir, "spool")
        q = DurableColumnarIngestQueue(broker_dir, nparts)
        for b in batches:
            q.append_columns(b)
        ends = [q.end_offset(p) for p in range(nparts)]
        q.close()
        total = int(sum(ends))

        cfg = Config(
            matcher_backend="jax",
            service=ServiceConfig(datastore_url="http://sink.invalid/"),
            streaming=StreamingConfig(num_partitions=nparts))

        # ---- closed-loop arm: the serving worker drains the spool ----
        def _closed_drain() -> dict:
            posts = [0]

            def transport(url, body):
                posts[0] += 1
                return 200

            pipe = ColumnarStreamPipeline(
                ts, cfg,
                queue=DurableColumnarIngestQueue(broker_dir, nparts),
                transport=transport)
            try:
                t0 = time.perf_counter()
                while pipe.queue.lag(pipe.committed) > 0:
                    pipe.step()
                pipe.drain()
                dt = max(time.perf_counter() - t0, 1e-9)
            finally:
                pipe.close()
                pipe.queue.close()
            return {"seconds": round(dt, 3),
                    "krows_per_s": round(total / dt / 1e3, 3),
                    "posts": posts[0]}

        _closed_drain()                       # warm (compile, untimed)
        closed = _closed_drain()

        # ---- open-loop arm: the backfill engine over the same spool --
        bf = BackfillConfig(slice_traces=64, max_inflight=4,
                            poll_records=4096, k_anonymity=2)

        def _open_run(shadow: bool):
            eng = BackfillEngine(ts, cfg, bf)
            if shadow:
                eng.enable_shadow_reference()
            return eng, eng.run(broker_dir)

        try:
            _open_run(False)                  # warm (compile, untimed)
            eng, ostats = _open_run(True)
        except RuntimeError as exc:           # no native walker: the
            return {"records": total,         # parity suites scream, the
                    "closed_loop": closed,    # leg degrades to a note
                    "note": f"open loop skipped: {exc}"}

        vs = round(ostats["krows_per_s"] / max(closed["krows_per_s"],
                                               1e-9), 2)

        # ---- mesh arm (round 21): same spool, data-parallel engine ---
        # Shards every rung slice over ALL devices through the SAME
        # undecorated wire bodies (dp_e2e.mesh_wire_fn) and keeps a
        # per-device partial aggregate grid, merged bucket-wise at the
        # one harvest sync. Three identity bits ride the capture: the
        # mesh arm's own device-vs-reference shadow, mesh-vs-single
        # aggregate grid equality, and prepared-seam wire-byte identity
        # (one probe slice through both matchers; the mesh harvest is
        # sliced to the real row count, the single arm's bytes must be
        # its prefix). Skipped with a note on a 1-device composite (one
        # chip); no-chip composites always have the 8-device virtual
        # host platform forced in main(). The four-chip run of this
        # path is `chip_smoke.py --chips 4`.
        import jax
        import numpy as np

        ndev = len(jax.devices())
        if ndev >= 2:
            from reporter_tpu.parallel.mesh import make_mesh

            mesh = make_mesh(dp=ndev)

            def _mesh_run(shadow: bool):
                meng = BackfillEngine(ts, cfg, bf, mesh=mesh)
                if shadow:
                    meng.enable_shadow_reference()
                return meng, meng.run(broker_dir)

            _mesh_run(False)                  # warm (compile, untimed)
            meng, mstats = _mesh_run(True)

            probe, _, _ = eng._wave_traces(batches[0])
            padded = eng._pad_to_rung(probe[:32])
            w1, sl1 = eng.matcher.plan_submit(padded)
            w2, sl2 = meng.matcher.plan_submit(padded)
            wire_ok = len(sl1) == len(sl2)
            for (b1, ws1), (b2, ws2) in zip(sl1, sl2):
                a1 = np.asarray(eng.matcher.submit_prepared(
                    eng.matcher.prepare_submit_slice(padded, w1, b1, ws1)))
                a2 = np.asarray(meng.matcher.submit_prepared(
                    meng.matcher.prepare_submit_slice(padded, w2, b2, ws2)))
                wire_ok = wire_ok and bool(
                    np.array_equal(a1, a2[:a1.shape[0]]))

            mesh_doc = {
                "devices": ndev,
                "krows_per_s": mstats["krows_per_s"],
                "seconds": mstats["seconds"],
                "vs_single_x": round(
                    mstats["krows_per_s"]
                    / max(ostats["krows_per_s"], 1e-9), 2),
                "agg_identical": meng.shadow_identical(),
                "agg_equal_single": bool(
                    np.array_equal(eng.hist.snapshot(),
                                   meng.hist.snapshot())
                    and np.array_equal(eng.qhist.snapshot(),
                                       meng.qhist.snapshot())),
                "wire_bytes_identical": wire_ok,
            }
        else:
            mesh_doc = {"devices": ndev,
                        "note": "single device - mesh arm skipped"}

        return {
            "config": (f"{n_veh} vehicles x {n_pt} pts = {total} records "
                       f"over a {nparts}-partition durable columnar "
                       f"spool, both arms warmed, tile={ts.name}"),
            "records": total,
            "open_loop": {
                "krows_per_s": ostats["krows_per_s"],
                "seconds": ostats["seconds"],
                "waves": ostats["waves"],
                "chunks": ostats["chunks"],
                "reports": ostats["reports"],
                "replay_tax_records": ostats["replay_tax_records"],
                "kept_segments": ostats["kept_segments"],
                "kanon_dropped": ostats["kanon_dropped"],
                "agg_identical": eng.shadow_identical(),
            },
            "closed_loop": closed,
            "mesh": mesh_doc,
            "vs_soak_x": vs,
            "open_ge_closed_ok": bool(
                ostats["krows_per_s"] >= closed["krows_per_s"]),
        }
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def _provenance(tpu_ok: bool) -> dict:
    """Self-describing capture stamp (ISSUE-4 satellite): git sha + an
    optional round label, so a stale BENCH_DETAIL.json can never again
    masquerade as the current round's numbers (the r5-run8 confusion)."""
    import subprocess

    sha = None
    try:
        r = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        sha = r.stdout.strip() or None
    except Exception:
        pass
    return {
        "git_sha": sha,
        "round": os.environ.get("REPORTER_BENCH_ROUND"),
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "device_ok": bool(tpu_ok),
    }


def _cached_mode_tileset():
    """sf with mixed mode access (8% bike-only, 5% foot-only ways),
    compiled as the BICYCLE subgraph — the non-auto audit tile
    (VERDICT r3 #7)."""
    from reporter_tpu.config import CompilerParams
    from reporter_tpu.netgen.synthetic import assign_mode_access, generate_city
    from reporter_tpu.tiles.compiler import compile_network
    from reporter_tpu.tiles.tileset import TileSet

    t0 = time.perf_counter()
    net = assign_mode_access(generate_city("sf"), seed=21)
    fp = net.fingerprint()
    path = _repo_path(f".bench_tiles_sfm-bicycle_v4_{fp & 0xFFFFFFFF:08x}.npz")
    if os.path.exists(path):
        try:
            return TileSet.load(path), {
                "source": "npz-cache",
                "seconds": round(time.perf_counter() - t0, 2)}
        except Exception:
            pass
    ts = compile_network(net, CompilerParams(), mode="bicycle")
    ts.save(path)
    return ts, {"source": "compiled",
                "seconds": round(time.perf_counter() - t0, 2)}


def _link_rtt() -> float:
    """Median of 7 tiny dispatch+readback round trips, in seconds (the
    link floor; re-probed before each mood window — VERDICT r3 weak #4)."""
    import jax.numpy as jnp
    import numpy as np

    tiny = jnp.zeros(8, jnp.float32)
    np.asarray(tiny + 1)                        # warm the tiny executable
    rtts = sorted(_time_best(lambda: np.asarray(tiny + 1), repeats=1)
                  for _ in range(7))
    return rtts[len(rtts) // 2]


# ---------------------------------------------------------------------------
# Round 24: the SLO burn-rate plane (ISSUE 20) — chaos-proven alerting
# over the metrics registry. Fully synthetic (no tiles, no chip, no
# link): an injected-clock serving driver feeds the REAL metric names
# the committed specs read, two fault classes each fire their MATCHING
# alert with exactly one post-mortem per transition, the clean arm
# fires none, and the merge-commute property (topology burn over
# merge_exports == per-worker sum) is re-proven on the driver's own
# exports every composite.


def _slo_bench() -> dict:
    """Self-contained ``detail.slo`` leg (~seconds; `--legs slo` fits
    any window). Mechanism validation, never a throughput claim."""
    import shutil
    import tempfile

    from reporter_tpu import faults
    from reporter_tpu.obs import slo as obs_slo
    from reporter_tpu.utils import tracing
    from reporter_tpu.utils.eventlog import EventLog
    from reporter_tpu.utils.metrics import (MetricsRegistry, delta_exports,
                                            merge_exports)

    t0 = time.perf_counter()
    reg = MetricsRegistry()
    clock = {"now": 0.0}
    workdir = tempfile.mkdtemp(prefix="rtpu_slo_bench_")
    ledger = EventLog(os.path.join(workdir, "alerts.jsonl"))
    # scale 0.1 ⇒ fast windows 6 s / 30 s of VIRTUAL time (the injected
    # clock steps 1 s per iteration — transitions are deterministic, so
    # this leg's pass/fail can never ride link mood)
    ev = obs_slo.SloEvaluator(reg, ledger=ledger, clock=lambda: clock["now"],
                              scale=0.1, min_tick_s=0.0,
                              enabled_override=True)

    def drive(n: int) -> None:
        """n virtual seconds of serving traffic against the REAL metric
        names the committed specs read. The publish and dispatch fault
        sites are consulted per event, so an installed FaultPlan turns
        this into the matching outage."""
        for _ in range(n):
            clock["now"] += 1.0
            for _ in range(10):
                reg.count("http_requests")
                reg.count("publish_attempts")
                if faults.check("publish") is not None:
                    reg.count("publish_failures")
                slow = faults.check("dispatch") is not None
                reg.observe("request_seconds", 1.0 if slow else 0.01)
            ev.tick()

    tr = tracing.tracer()
    prev_tr = (tr.enabled, tr.dump_dir, tr.capacity, tr.max_dumps)
    prev_written = tr.dumps_written
    try:
        tr.configure(enabled=True, dump_dir=workdir, max_dumps=8)
        # clean arm: healthy traffic through every window — zero alerts
        drive(60)
        clean_alerts = ev.alerts_total
        clean_active = list(ev.status()["active"])
        # chaos arm A: publish outage (open-ended fail) ⇒ the publish
        # ratio SLO must fire; arm B after recovery: dispatch slowness ⇒
        # the latency SLO must fire. Distinct fault classes, distinct
        # matching specs.
        with faults.use(faults.FaultPlan.parse("publish:fail@0-")):
            drive(40)
        publish_fired = "publish" in ev.status()["active"]
        drive(80)                                    # recovery: resolves
        publish_resolved = "publish" not in ev.status()["active"]
        # check(), not fire(): the driver maps the rule to a slow
        # observation itself, so the nominal hang duration never sleeps
        with faults.use(faults.FaultPlan.parse("dispatch:hang(0.5)@0-")):
            drive(40)
        latency_fired = "latency" in ev.status()["active"]
        drive(80)
        latency_resolved = "latency" not in ev.status()["active"]
        chaos_alerts = ev.alerts_total - clean_alerts
        dumps = [f for f in sorted(os.listdir(workdir))
                 if "slo_alert" in f]
        entries = ledger.read()
    finally:
        tr.configure(enabled=prev_tr[0], dump_dir=prev_tr[1],
                     capacity=prev_tr[2], max_dumps=prev_tr[3])
        tr.dumps_written = prev_written
        shutil.rmtree(workdir, ignore_errors=True)

    # one bounded post-mortem per FIRE transition (r18 discipline: a
    # budget that stays blown dumps once, not once per tick)
    fires = [e for e in entries if e["event"] == "fire"]
    resolves = [e for e in entries if e["event"] == "resolve"]
    one_pm_per_fire = len(dumps) == len(fires) == 2
    # zero lost ledger entries: every transition the evaluator counted
    # is durably on disk (fire+resolve per chaos class)
    ledger_ok = (len(fires) == chaos_alerts
                 and len(resolves) == chaos_alerts
                 and sorted(e["slo"] for e in fires)
                 == ["latency", "publish"])

    # topology-wide burn = per-worker sum BY CONSTRUCTION: delta of the
    # merged exports equals the merge of per-worker deltas, counters and
    # buckets both (the r19 merge grid is what makes burn linear)
    w1, w2 = MetricsRegistry(), MetricsRegistry()
    for i in range(50):
        w1.count("http_requests"), w2.count("http_requests", 2)
        if i % 9 == 0:
            w1.count("http_errors")
        w2.observe("request_seconds", 0.02 * (i % 7 + 1))
    b1, b2 = w1.export(), w2.export()
    for i in range(30):
        w1.observe("request_seconds", 0.3)
        w2.count("http_errors", 3)
    n1, n2 = w1.export(), w2.export()
    lhs = delta_exports(merge_exports({"w1": n1, "w2": n2}).export(),
                        merge_exports({"w1": b1, "w2": b2}).export())
    rhs = merge_exports({"w1": delta_exports(n1, b1),
                         "w2": delta_exports(n2, b2)}).export()
    merge_commute = (lhs["counters"] == rhs["counters"]
                     and lhs["hist"] == rhs["hist"])

    tp_match = bool(publish_fired and publish_resolved
                    and latency_fired and latency_resolved)
    return {
        "config": ("synthetic injected-clock serving driver, scale=0.1, "
                   "real spec metric names (no chip, no link — "
                   "mechanism validation)"),
        "specs": [s.name for s in ev.specs],
        "ticks": ev.ticks,
        "clean_alerts": clean_alerts,
        "clean_active": clean_active,
        "chaos_alerts": chaos_alerts,
        "publish_fired": publish_fired,
        "publish_resolved": publish_resolved,
        "latency_fired": latency_fired,
        "latency_resolved": latency_resolved,
        "tp_match": tp_match,
        "post_mortems": len(dumps),
        "one_pm_per_fire": one_pm_per_fire,
        "ledger_entries": len(entries),
        "ledger_ok": ledger_ok,
        "merge_commute": merge_commute,
        "seconds": round(time.perf_counter() - t0, 2),
    }


# ---------------------------------------------------------------------------
# Round 15: the capture journal + link-health + regression sentinel — the
# layer that turns "the run was cut" from a zeroed 10-13 min run
# into a journaled, attributable, resumable artifact (ROADMAP open item
# 1's first half; the r13 MXU acceptance bar is blocked on exactly this).

_JOURNAL_NAME = "bench_journal.jsonl"

# the composite's leg DAG in run order. Self-contained legs build their
# own inputs (fleet always; sweep_ab on the no-chip validation path) so
# `--legs sweep_ab` / `--legs fleet` fits a short chip call without
# paying the primary tile+fleet setup.
_ALL_LEGS = (
    "primary", "service", "oracle", "fresh_rotation",
    "metro", "restricted", "xl", "organic", "organic_xl", "bicycle",
    "streaming", "streaming_capacity", "streaming_soak",
    "latency_attribution", "streaming_overload", "chaos",
    "device_compute", "sweep_ab", "autotune", "quality", "window2",
    "prepare_bench", "fleet", "topology", "backfill", "slo",
)
_SELF_CONTAINED_LEGS = {"fleet", "topology", "backfill",
                        "slo"}                             # + sweep_ab /
#                                         autotune /
#                                         quality when no chip is in
#                                         play (their *_cpu_validate
#                                         stand-ins compile their own
#                                         tiny tiles); topology builds
#                                         its own tile AND pins its
#                                         worker subprocesses to CPU on
#                                         every composite


class BenchJournal:
    """Crash-safe per-leg capture journal (``bench_journal.jsonl``).

    Every completed leg is appended as one JSON line — result +
    provenance (wall time, capture timestamp, the contemporaneous
    link-health window) — via the r9 checkpoint discipline (full
    tmp+fsync+rename rewrite: a reader never sees a torn file this
    writer produced, and a crash mid-append leaves the previous journal
    intact). ``--resume`` reloads the journal and serves journaled legs
    from it instead of re-measuring, so a run cut mid-way keeps
    everything already captured; a torn/corrupt TAIL line (a foreign
    writer, a half-synced disk) is truncated at reopen and counted,
    never fatal. Resume is refused — journal restarted, noted — when
    the header's config/git-sha fingerprint doesn't match this run:
    journaled numbers from a different workload or code state must not
    leak into a composite claiming this one.
    """

    def __init__(self, path: str, meta: dict, resume: bool = False,
                 only: "set[str] | None" = None):
        self.path = path
        self.meta = dict(meta)
        self.only = set(only) if only is not None else None
        self.entries: "dict[str, dict]" = {}
        self.order: "list[str]" = []
        self.reused: "set[str]" = set()
        self.truncated_lines = 0
        self.resume_rejected: "str | None" = None
        if resume:
            self._load()
        self._write_all()

    # ---- persistence -----------------------------------------------------

    def _load(self) -> None:
        try:
            with open(self.path) as f:
                lines = f.read().splitlines()
        except OSError:
            return
        header = None
        for i, ln in enumerate(lines):
            if not ln.strip():
                continue
            try:
                doc = json.loads(ln)
            except json.JSONDecodeError:
                # torn tail: keep everything before it, drop the rest
                self.truncated_lines = len(lines) - i
                break
            if i == 0 or header is None:
                if doc.get("journal") != "bench":
                    self.resume_rejected = "no journal header"
                    return
                header = doc
                continue
            if isinstance(doc, dict) and "leg" in doc:
                self.entries[doc["leg"]] = doc
                self.order.append(doc["leg"])
        if header is None:
            self.resume_rejected = "empty journal"
            self.entries.clear()
            self.order.clear()
            return
        for key in ("config", "git_sha"):
            if header.get(key) != self.meta.get(key):
                self.resume_rejected = (
                    f"{key} changed ({header.get(key)!r} -> "
                    f"{self.meta.get(key)!r}) — journaled legs are from "
                    "a different workload/code state")
                self.entries.clear()
                self.order.clear()
                return
        self.reused = set(self.entries)

    def _write_all(self) -> None:
        # r9 checkpoint discipline: .tmp + fsync + atomic rename — a
        # crash between any two syscalls leaves either the old journal
        # or the new one, never a torn line of this writer's making
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            f.write(json.dumps({"journal": "bench", **self.meta}) + "\n")
            for name in self.order:
                f.write(json.dumps(self.entries[name]) + "\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.path)

    # ---- leg execution ---------------------------------------------------

    def wants(self, name: str) -> bool:
        return self.only is None or name in self.only

    def leg(self, name: str, fn):
        """Run (or replay) one journaled leg. Returns the leg's result —
        from the journal when resuming and the leg is already captured,
        None when a ``--legs`` subset excludes it."""
        if not self.wants(name):
            return None
        if name in self.entries:
            return self.entries[name].get("result")
        from reporter_tpu.utils import linkhealth

        s = linkhealth.sampler() if linkhealth.enabled() else None
        t_link0 = s.clock() if s is not None else None
        t0 = time.perf_counter()
        result = fn()
        entry = {
            "leg": name,
            "seconds": round(time.perf_counter() - t0, 2),
            "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                         time.gmtime()),
            "link": (s.window(since=t_link0) if s is not None
                     else {"rtt_ms": None, "mbps": None, "mood": None,
                           "samples": 0}),
            "result": result,
        }
        self.entries[name] = entry
        self.order.append(name)
        self._write_all()
        return result

    def seconds(self, name: str) -> "float | None":
        e = self.entries.get(name)
        return None if e is None else e.get("seconds")

    def to_json(self) -> dict:
        """The composite's journal block: which legs were measured this
        run vs replayed, plus the per-leg link windows — the capture's
        own provenance for every number in it."""
        return {
            "path": os.path.basename(self.path),
            "legs": {n: {"seconds": e.get("seconds"),
                         "captured_at": e.get("captured_at"),
                         "link": e.get("link"),
                         "resumed": n in self.reused}
                     for n, e in self.entries.items()},
            "resumed_legs": sorted(self.reused),
            "truncated_lines": self.truncated_lines,
            **({"resume_rejected": self.resume_rejected}
               if self.resume_rejected else {}),
        }


def _current_round() -> "int | None":
    """This build's round number: REPORTER_BENCH_ROUND when the driver
    sets it (e.g. "r15"), else derived from CHANGES.md (one ``- rN``
    line per landed round; the next capture is N+1)."""
    import re as _re

    tag = os.environ.get("REPORTER_BENCH_ROUND", "")
    m = _re.search(r"(\d+)", tag)
    if m:
        return int(m.group(1))
    try:
        with open(_repo_path("CHANGES.md")) as f:
            rounds = [int(x) for x in _re.findall(r"^- r(\d+)", f.read(),
                                                  _re.MULTILINE)]
        return max(rounds) + 1 if rounds else None
    except OSError:
        return None


def _staleness_banner() -> "str | None":
    """Loud when the committed chip capture is >=2 rounds behind the
    code being benched (the r5-run8 capture sat silently stale for 8
    rounds while r8/r12/r13 perf work shipped with zero silicon
    numbers). Printed to stderr AND recorded in the journal header, so
    both the operator and the artifact know the baseline is old."""
    import re as _re

    cur = _current_round()
    if cur is None:
        return None
    try:
        with open(_repo_path("BENCH_DETAIL.json")) as f:
            committed = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    tag = (committed.get("provenance") or {}).get("round") or ""
    m = _re.search(r"(\d+)", str(tag))
    if not m:
        return None
    behind = cur - int(m.group(1))
    if behind < 2:
        return None
    return (f"STALE CHIP CAPTURE: committed BENCH_DETAIL.json is "
            f"round {m.group(1)} ({tag!r}), current round is r{cur} — "
            f"{behind} rounds behind. Every perf feature since has no "
            f"silicon numbers; land a chip capture (or use --legs for "
            f"a short-window partial) before trusting cross-round "
            f"comparisons.")


def _bench_delta_tail(doc: dict, against_path: str) -> "dict | None":
    """The regression sentinel, run against the committed capture of
    the SAME flavor (chip runs diff the chip capture, CPU runs the CPU
    one) BEFORE this run overwrites it. Returns the bounded embed (top
    regressions + counters) or None when there is nothing to compare."""
    from reporter_tpu.analysis import bench_delta

    try:
        with open(against_path) as f:
            old = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    delta = bench_delta.compare(old, doc)
    out = bench_delta.compact(delta)
    out["against"] = os.path.basename(against_path)
    return out


def _parse_args(argv: "list[str]") -> "tuple":
    """(n_traces|None, city, resume, legs|None). Positional args keep
    the historical manual form (``bench.py 16000 bayarea``); --resume /
    --legs are the round-15 journal controls, with env twins
    (RTPU_BENCH_RESUME / RTPU_BENCH_LEGS) so the driver can steer a run
    it can't pass flags to."""
    import argparse

    from reporter_tpu.utils.tracing import env_flag

    ap = argparse.ArgumentParser(
        description="reporter_tpu composite bench (see module docstring)")
    ap.add_argument("n_traces", nargs="?", type=int, default=None)
    ap.add_argument("city", nargs="?", default="sf")
    ap.add_argument("--resume", action="store_true",
                    help="serve already-journaled legs from "
                         f"{_JOURNAL_NAME} instead of re-measuring")
    ap.add_argument("--legs", default=None,
                    help="comma-separated leg subset to run (names: "
                         + ",".join(_ALL_LEGS) + ")")
    args = ap.parse_args(argv)
    resume = args.resume or env_flag(os.environ.get("RTPU_BENCH_RESUME"))
    legs_raw = args.legs or os.environ.get("RTPU_BENCH_LEGS") or None
    legs = None
    if legs_raw:
        legs = {x.strip() for x in legs_raw.split(",") if x.strip()}
        unknown = legs - set(_ALL_LEGS)
        if unknown:
            ap.error(f"unknown legs {sorted(unknown)}; "
                     f"known: {', '.join(_ALL_LEGS)}")
    return args.n_traces, args.city, resume, legs


def main() -> None:
    t_setup = time.perf_counter()
    split: dict = {}

    # Pin the PROCESS-GLOBAL shadow auditor's default sampling off for
    # the composite (r18, the tests/conftest.py discipline): one
    # default-schedule exact-oracle audit landing inside a measured
    # window starves the one-core closed loop for seconds (observed:
    # the legacy service arm collapsing 392→70 req/s when an audit hit
    # its round). The audit subsystem is measured under controlled
    # conditions by detail.quality's explicit auditors — which override
    # this via constructor args — not by randomly poisoning other legs.
    # An operator-set value wins (A/B runs can re-enable on purpose).
    os.environ.setdefault("RTPU_QUALITY_AUDIT_RATE", "0")

    n_arg, city, resume, legs_filter = _parse_args(sys.argv[1:])
    manual = n_arg is not None

    t0 = time.perf_counter()
    # The bench measures the chip and nothing else: without one it exits
    # non-zero. REPORTER_BENCH_FORCE_CPU=1 is the only CPU run — a
    # labelled sanity pass of the control flow whose numbers are never
    # device metrics.
    from reporter_tpu.utils.tracing import env_flag

    forced_cpu = env_flag(os.environ.get("REPORTER_BENCH_FORCE_CPU"))
    if forced_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        # 8-device VIRTUAL mesh (round 21): a no-chip composite still
        # exercises detail.backfill's mesh arm (data-parallel spool
        # reprocessing + sharded aggregate) — the flag must land BEFORE
        # the first jax import or the host platform stays single-device.
        # Unsharded legs are unaffected: their dispatches ride device 0.
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    if forced_cpu:
        jax.config.update("jax_platforms", "cpu")
    tpu_ok = jax.devices()[0].platform == "tpu"
    split["device_probe_s"] = round(time.perf_counter() - t0, 1)
    if not tpu_ok and not forced_cpu:
        print(f"bench.py: no TPU (jax.devices()[0] is "
              f"{jax.devices()[0].platform!r}); set "
              "REPORTER_BENCH_FORCE_CPU=1 for a labelled CPU sanity run",
              file=sys.stderr)
        sys.exit(2)

    from reporter_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()

    import numpy as np

    from reporter_tpu.config import Config
    from reporter_tpu.matcher.api import SegmentMatcher
    from reporter_tpu.utils import linkhealth

    # link-health sampler (round 15): probes RTT + bandwidth at low duty
    # for the WHOLE composite; every journaled leg gets stamped with its
    # contemporaneous window. Bench tightens the period (finer per-leg
    # attribution) unless the operator pinned it.
    link_enabled = linkhealth.enabled()
    if link_enabled:
        _ls = linkhealth.sampler()
        if "RTPU_LINK_PROBE_PERIOD_S" not in os.environ:
            _ls.period_s = 30.0
        _ls.start()
        _ls.sample_once()       # every leg window has >= 1 observation

    n_traces = n_arg if manual else 16000
    if not tpu_ok:
        n_traces = min(n_traces, 128)   # keep the degraded-mode run short:
                                        # even the grid gather path (auto's
                                        # CPU pick, ~60k probes/s) plus the
                                        # oracle pass should finish in well
                                        # under a minute on one core
    n_points = 120
    n_cpu = min(250, n_traces)          # sf leg of the ≥500-trace audit
    full_run = (not manual) and tpu_ok

    prov = _provenance(tpu_ok)
    banner = _staleness_banner()
    if banner:
        print("=" * 72 + f"\n{banner}\n" + "=" * 72, file=sys.stderr)

    requested = set(legs_filter) if legs_filter is not None \
        else set(_ALL_LEGS)
    self_contained = set(_SELF_CONTAINED_LEGS) | (
        set() if tpu_ok else {"sweep_ab", "autotune", "quality"})
    needs_primary = bool(requested - self_contained)

    cur_round = _current_round()
    journal = BenchJournal(
        _repo_path(_JOURNAL_NAME),
        meta={"config": {"n_traces": n_traces, "city": city,
                         "tpu_ok": bool(tpu_ok), "manual": bool(manual)},
              "git_sha": prov.get("git_sha"),
              "round": (prov.get("round")
                        or (f"r{cur_round}" if cur_round else None)),
              "staleness_banner": banner,
              "started_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                          time.gmtime())},
        resume=resume, only=legs_filter)

    # ---- setup (always re-run: disk-cached tiles/fleets + one compile
    # warm; the journal resumes MEASUREMENTS, not staging) ---------------
    ts = traces = true_edges = jax_matcher = None
    tile_info = {"source": None}
    link_rtt = 0.0
    if needs_primary:
        t0 = time.perf_counter()
        ts, tile_info = _cached_tileset(city)
        split["tile_s"] = round(time.perf_counter() - t0, 1)
        t0 = time.perf_counter()
        traces, true_edges = _cached_fleet(ts, n_traces, n_points)
        split["fleet_s"] = round(time.perf_counter() - t0, 1)
        jax_matcher = SegmentMatcher(ts, Config(matcher_backend="jax"))
        jax_matcher.match_many(traces)  # compile + stage HBM (untimed)
        link_rtt = _link_rtt()

    # window-2 re-measure set: (matcher, traces, window2 repeats) per
    # tile MEASURED FRESH this run — a journal-resumed tile keeps its
    # window-1 numbers (its matcher was never rebuilt)
    live: dict = {}

    # ---- primary tile (BASELINE config 2) ------------------------------
    def _leg_primary():
        dt, dt_dec = _timed_pair(jax_matcher, traces, repeats=5)
        probes = sum(len(t.xy) for t in traces)
        # p50 single-trace match latency (the north star's second
        # metric; often host-device RTT-bound, not compute-bound).
        # Untimed B=1 warmup first.
        jax_matcher.match_many(traces[:1])
        lat = sorted(
            _time_best(lambda: jax_matcher.match_many(traces[:1]),
                       repeats=1) for _ in range(7))
        p50_mo = _matcher_only_latency(jax_matcher, traces[0], link_rtt)
        return {"jax_pps": probes / dt, "decode_pps": probes / dt_dec,
                "batch_seconds": round(dt, 3),
                "p50_latency_s": lat[len(lat) // 2],
                "p50_matcher_only_s": p50_mo,
                "link_rtt_s": link_rtt}

    primary = (journal.leg("primary", _leg_primary) or {}
               if needs_primary else {})
    split["primary_measure_s"] = journal.seconds("primary")
    jax_pps = primary.get("jax_pps")
    decode_pps = primary.get("decode_pps")
    if needs_primary:
        live["sf"] = (jax_matcher, traces, 3)

    # ---- serving face (round 7 A/B: scheduler vs queue-and-combine) ----
    def _leg_service():
        from reporter_tpu.config import ServiceConfig as _SvcCfg
        from reporter_tpu.service.app import ReporterApp

        svc_apps = {
            "scheduler": ReporterApp(ts, Config(matcher_backend="jax")),
            "legacy": ReporterApp(ts, Config(
                matcher_backend="jax",
                service=_SvcCfg(batching="combine"))),
        }
        # one level past 256 (round-8 satellite / VERDICT weak #6)
        curve = _service_saturation_curve(
            svc_apps, ts, traces,
            levels=(16, 64, 256, 512) if tpu_ok else (16, 64, 256))
        # degraded (CPU) runs keep the paced sweep short: one core
        # serves both the submitters and the matcher
        open_loop = _service_open_loop(
            svc_apps, ts, traces,
            rates=(100, 250, 500, 1000) if tpu_ok else (50, 100))
        for _app in svc_apps.values():
            _app.close()        # drain schedulers; frees the executor
        top = curve[-1]

        def _spread_pct(draws: "list | None") -> "float | None":
            # (max-min)/max of the per-round rates — a one-number
            # bimodality flag (≳50% = the r18 class; ≲15% = the normal
            # same-mood jitter band)
            if not draws or max(draws) <= 0:
                return None
            return round(100.0 * (max(draws) - min(draws)) / max(draws),
                         1)

        ab = {
            "clients": top["clients"],
            # client-THREAD count recorded explicitly (round 19): the
            # closed loop runs one thread per client on however many
            # cores the host has — 128 threads/core is the condition
            # the per-draw spread below must be read against
            "client_threads": top["clients"],
            "scheduler_rps": top["scheduler"]["req_per_sec"],
            "legacy_rps": top["legacy"]["req_per_sec"],
            "scheduler_draw_rps": top["scheduler"].get("round_rps"),
            "legacy_draw_rps": top["legacy"].get("round_rps"),
            "scheduler_draw_spread_pct": _spread_pct(
                top["scheduler"].get("round_rps")),
            "legacy_draw_spread_pct": _spread_pct(
                top["legacy"].get("round_rps")),
            "speedup": (round(top["scheduler"]["req_per_sec"]
                              / top["legacy"]["req_per_sec"], 3)
                        if top["scheduler"]["req_per_sec"]
                        and top["legacy"]["req_per_sec"] else None),
            "inflight_ge2_dispatches": sum(
                v for k, v in top["scheduler"].get("inflight_hist",
                                                   {}).items()
                if int(k) >= 2),
            "errors": (top["scheduler"]["errors"]
                       + top["legacy"]["errors"]),
        }
        return {"service_curve": curve, "service_open_loop": open_loop,
                "service_ab": ab,
                "service_overload_boundary":
                    _service_overload_boundary(curve)}

    service = (journal.leg("service", _leg_service) or {}
               if needs_primary else {})
    split["service_s"] = journal.seconds("service")

    # ---- fidelity audit leg 1 (BASELINE north star) + truth rates ------
    def _leg_oracle():
        disagreement, cpu_pps, _, fsrc = _oracle_audit(
            ts, jax_matcher, traces, n_cpu)
        truth = _truth_rates(ts, jax_matcher, traces, true_edges,
                             n=min(2000, n_traces))
        return {"disagreement": round(disagreement, 4),
                "cpu_pps": cpu_pps, "source": fsrc, "truth": truth,
                "near_tie": _near_tie_stats(jax_matcher, traces),
                "audit_entry": {"traces": n_cpu,
                                "disagreement": round(disagreement, 4),
                                "fidelity_source": fsrc}}

    oracle = (journal.leg("oracle", _leg_oracle) or {}
              if needs_primary else {})
    split["oracle_primary_s"] = journal.seconds("oracle")
    cpu_pps = oracle.get("cpu_pps")
    audit: dict = {}
    if oracle:
        audit[ts.name] = oracle["audit_entry"]

    # ---- guaranteed-fresh rotation leg (VERDICT r4 weak #2/next #7) ----
    def _leg_fresh():
        rotf = _repo_path(".bench_fresh_rotation")
        try:
            with open(rotf) as f:
                rot_k = int(f.read().strip() or 0)
        except (OSError, ValueError):
            rot_k = 0
        with open(rotf, "w") as f:
            f.write(str(rot_k + 1))
        n_fresh = min(25, max(0, len(traces) - n_cpu))
        if not n_fresh:     # tiny fallback fleets: the audited set
            return {}       # covers it all
        span = max(1, len(traces) - n_cpu - n_fresh + 1)
        lo = n_cpu + (rot_k * n_fresh) % span
        fr_dis, _, fr_n, fr_src = _oracle_audit(
            ts, jax_matcher, traces[lo:lo + n_fresh], n_fresh,
            force_fresh=True)
        return {"audit_key": f"{ts.name}-fresh-rot",
                "audit_entry": {
                    "traces": fr_n, "disagreement": round(fr_dis, 4),
                    "fidelity_source": fr_src, "rotation_index": rot_k,
                    "trace_window": [lo, lo + n_fresh]}}

    fresh = (journal.leg("fresh_rotation", _leg_fresh) or {}
             if needs_primary else {})
    split["fresh_rotation_s"] = journal.seconds("fresh_rotation")
    if fresh.get("audit_key"):
        audit[fresh["audit_key"]] = fresh["audit_entry"]

    detail = {
        "config": (f"{n_traces}x{n_points}pt traces, "
                   f"tile={ts.name if ts is not None else city}"),
        "headline_tile": ts.name if ts is not None else city,
        "device": (str(jax.devices()[0]).split(":")[0] if tpu_ok
                   else "CPU (forced by REPORTER_BENCH_FORCE_CPU)"),
    }
    if primary:
        p50_latency = primary["p50_latency_s"]
        p50_mo = primary.get("p50_matcher_only_s")
        rtt_s = primary.get("link_rtt_s") or link_rtt
        detail.update({
            "decode_only_probes_per_sec": round(decode_pps, 1),
            "e2e_over_decode": round(jax_pps / decode_pps, 3),
            "p50_single_trace_latency_ms": round(p50_latency * 1e3, 2),
            "p50_matcher_only_ms": (round(p50_mo * 1e3, 3)
                                    if p50_mo is not None else None),
            "link_rtt_ms": round(rtt_s * 1e3, 2),
            "latency_note": (
                "CPU sanity run — no device in play" if not tpu_ok
                else "single-trace p50 is host-device RTT-bound"
                if p50_latency < 4 * rtt_s + 5e-3
                else "single-trace p50 is compute-bound"),
            "batch_seconds": primary["batch_seconds"],
        })
    if service:
        curve = service["service_curve"]
        lvl16 = curve[0]["scheduler"]
        n_conc = curve[0]["clients"]
        conc_errors = [e for lvl in curve
                       for arm in ("scheduler", "legacy")
                       for e in lvl[arm].get("error_samples", [])]
        detail.update({
            f"concurrent{n_conc}_combined_p50_ms": lvl16["p50_ms"],
            f"concurrent{n_conc}_requests_per_sec": lvl16["req_per_sec"],
            "service_curve": curve,
            "service_ab": service["service_ab"],
            "service_open_loop": service["service_open_loop"],
            "service_overload_boundary":
                service["service_overload_boundary"],
            **({"concurrent_errors": conc_errors[:4]}
               if conc_errors else {}),
        })
    if oracle:
        detail.update({
            "cpu_reference_probes_per_sec": round(cpu_pps, 1),
            "oracle_sample_traces": n_cpu,
            "segment_id_disagreement_vs_cpu_ref": oracle["disagreement"],
            "near_tie": oracle["near_tie"],
            "ground_truth": oracle["truth"],
        })
    if ts is not None:
        detail["tile_source"] = tile_info["source"]
        detail["tile_stats"] = ts.stats

    # ---- extra tiles (full chip composites only) -----------------------
    if full_run:
        # -- metro scale (BASELINE config 3: bayarea tables in HBM) ------
        def _leg_metro():
            mts, mtile_info = _cached_tileset("bayarea")
            mtraces, _ = _cached_fleet(mts, n_traces, n_points)
            mm, m_pps, m_decode, _ = _throughput(mts, mtraces, repeats=3)
            m_dis, _, m_n, m_src = _oracle_audit(mts, mm, mtraces, 100)
            live["bayarea"] = (mm, mtraces, 5)
            return {
                "audit_key": mts.name,
                "audit_entry": {"traces": m_n,
                                "disagreement": round(m_dis, 4),
                                "fidelity_source": m_src},
                "block": {
                    "config": (f"{len(mtraces)}x{n_points}pt traces, "
                               f"tile={mts.name}"),
                    "probes_per_sec_e2e": round(m_pps, 1),
                    "decode_only_probes_per_sec": round(m_decode, 1),
                    "hbm_tile_bytes": int(mts.hbm_bytes()),
                    # round-8 satellite: every tile carries its
                    # co-located attribution so the headline table is
                    # link-mood-free
                    "device_compute": _device_compute_probe(
                        mm, mtraces, link_rtt, roofline=False),
                    "tile_source": mtile_info["source"],
                    "tile_stats": mts.stats,
                }}

        metro = journal.leg("metro", _leg_metro)
        if metro:
            detail["metro"] = metro["block"]
            audit[metro["audit_key"]] = metro["audit_entry"]
        split["metro_s"] = journal.seconds("metro")

        # -- restrictions on (VERDICT r2 #5: realistic ban density) ------
        def _leg_restricted():
            rts, rtile_info = _cached_tileset("sf", restricted=True)
            # same fleet size as the primary: throughput_vs_unrestricted
            # must isolate the restriction cost; repeats must MATCH the
            # primary's (best-of-5) or the ratio biases on a ~2x link
            rtraces, _ = _cached_fleet(rts, n_traces, n_points)
            rm, r_pps, r_decode, _ = _throughput(rts, rtraces, repeats=5)
            r_dis, _, r_n, r_src = _oracle_audit(rts, rm, rtraces, 150)
            live["sf+r"] = (rm, rtraces, 3)
            return {
                "audit_key": rts.name,
                "audit_entry": {"traces": r_n,
                                "disagreement": round(r_dis, 4),
                                "fidelity_source": r_src},
                "block": {
                    "config": (f"{len(rtraces)}x{n_points}pt traces, "
                               f"tile={rts.name} "
                               f"({int(_RESTRICT_FRACTION * 100)}% "
                               "junction restriction density)"),
                    "probes_per_sec_e2e": round(r_pps, 1),
                    "decode_only_probes_per_sec": round(r_decode, 1),
                    "throughput_vs_unrestricted": (
                        round(r_pps / jax_pps, 3) if jax_pps else None),
                    "reach_rows_growth": round(
                        rts.reach_to.shape[0]
                        / max(ts.reach_to.shape[0], 1), 3),
                    "device_compute": _device_compute_probe(
                        rm, rtraces, link_rtt, roofline=False),
                    "tile_source": rtile_info["source"],
                    "tile_stats": rts.stats,
                }}

        restricted = journal.leg("restricted", _leg_restricted)
        if restricted:
            detail["restricted"] = restricted["block"]
            audit[restricted["audit_key"]] = restricted["audit_entry"]
        split["restricted_s"] = journal.seconds("restricted")

        # -- realistic-scale HBM envelope (SURVEY §7 "HBM budget"):
        # bayarea-xl, ~0.5M directed edges; no oracle leg (exact
        # Dijkstra is minutes/trace at this size) — staging, culling,
        # throughput + the replicated-vs-sharded plan ---------------------
        def _leg_xl():
            from reporter_tpu.tiles.capacity import plan_staging

            xts, xtile_info = _cached_tileset("bayarea-xl")
            xtraces, xtrue = _cached_fleet(xts, 4000, n_points)
            xm, x_pps, x_decode, _ = _throughput(xts, xtraces, repeats=3)
            plan = plan_staging(xts)
            live["bayarea-xl"] = (xm, xtraces, 5)
            return {"block": {
                "config": (f"{len(xtraces)}x{n_points}pt traces, "
                           f"tile={xts.name}"),
                "probes_per_sec_e2e": round(x_pps, 1),
                "decode_only_probes_per_sec": round(x_decode, 1),
                "hbm_tile_bytes": int(xts.hbm_bytes()),
                "staging_plan": plan.to_json(),
                # output-sensitivity check: decode slowdown vs sf should
                # stay far below the edge-count ratio (culling working)
                "culling": {
                    "edges_vs_sf": round(xts.num_edges / ts.num_edges, 1),
                    "decode_slowdown_vs_sf": (
                        round(decode_pps / x_decode, 1)
                        if decode_pps else None),
                },
                # VERDICT r3 #5: xl fidelity via synthesis ground truth
                # + the reach-table miss rate (no exact oracle)
                "ground_truth": _truth_rates(xts, xm, xtraces, xtrue,
                                             n=1000),
                "reach_audit": _reach_audit_cached(
                    xts, [np.asarray(t.xy, np.float64)
                          for t in xtraces[:15]], label=xts.name),
                # VERDICT r4 next #3: attribute the xl slowdown
                "device_compute": _device_compute_probe(xm, xtraces,
                                                        link_rtt),
                # round-8 tentpole evidence at metro-xl scale
                "sweep_ab": _sweep_variants_probe(xm, xtraces, link_rtt),
                "tile_source": xtile_info["source"],
                "tile_stats": xts.stats,
            }}

        xl = journal.leg("xl", _leg_xl)
        if xl:
            detail["xl"] = xl["block"]
        split["xl_s"] = journal.seconds("xl")

        # -- organic topology (VERDICT r4 #3) + residual attribution -----
        def _leg_organic():
            import dataclasses as _dc

            from reporter_tpu.config import Config as _Config2
            from reporter_tpu.config import MatcherParams as _MP

            ots, otile_info = _cached_tileset("organic")
            otraces, otrue = _cached_fleet(ots, 8000, n_points)
            om, o_pps, o_decode, _ = _throughput(ots, otraces, repeats=3)
            o_dis, _, o_n, o_src = _oracle_audit(ots, om, otraces, 80)
            cfg12 = _Config2(matcher_backend="jax",
                             matcher=_dc.replace(_MP(),
                                                 max_candidates=12))
            om12 = SegmentMatcher(ots, cfg12)
            o12_dis, _, _, o12_src = _oracle_audit(ots, om12, otraces,
                                                   80, config=cfg12)
            del om12
            live["organic"] = (om, otraces, 5)
            return {
                "audit_key": ots.name,
                "audit_entry": {"traces": o_n,
                                "disagreement": round(o_dis, 4),
                                "fidelity_source": o_src},
                # VERDICT r4 weak #6: the residual's attribution in the
                # ARTIFACT — near-tie density + K-escalation
                "residual_attribution": {
                    "near_tie": _near_tie_stats(om, otraces),
                    "near_tie_sf": oracle.get("near_tie"),
                    "disagreement_k8": round(o_dis, 4),
                    "disagreement_k12": round(o12_dis, 4),
                    "k12_fidelity_source": o12_src,
                    "note": ("K-escalation probes tied-candidate "
                             "overflow; the near-tie fractions bound "
                             "the f32-flippable population the prose "
                             "attributes the residual to"),
                },
                "block": {
                    "config": (f"{len(otraces)}x{n_points}pt traces, "
                               f"tile={ots.name}"),
                    "probes_per_sec_e2e": round(o_pps, 1),
                    "decode_only_probes_per_sec": round(o_decode, 1),
                    "throughput_vs_sf": (round(o_pps / jax_pps, 3)
                                         if jax_pps else None),
                    "ground_truth": _truth_rates(ots, om, otraces,
                                                 otrue, n=1000),
                    "reach_audit": _reach_audit_cached(
                        ots, [np.asarray(t.xy, np.float64)
                              for t in otraces[:20]], label=ots.name),
                    "device_compute": _device_compute_probe(
                        om, otraces, link_rtt, roofline=False),
                    "tile_source": otile_info["source"],
                    "tile_stats": ots.stats,
                }}

        organic = journal.leg("organic", _leg_organic)
        if organic:
            detail["organic"] = organic["block"]
            detail["organic_residual_attribution"] = \
                organic["residual_attribution"]
            audit[organic["audit_key"]] = organic["audit_entry"]
        split["organic_s"] = journal.seconds("organic")

        # -- organic at several-times-metro scale ------------------------
        def _leg_organic_xl():
            oxts, oxtile_info = _cached_tileset("organic-xl")
            oxtraces, oxtrue = _cached_fleet(oxts, 4000, n_points)
            oxm, ox_pps, ox_decode, _ = _throughput(oxts, oxtraces,
                                                    repeats=3)
            live["organic-xl"] = (oxm, oxtraces, 5)
            return {"block": {
                "config": (f"{len(oxtraces)}x{n_points}pt traces, "
                           f"tile={oxts.name}"),
                "probes_per_sec_e2e": round(ox_pps, 1),
                "decode_only_probes_per_sec": round(ox_decode, 1),
                "ground_truth": _truth_rates(oxts, oxm, oxtraces,
                                             oxtrue, n=1000),
                "reach_audit": _reach_audit_cached(
                    oxts, [np.asarray(t.xy, np.float64)
                           for t in oxtraces[:8]], label=oxts.name),
                "device_compute": _device_compute_probe(oxm, oxtraces,
                                                        link_rtt),
                "tile_source": oxtile_info["source"],
                "tile_stats": oxts.stats,
            }}

        organic_xl = journal.leg("organic_xl", _leg_organic_xl)
        if organic_xl:
            detail["organic_xl"] = organic_xl["block"]
        split["organic_xl_s"] = journal.seconds("organic_xl")

        # -- non-auto mode fidelity (VERDICT r4 #7) ----------------------
        def _leg_bicycle():
            from reporter_tpu.config import Config as _Cfg

            bts, btile_info = _cached_mode_tileset()
            btraces, _ = _cached_fleet(bts, 2000, n_points)
            bcfg = _Cfg.for_mode("bicycle", matcher_backend="jax")
            bm = SegmentMatcher(bts, bcfg)
            b_dis, _, b_n, b_src = _oracle_audit(
                bts, bm, btraces, 60, config=bcfg)
            return {
                "audit_key": bts.name,
                "audit_entry": {"traces": b_n,
                                "disagreement": round(b_dis, 4),
                                "fidelity_source": b_src,
                                "mode": "bicycle"},
                "block": {
                    "config": (f"{b_n} oracle traces, tile={bts.name} "
                               "(8% bike-only / 5% foot-only ways)"),
                    "tile_source": btile_info["source"],
                    "tile_stats": bts.stats,
                }}

        bicycle = journal.leg("bicycle", _leg_bicycle)
        if bicycle:
            detail["bicycle"] = bicycle["block"]
            audit[bicycle["audit_key"]] = bicycle["audit_entry"]
        split["bicycle_s"] = journal.seconds("bicycle")

        # -- streaming path (BASELINE config 5) --------------------------
        def _leg_streaming():
            # detail.streaming = the COLUMNAR worker (the firehose
            # deployment shape, r5); dict worker stays as
            # streaming_dict for the compat surface. Best of two full
            # pumps (a single link stall once recorded 2.1k pps for a
            # leg that otherwise reads 50-65k).
            out = {}
            s_runs = [_streaming_columnar_bench(ts, traces,
                                                n_stream=2000)
                      for _ in range(2)]
            out["streaming"] = max(s_runs,
                                   key=lambda r: r["probes_per_sec"])
            out["streaming"]["runs_pps"] = [r["probes_per_sec"]
                                            for r in s_runs]
            sd_runs = [_streaming_bench(ts, traces, n_stream=2000)
                       for _ in range(2)]
            out["streaming_dict"] = max(
                sd_runs, key=lambda r: r["probes_per_sec"])
            out["streaming_dict"]["runs_pps"] = [r["probes_per_sec"]
                                                 for r in sd_runs]
            w2_runs = [_streaming_two_workers(ts, traces, n_stream=2000)
                       for _ in range(2)]
            out["streaming_2workers"] = max(
                w2_runs, key=lambda r: r["probes_per_sec"])
            out["streaming_2workers"]["runs_pps"] = [
                r["probes_per_sec"] for r in w2_runs]
            return out

        streaming = journal.leg("streaming", _leg_streaming)
        if streaming:
            detail.update(streaming)
        split["streaming_s"] = journal.seconds("streaming")

        # -- streaming capacity grid (r6 tentpole) -----------------------
        cap = journal.leg("streaming_capacity",
                          lambda: _streaming_capacity(ts, traces,
                                                      n_stream=2000))
        if cap:
            detail["streaming_capacity"] = cap
        split["streaming_capacity_s"] = journal.seconds(
            "streaming_capacity")

    # -- streaming soak (VERDICT r5 missing #1) + the r22 pipelined-vs-
    # serial prepare A/B. The soak point is a full-run measurement; the
    # A/B rides the same journal leg on EVERY composite (no-chip =
    # injected-flight mechanism validation, ~15 s — the driver's harness
    # for the r22 overlap bar), so --resume/--legs names are unchanged.
    def _leg_soak():
        out = (_streaming_soak(ts, traces, n_stream=2000)
               if full_run else {})
        out["prepare_ab"] = _soak_prepare_ab(ts, traces)
        return out

    soak = (journal.leg("streaming_soak", _leg_soak)
            if needs_primary else None)
    if soak:
        detail["streaming_soak"] = soak
    split["streaming_soak_s"] = journal.seconds("streaming_soak")

    # -- latency attribution (ISSUE 5 tentpole) runs on EVERY composite:
    # the reconciled per-stage decomposition + the tracing-overhead A/B —
    # scaled down off-chip so one core serving producer+consumer stays
    # honest -------------------------------------------------------------
    def _leg_lattr():
        if full_run:
            return _latency_attribution(ts, traces, n_stream=2000,
                                        offered_pps=100_000)
        return _latency_attribution(
            ts, traces, n_stream=min(500, len(traces)),
            offered_pps=(50_000 if tpu_ok else 2_000), seconds=5.0)

    lattr = (journal.leg("latency_attribution", _leg_lattr)
             if needs_primary else None)
    if lattr:
        detail["latency_attribution"] = lattr
    split["latency_attribution_s"] = journal.seconds(
        "latency_attribution")

    if full_run:
        # -- overload soak (VERDICT r5 missing #2): 2x the sustainable
        # rate against a bounded broker, counted shedding ----------------
        def _leg_overload():
            sustainable = max(
                (detail.get("streaming_soak") or {}).get(
                    "sustained_pps") or 0.0,
                (detail.get("streaming_capacity") or {}).get(
                    "best_held_pps") or 0.0)
            return _streaming_overload(ts, traces, 2000, sustainable)

        overload = journal.leg("streaming_overload", _leg_overload)
        if overload:
            detail["streaming_overload"] = overload
        split["streaming_overload_s"] = journal.seconds(
            "streaming_overload")

    # -- chaos legs (ISSUE 4): publisher outage, kill-and-recover, live
    # 2-process consumer group — chip composites always; CPU/manual runs
    # opt in via REPORTER_BENCH_CHAOS=1 ----------------------------------
    def _leg_chaos():
        d: dict = {}
        s: dict = {}
        _run_chaos_legs(ts, traces, d, s)
        return {"detail": d, "split": s}

    if ts is not None and (full_run or env_flag(
            os.environ.get("REPORTER_BENCH_CHAOS"))):
        chaos = journal.leg("chaos", _leg_chaos)
        if chaos:
            detail.update(chaos["detail"])
            split.update(chaos["split"])

    if full_run:
        # -- device-only compute (VERDICT r4 #6): best of two probes ----
        def _leg_device_compute():
            d_runs = [_device_compute_probe(jax_matcher, traces,
                                            link_rtt)
                      for _ in range(2)]
            best = max(d_runs,
                       key=lambda r: r["colocated_probes_per_sec"])
            best["runs_colocated_pps"] = [
                r["colocated_probes_per_sec"] for r in d_runs]
            return best

        dc = journal.leg("device_compute", _leg_device_compute)
        if dc:
            detail["device_compute"] = dc
        split["device_compute_s"] = journal.seconds("device_compute")

    # -- sweep-kernel three-arm A/B: on-chip interleaved probe for chip
    # composites; pallas-interpreter validation (identity bits only) on
    # every no-chip composite — self-contained there, so
    # `--legs sweep_ab` fits a short window -------------------------------
    def _leg_sweep_ab():
        if full_run:
            return _sweep_variants_probe(jax_matcher, traces, link_rtt)
        return _sweep_ab_cpu_validate()

    sweep = journal.leg("sweep_ab", _leg_sweep_ab)
    if sweep:
        detail["sweep_ab"] = sweep
    split["sweep_ab_s"] = journal.seconds("sweep_ab")

    # -- per-metro self-tuning (round 17): the resolved plan +
    # per-candidate calibration timings + tuned-vs-default A/B on chip;
    # injected-timer mechanism validation on every no-chip composite
    # (self-contained there, so `--legs autotune` fits a short window) --
    def _leg_autotune():
        if full_run:
            return _autotune_probe(jax_matcher, traces, link_rtt)
        return _autotune_cpu_validate()

    tune = journal.leg("autotune", _leg_autotune)
    if tune:
        detail["autotune"] = tune
    split["autotune_s"] = journal.seconds("autotune")

    # -- online match-quality telemetry (round 18): steady-wave quality
    # signals + the shadow-audit overhead A/B at the default rate on
    # chip; tiny-scale mechanism validation (signals, audit, sampler
    # determinism, drift chaos) on every no-chip composite — self-
    # contained there, so `--legs quality` fits a short window ---------
    def _leg_quality():
        if full_run:
            return _quality_probe(jax_matcher, traces)
        return _quality_cpu_validate()

    qual = journal.leg("quality", _leg_quality)
    if qual:
        detail["quality"] = qual
    split["quality_s"] = journal.seconds("quality")

    if full_run:
        # -- per-tile co-located e2e (round-8 satellite): derived from
        # the assembled detail, not journaled ---------------------------
        detail["colocated_e2e"] = {
            name: blk["device_compute"]["colocated_e2e_probes_per_sec"]
            for name, blk in (("sf", detail),
                              ("bayarea", detail.get("metro", {})),
                              ("sf+r", detail.get("restricted", {})),
                              ("bayarea-xl", detail.get("xl", {})),
                              ("organic", detail.get("organic", {})),
                              ("organic-xl",
                               detail.get("organic_xl", {})))
            if blk.get("device_compute", {}).get(
                "colocated_e2e_probes_per_sec") is not None}

        # -- second mood window (round-4 discipline): re-measure every
        # tile measured FRESH this run back-to-back; journal-resumed
        # tiles keep their window-1 numbers -------------------------------
        def _leg_window2():
            rtt2 = _link_rtt()      # per-window link mood, recorded
            #                         with the window it conditions
            w2: dict = {"link_rtt_ms": round(rtt2 * 1e3, 2)}
            for name, (mobj, mtr, reps) in live.items():
                dt2, dt_dec2 = _timed_pair(mobj, mtr, reps)
                p = sum(len(t.xy) for t in mtr)
                w2[name] = {
                    "probes_per_sec_e2e": round(p / dt2, 1),
                    "decode_only_probes_per_sec": round(p / dt_dec2, 1)}
            return w2

        w2 = journal.leg("window2", _leg_window2)
        if w2:
            detail["second_window"] = w2
            # One selection rule for EVERY tile: the window whose e2e
            # won supplies BOTH that tile's published e2e and decode
            # numbers (mood-consistent pairs; merge is idempotent on
            # resume because both windows' numbers are journaled).
            sfw = w2.get("sf")
            if sfw and jax_pps and sfw["probes_per_sec_e2e"] > jax_pps:
                jax_pps = sfw["probes_per_sec_e2e"]
                decode_pps = sfw["decode_only_probes_per_sec"]
                detail["decode_only_probes_per_sec"] = round(
                    decode_pps, 1)
                detail["e2e_over_decode"] = round(jax_pps / decode_pps,
                                                  3)
                detail["batch_seconds"] = round(
                    n_traces * n_points / jax_pps, 3)
            for name, key in (("bayarea", "metro"),
                              ("sf+r", "restricted"),
                              ("bayarea-xl", "xl"),
                              ("organic", "organic"),
                              ("organic-xl", "organic_xl")):
                tw = w2.get(name)
                if (tw and key in detail
                        and tw["probes_per_sec_e2e"]
                        > detail[key]["probes_per_sec_e2e"]):
                    detail[key]["probes_per_sec_e2e"] = \
                        tw["probes_per_sec_e2e"]
                    detail[key]["decode_only_probes_per_sec"] = \
                        tw["decode_only_probes_per_sec"]
            # cross-tile ratios divide the PUBLISHED (best-of-both-
            # windows) numbers; effects under the ~10% residual noise
            # floor are not resolvable — noted inline
            if jax_pps and "restricted" in detail:
                detail["restricted"]["throughput_vs_unrestricted"] = \
                    round(detail["restricted"]["probes_per_sec_e2e"]
                          / jax_pps, 3)
            if jax_pps and "organic" in detail:
                detail["organic"]["throughput_vs_sf"] = round(
                    detail["organic"]["probes_per_sec_e2e"] / jax_pps, 3)
            if decode_pps and "xl" in detail:
                detail["xl"]["culling"]["decode_slowdown_vs_sf"] = round(
                    decode_pps
                    / detail["xl"]["decode_only_probes_per_sec"], 1)
            detail["ratio_note"] = (
                "ratios divide best-of-8-draws numbers (equal draw "
                "counts per tile, window-paired e2e/decode); link "
                "noise ~2x dominates effects under ~10%")
        split["window2_s"] = journal.seconds("window2")

    if audit:
        detail["audit"] = {
            "total_traces": sum(v["traces"] for v in audit.values()),
            "per_tile": audit}

    # -- host-prepare micro A/B (ISSUE 7): every composite ---------------
    if needs_primary:
        prep = journal.leg("prepare_bench",
                           lambda: _prepare_bench(ts, traces))
        if prep:
            detail["prepare_bench"] = prep
        split["prepare_bench_s"] = journal.seconds("prepare_bench")

    # -- metro fleet residency (ISSUE 6): every composite; self-contained
    # (builds its own metros), so `--legs fleet` needs no primary setup --
    fleet = journal.leg("fleet", lambda: _fleet_bench(tpu_ok))
    if fleet:
        detail["fleet"] = fleet
    # NOT split["fleet_s"] — that key is the trace-FLEET synthesis timing
    # in setup_seconds' sum
    split["fleet_residency_s"] = journal.seconds("fleet")

    # -- topology observability plane (ISSUE 15): every composite;
    # self-contained (builds its own tile, CPU-pinned worker
    # subprocesses), so `--legs topology` fits a short window ----------
    topo = journal.leg("topology", lambda: _topology_bench(tpu_ok))
    if topo:
        detail["topology"] = topo
    split["topology_s"] = journal.seconds("topology")

    # -- open-loop backfill engine (ISSUE 16): every composite;
    # self-contained (builds + spools its own tile), so `--legs
    # backfill` fits a short window ------------------------------------
    backfill = journal.leg("backfill", lambda: _backfill_bench(tpu_ok))
    if backfill:
        detail["backfill"] = backfill
    split["backfill_s"] = journal.seconds("backfill")

    # -- SLO burn-rate plane (ISSUE 20): every composite; fully
    # synthetic (injected clock, no chip, no link), so `--legs slo`
    # fits any window and its pass/fail can never ride link mood ------
    slo_leg = journal.leg("slo", _slo_bench)
    if slo_leg:
        detail["slo"] = slo_leg
    split["slo_s"] = journal.seconds("slo")

    # -- link-health record (round 15): the whole run's window + the
    # measured probe duty (the <0.5% steady-state claim as a field) ------
    if link_enabled:
        _ls = linkhealth.sampler()
        detail["link_health"] = {
            **_ls.window(),
            "probe_duty_pct": _ls.probe_duty_pct(),
            "probes": _ls.probes_total,
            "dead_probes": _ls.dead_probes_total,
        }
    else:
        detail["link_health"] = {"rtt_ms": None, "mbps": None,
                                 "mood": None, "samples": 0,
                                 "probe_duty_pct": None, "probes": 0,
                                 "dead_probes": 0}
    detail["journal"] = journal.to_json()

    detail["setup_split"] = {k: v for k, v in split.items()
                             if v is not None}
    detail["setup_seconds"] = round(
        split.get("device_probe_s", 0.0) + (split.get("tile_s") or 0.0)
        + (split.get("fleet_s") or 0.0), 1)
    detail["total_seconds"] = round(time.perf_counter() - t_setup, 1)

    doc = {
        "metric": "probes_per_sec_e2e",
        "value": (round(jax_pps, 1) if jax_pps else None),
        "unit": "probes/s",
        "vs_baseline": (round(jax_pps / cpu_pps, 2)
                        if jax_pps and cpu_pps else None),
        "provenance": prov,
        "detail": detail,
    }
    # Full composite detail: a side file + an EARLY stdout line. The
    # driver records only the tail of stdout (round 3's single fat line
    # overran it → BENCH_r03 parsed:null), so the FINAL line below is a
    # compact summary that always fits the capture window. ANY CPU
    # composite (the env-forced sanity run; without it main() exits
    # non-zero) goes to BENCH_DETAIL_CPU.json, so it can never clobber
    # the chip-captured BENCH_DETAIL.json.
    full_name = ("BENCH_DETAIL.json" if tpu_ok
                 else "BENCH_DETAIL_CPU.json")
    # a --legs SUBSET composite must never clobber the committed FULL
    # capture (the r6 overwrite-hazard class: a sparse artifact wearing
    # the full capture's filename) — it gets its own side file
    detail_name = (full_name if legs_filter is None
                   else full_name.replace(".json", "_PARTIAL.json"))
    # regression sentinel (round 15): diff against the committed FULL
    # capture of the SAME flavor BEFORE any overwrite — every capture
    # self-reports what moved and whether the link excuses it
    delta = _bench_delta_tail(doc, _repo_path(full_name))
    if delta is not None:
        detail["bench_delta"] = delta
    with open(_repo_path(detail_name), "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps(doc))
    print(json.dumps(_summary_line(doc)))


def _mxu_token(_g) -> list:
    """mxu = [sf Mpps, xl Mpps, bytes_identical] — the round-13
    acceptance bar in three slots. The identity slot folds a tile's
    recorded identity bits (cross-arm wire bytes + the paging-cycle
    re-harvest) ONLY when that tile's probe says the mxu arm actually
    participated (``mxu_compared`` — a lowering failure drops the arm,
    and two legacy arms agreeing must not read as a green three-arm
    contract): any counted False → 0, all counted True → 1, no tile
    compared the mxu arm → None."""
    sf = _g("sweep_ab", "mxu", "device_probes_per_sec")
    xl = _g("xl", "sweep_ab", "mxu", "device_probes_per_sec")
    bits = []
    for path in ((), ("xl",)):
        if _g(*path, "sweep_ab", "mxu_compared"):
            bits += [b for b in (
                _g(*path, "sweep_ab", "wires_bit_identical"),
                _g(*path, "sweep_ab", "wires_identical_after_paging"),
            ) if b is not None]
    return [None if sf is None else round(sf / 1e6, 2),
            None if xl is None else round(xl / 1e6, 2),
            None if not bits else int(all(bits))]


def _qual_token(_g) -> list:
    """qual = [empty-match bp, speed-violation bp, audit-disagreement
    bp, audit overhead % of steady-wave host cost (acceptance <2),
    drift events, mechanism bit (CPU validation; None on chip)] — the
    round-18 quality leg's headline (full leg in detail.quality).
    Rates ride as BASIS-POINT ints (the r18 compaction; exact values
    stay in the detail file)."""
    def bp(v):
        return None if v is None else int(round(v * 1e4))

    mech = _g("quality", "mechanism_ok")
    return [bp(_g("quality", "signals", "empty_match_rate")),
            bp(_g("quality", "signals", "violation_rate")),
            bp(_g("quality", "audit", "disagreement_rate")),
            _g("quality", "audit_overhead", "audit_overhead_pct"),
            _g("quality", "drift", "drift_events"),
            None if mech is None else int(bool(mech))]


def _topo_token(_g) -> list:
    """topo = [workers (main arm), aggregate probes/s over the soak
    wall (int — CPU-pinned workers by construction, see
    _topology_bench), deaths (main + lease arms summed), restarts,
    recovery seconds (SIGKILL → the restarted worker spooling snapshots
    again, 1 decimal), lost records across BOTH arms' replays (must be
    0), lease-arm kill→reacquire seconds (1 decimal, the r23 rebalance
    latency; None when the arm didn't run), folded identity bit]. The
    fold (mxu-token style) covers every bit the leg recorded:
    aggregation fidelity, cross-pid stitch, and the lease arm's
    zero-lost + zero-dup + drained-by-exit + stale-commit-fenced +
    fault-stats-surfaced —
    any recorded False reads 0; an unexercised bit is absent from the
    fold, never vacuous green."""
    pps = _g("topology", "soak", "probes_per_sec_wall")
    rec_s = _g("topology", "recovery_seconds")
    reb_s = _g("topology", "lease", "kill_to_reacquire_seconds")
    deaths = [d for d in (_g("topology", "deaths"),
                          _g("topology", "lease", "deaths"))
              if d is not None]
    lost = [v for v in (_g("topology", "lost_records"),
                        _g("topology", "lease", "lost_records"))
            if v is not None]
    bits = [b for b in (_g("topology", "aggregation", "fidelity_ok"),
                        _g("topology", "stitch", "ok"),
                        _g("topology", "lease", "zero_lost_ok"),
                        _g("topology", "lease", "zero_dup_ok"),
                        _g("topology", "lease", "drained_by_exit_ok"),
                        _g("topology", "lease", "stale_commit_rejected"),
                        _g("topology", "lease", "fault_stats_surfaced"))
            if b is not None]
    return [_g("topology", "workers"),
            None if pps is None else int(pps),
            None if not deaths else int(sum(deaths)),
            _g("topology", "restarts"),
            None if rec_s is None else round(rec_s, 1),
            None if not lost else int(sum(lost)),
            None if reb_s is None else round(reb_s, 1),
            None if not bits else int(all(bits))]


def _bf_token(_g) -> list:
    """bf = [open-loop krows/s (1 decimal), open/closed speedup vs the
    same spool's closed-loop drain (the acceptance bar: ≥ 1 on a CPU
    capture), identity bit, k-anonymity-withheld segment count,
    mesh-arm krows/s (1 decimal; None on a 1-device composite)] — full
    leg in detail.backfill. The identity slot folds EVERY recorded
    identity bit (the mxu-token style): single-arm shadow, and when the
    mesh arm ran, its shadow + mesh-vs-single aggregate equality +
    prepared-seam wire-byte identity — any recorded False reads 0, an
    unexercised bit is simply absent from the fold, never vacuous
    green."""
    kr = _g("backfill", "open_loop", "krows_per_s")
    vs = _g("backfill", "vs_soak_x")
    bits = [b for b in (_g("backfill", "open_loop", "agg_identical"),
                        _g("backfill", "mesh", "agg_identical"),
                        _g("backfill", "mesh", "agg_equal_single"),
                        _g("backfill", "mesh", "wire_bytes_identical"))
            if b is not None]
    mkr = _g("backfill", "mesh", "krows_per_s")
    return [None if kr is None else round(kr, 1),
            None if vs is None else round(vs, 2),
            None if not bits else int(all(bits)),
            _g("backfill", "open_loop", "kanon_dropped"),
            None if mkr is None else round(mkr, 1)]


def _slo_token(_g) -> list:
    """slo = [clean-arm alerts (must be 0), chaos-arm alerts (2 = both
    fault classes fired their matching spec), folded contract bit] —
    full leg in detail.slo. The fold takes EVERY recorded bit (the
    mxu-token style): matching-spec fire+resolve per fault class, one
    post-mortem per fire transition, zero lost ledger entries, and the
    topology merge-commute property — any recorded False reads 0, an
    unexercised bit is absent from the fold, never vacuous green."""
    bits = [b for b in (_g("slo", "tp_match"),
                        _g("slo", "one_pm_per_fire"),
                        _g("slo", "ledger_ok"),
                        _g("slo", "merge_commute"))
            if b is not None]
    return [_g("slo", "clean_alerts"),
            _g("slo", "chaos_alerts"),
            None if not bits else int(all(bits))]


def _summary_line(doc: dict) -> dict:
    """Compact (<1 KB, CI-pinned by tests/test_bench_summary.py)
    machine-readable round summary: headline value, per-tile throughput,
    per-tile audit disagreement, fidelity provenance,
    streaming/device-compute/reach/serving key numbers."""
    d = doc["detail"]

    def _g(*path, default=None):
        cur = d
        for p in path:
            if not isinstance(cur, dict) or p not in cur:
                return default
            cur = cur[p]
        return cur

    # per-tile numbers ride as FIXED-ORDER kpps arrays (round 8: the 1 KB
    # pin had no room for six names twice) — order is always [sf,
    # bayarea, sf+r, bayarea-xl, organic, organic-xl]; exact values keep
    # their names in the detail file
    # device string truncated at its parenthetical (a constant
    # provenance tail — the full string stays in the detail file); the
    # r12 prep token needed the bytes
    dev = d.get("device")
    if isinstance(dev, str):
        dev = dev.split(" (", 1)[0]
    # value is None on --legs subset composites that skipped the
    # primary leg — the slot stays None, never a crash
    tiles_kpps: list = [None if doc.get("value") is None
                        else int(doc["value"] / 1e3)]
    for key in ("metro", "restricted", "xl", "organic", "organic_xl"):
        v = _g(key, "probes_per_sec_e2e")
        tiles_kpps.append(None if v is None else int(v / 1e3))
    if all(v is None for v in tiles_kpps[1:]):
        tiles_kpps = tiles_kpps[:1]     # sparse runs: just the headline
    per_tile = _g("audit", "per_tile", default={})
    fleet_pps = _g("fleet", "mixed", "probes_per_sec")
    fleet_bit = _g("fleet", "fidelity", "wires_bit_identical")
    regs = _g("bench_delta", "regressions", default=[]) or []
    summary = {
        "metric": doc["metric"],
        "value": doc["value"],
        # "unit" dropped from the LINE (r19 compaction — the topo token
        # needed the bytes): it is implied by the metric name and stays
        # in the doc/detail file
        "vs_baseline": doc["vs_baseline"],
        "device": dev,
        "tiles_kpps": tiles_kpps,
        # per-mille int (r21 compaction — the bf mesh slot needed the
        # bytes); the exact ratio keeps its name in the detail file
        "e2e_od_pm": (None if d.get("e2e_over_decode") is None
                      else int(round(d["e2e_over_decode"] * 1e3))),
        # fixed-order array [single-trace e2e p50 (whole ms, r18
        # compaction), matcher-only p50] — the two r18 keys folded into
        # one (r20 compaction: the bf token needed the bytes); exact
        # values stay in the detail file
        "p50_ms": [(None
                    if d.get("p50_single_trace_latency_ms") is None
                    else int(d["p50_single_trace_latency_ms"])),
                   d.get("p50_matcher_only_ms")],
        # key names compacted for the 1 KB pin (r8 precedent): xl_bind =
        # xl binding leg ("dev" = device_sweep, "host" = host legs —
        # r15 compaction, the link/delta tokens needed the bytes),
        # rtt_ms = [window1, window2] link RTT, whole ms
        "xl_bind": (None if _g("xl", "device_compute",
                               "binding_leg") is None
                    else ("dev" if _g("xl", "device_compute",
                                      "binding_leg") == "device_sweep"
                          else "host")),
        "rtt_ms": [
            None if v is None else int(v)
            for v in (d.get("link_rtt_ms"),
                      _g("second_window", "link_rtt_ms"))],
        # audit is a FIXED-ORDER array now (r24 compaction — the slo
        # token needed the bytes): [total traces, dis_bp array, src
        # array]. dis_bp: BASIS-POINT ints (r18; 0.0123 rides as 123)
        # in the audit legs' insertion order [headline,
        # headline-fresh-rot, bayarea, sf+r, organic, bicycle]; named
        # exact values in detail.audit.per_tile
        "audit": [
            _g("audit", "total_traces"),
            [None if v.get("disagreement") is None
             else int(round(v["disagreement"] * 1e4))
             for v in per_tile.values()],
            sorted({v.get("fidelity_source", "?")
                    for v in per_tile.values()}),
        ],
        # fixed-order arrays (the r8 kpps compaction, applied here when
        # the lattr token needed the bytes back): gt_pm = point-on-edge
        # rate in PER-MILLE ints (r18 compaction: 0.9444 rides as 944)
        # for [headline tile, bayarea-xl, organic, organic-xl],
        # reach_miss = step miss rate for [bayarea-xl, organic,
        # organic-xl]; named exact values stay in detail.*.ground_truth /
        # detail.*.reach_audit
        "gt_pm": [None if v is None else int(round(v * 1e3))
                  for v in (_g(*path, "point_edge_rate") for path in
                            (("ground_truth",), ("xl", "ground_truth"),
                             ("organic", "ground_truth"),
                             ("organic_xl", "ground_truth")))],
        # basis-point ints (r18 compaction; exact rates stay in
        # detail.*.reach_audit)
        "reach_miss_bp": [
            None if v is None else int(round(v * 1e4))
            for v in (_g(k, "reach_audit", "step_miss_rate")
                      for k in ("xl", "organic", "organic_xl"))],
        # kpps int (r13: the mxu token needed the bytes — the r8
        # tiles_kpps compaction applied here; exact value in
        # detail.streaming.probes_per_sec)
        "stream_kpps": (None if _g("streaming", "probes_per_sec") is None
                        else int(_g("streaming", "probes_per_sec") / 1e3)),
        # dict-pipeline pps + soak p99/offered/duration + the full
        # capacity grid live in the detail file only: the FINAL line must
        # stay under the driver's ~1 KB tail. Fixed-order array (r15
        # compaction): [sustained kpps, end lag, p50 probe->report ms,
        # best held capacity kpps, overload producer rejections,
        # pipelined-vs-serial prepare speedup x100 int (r22 A/B),
        # prepare-A/B identity bit (wire bytes AND report stream — folded
        # only when the A/B ran, never vacuous green)] — exact values in
        # detail.streaming_soak (incl. .prepare_ab) / _capacity /
        # _overload
        "soak": [
            (None if _g("streaming_soak", "sustained_pps") is None
             else int(_g("streaming_soak", "sustained_pps") / 1e3)),
            _g("streaming_soak", "end_lag"),
            (None if _g("streaming_soak",
                        "p50_probe_to_report_ms") is None
             else int(_g("streaming_soak", "p50_probe_to_report_ms"))),
            (None if _g("streaming_capacity", "best_held_pps") is None
             else int(_g("streaming_capacity", "best_held_pps") / 1e3)),
            _g("streaming_overload", "broker_rejected"),
            (None if _g("streaming_soak", "prepare_ab",
                        "pipelined_speedup") is None
             else int(round(_g("streaming_soak", "prepare_ab",
                               "pipelined_speedup") * 100))),
            (None if _g("streaming_soak", "prepare_ab",
                        "wire_bytes_identical") is None
             else int(bool(
                 _g("streaming_soak", "prepare_ab",
                    "wire_bytes_identical")
                 and _g("streaming_soak", "prepare_ab",
                        "reports_identical"))))],
        # sf submit-vs-device colocated bound, kpps int (same r13
        # compaction; exact value in detail.device_compute)
        "colo_kpps": (
            None if _g("device_compute", "colocated_probes_per_sec") is None
            else int(_g("device_compute",
                        "colocated_probes_per_sec") / 1e3)),
        # per-tile co-located e2e in THOUSANDS of probes/s, fixed tile
        # order [sf, bayarea, sf+r, bayarea-xl, organic, organic-xl] —
        # the link-mood-free headline table (full per-tile attribution in
        # detail.*.device_compute; exact values in detail.colocated_e2e)
        "coe2e_kpps": [
            None if v is None else int(v / 1e3)
            for v in (_g("colocated_e2e", t) for t in
                      ("sf", "bayarea", "sf+r", "bayarea-xl",
                       "organic", "organic-xl"))],
        # kernel-lever A/B on sf, thousands of device probes/s: [subcull,
        # whole-block, mxu (r13 matmul coarse pass — the promoted home of
        # the r8 bf16 lever), wires byte-identical] — xl's copy +
        # ms/dispatch live in detail.sweep_ab / detail.xl
        "sweep_kpps": [
            None if v is None else int(v / 1e3) if not isinstance(v, bool)
            else int(v)
            for v in (_g("sweep_ab", "subcull", "device_probes_per_sec"),
                      _g("sweep_ab", "block", "device_probes_per_sec"),
                      _g("sweep_ab", "mxu", "device_probes_per_sec"),
                      _g("sweep_ab", "wires_bit_identical"))],
        # round-13 acceptance token: mxu arm in MILLIONS of device
        # probes/s on [sf, xl], then bytes-identical (1 requires EVERY
        # recorded identity bit — both tiles' cross-arm wires AND the
        # evict→promote paging re-harvest — to be True; 0 = some bit
        # False; None = nothing recorded)
        "mxu": _mxu_token(_g),
        # round-17 self-tuning token: [chosen plan label, tuned-vs-
        # default dispatch speedup (chip probe; None on CPU validation),
        # plan source, mechanism bit (CPU validation; None on chip)] —
        # full leg in detail.autotune
        "tune": [_g("autotune", "plan", "label"),
                 _g("autotune", "tuned_vs_default_speedup"),
                 _g("autotune", "source"),
                 (None if _g("autotune", "mechanism_ok") is None
                  else int(bool(_g("autotune", "mechanism_ok"))))],
        # round-18 quality token (see _qual_token)
        "qual": _qual_token(_g),
        # chaos headline (full legs in detail.recovery /
        # detail.publish_outage / detail.streaming_soak_mp): [recovery
        # seconds after a SIGKILL, duplicated reports (the at-least-once
        # tax), LOST reports (must be 0), dead-letter rows still spooled
        # at outage end (must be 0), 2-vs-1-process drain speedup]
        "rec": [_g("recovery", "recovery_seconds"),
                _g("recovery", "duplicated_reports"),
                _g("recovery", "lost_reports"),
                _g("publish_outage", "dead_letter_pending_end"),
                _g("streaming_soak_mp", "speedup_2v1")],
        # latency attribution headline (full decomposition in
        # detail.latency_attribution): [e2e p50 ms at the held offer
        # (whole ms — r18 compaction), sum-of-stage-p50s / e2e-p50
        # (1.0 = perfect reconciliation), tracing-overhead % from the
        # traced-vs-untraced A/B]
        "lattr": [(None if _g("latency_attribution",
                              "e2e_p50_ms") is None
                   else int(_g("latency_attribution", "e2e_p50_ms"))),
                  _g("latency_attribution", "stage_sum_over_e2e_p50"),
                  _g("latency_attribution", "tracing_overhead_pct")],
        # host-prepare A/B headline (full leg in detail.prepare_bench):
        # [native krows/s through the submit-leg prepare (int), speedup
        # vs the numpy reference (1 decimal), wire bytes identical
        # native-vs-Python (must be 1)] — exact values in the detail
        "prep": [
            (None if _g("prepare_bench", "native_krows_per_s") is None
             else int(_g("prepare_bench", "native_krows_per_s"))),
            (None if _g("prepare_bench", "speedup") is None
             else round(_g("prepare_bench", "speedup"), 1)),
            (None if _g("prepare_bench", "bytes_identical") is None
             else int(bool(_g("prepare_bench", "bytes_identical"))))],
        # fleet residency headline (full leg in detail.fleet): [metros
        # served from one process, mixed-traffic kpps, storm promotion
        # p50 whole ms (r18 compaction), total promotions, total
        # demotions, fleet wires byte-identical through paging (must
        # be 1)]
        "fleet": [
            _g("fleet", "n_metros"),
            None if fleet_pps is None else int(fleet_pps / 1e3),
            (None if _g("fleet", "storm", "promote_p50_ms") is None
             else int(_g("fleet", "storm", "promote_p50_ms"))),
            _g("fleet", "occupancy", "promotions"),
            _g("fleet", "occupancy", "demotions"),
            None if fleet_bit is None else int(bool(fleet_bit))],
        # round-19 topology token (see _topo_token)
        "topo": _topo_token(_g),
        # round-20 backfill token (see _bf_token)
        "bf": _bf_token(_g),
        # round-24 SLO token (see _slo_token)
        "slo": _slo_token(_g),
        # round-15 link-health token: [rtt_ms, mbps, mood] — the run's
        # window; CPU composites record mood "cpu", never omit the token
        # (full record incl. measured probe duty in detail.link_health)
        "link": [
            (None if _g("link_health", "rtt_ms") is None
             else int(_g("link_health", "rtt_ms"))),
            (None if _g("link_health", "mbps") is None
             else round(_g("link_health", "mbps"), 1)),
            _g("link_health", "mood")],
        # round-15 regression sentinel: [regressions, link-attributable,
        # worst regression %] vs the committed same-flavor capture (full
        # attributed table in detail.bench_delta)
        "delta": [_g("bench_delta", "regressions_total"),
                  _g("bench_delta", "link_attributable_total"),
                  regs[0]["delta_pct"] if regs else None],
        # serving-face A/B headline (full curves + open loop in detail):
        # [clients, scheduler req/s, queue-and-combine req/s, dispatches
        # at in-flight depth >= 2, errors, first overloaded client level
        # (None = survived the whole curve — the r20 compaction folded
        # the old svc_edge key in as the last slot; the bf token needed
        # the bytes)] — same run, alternated rounds; req/s truncated to
        # ints (r15 compaction)
        "svc": [_g("service_ab", "clients"),
                (None if _g("service_ab", "scheduler_rps") is None
                 else int(_g("service_ab", "scheduler_rps"))),
                (None if _g("service_ab", "legacy_rps") is None
                 else int(_g("service_ab", "legacy_rps"))),
                _g("service_ab", "inflight_ge2_dispatches"),
                _g("service_ab", "errors"),
                _g("service_overload_boundary", "clients")],
        # r22 compaction (the soak token's two prepare-A/B slots needed
        # the bytes): the summary key is total_s now; the detail file
        # keeps the full total_seconds name
        "total_s": d.get("total_seconds"),
    }
    return summary


if __name__ == "__main__":
    main()
