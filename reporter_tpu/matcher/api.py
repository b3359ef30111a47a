"""SegmentMatcher — public matcher API with the backend boundary.

Mirrors the reference's `segment_matcher` binding surface (SURVEY.md §2.2
row 1, BASELINE.md north star): ``match(trace_json) → {"segments": [...],
"mode": ...}``, with ``matcher_backend`` selecting:

  "jax"           — batched TPU kernels (ops/), reach-table routing;
  "reference_cpu" — the in-repo Meili stand-in (cpu_reference.py), exact
                    Dijkstra routing; the accuracy oracle.

`match_many` is the throughput path: traces are padded into a small set of
length buckets so the jit'd kernel compiles once per bucket
(SURVEY.md §7.5) and a whole bucket crosses the host↔device boundary as one
batch.
"""

from __future__ import annotations

import threading
from collections.abc import Sequence as _SequenceABC
from dataclasses import dataclass
from typing import Any, NamedTuple, Sequence

import numpy as np

from reporter_tpu.utils import locks
from reporter_tpu import faults
from reporter_tpu.config import Config, MatcherParams
from reporter_tpu.geometry import lonlat_to_xy
from reporter_tpu.matcher import cpu_reference
from reporter_tpu.matcher.segments import (
    MatchedChain,
    SegmentRecord,
    build_segments,
    reach_route_fn,
)
from reporter_tpu.tiles.tileset import TileSet
from reporter_tpu.utils import linkhealth, tracing
from reporter_tpu.utils import watchdog as watchdog_mod
from reporter_tpu.utils.metrics import MetricsRegistry
from reporter_tpu.utils.watchdog import AbandonedThreadWatchdog

# padded point-length buckets — one compiled executable per bucket. The
# bucket set is part of the pinned compiled-shape universe
# (analysis/compile_manifest.py): changing it requires regenerating the
# golden manifest (`python -m reporter_tpu.analysis --update-manifest`).
_BUCKETS = (16, 32, 64, 128, 256, 512, 1024)

# Most rows one device dispatch carries, however many devices share it:
# the largest scheduler trace-count rung, so a dispatch shape on any mesh
# stays inside the pinned compiled-shape universe; on a mesh one host
# unpacks and walks every device's rows, so its slices stay this small.
_MAX_DISPATCH_ROWS = 4096


class PreparedSlice(NamedTuple):
    """One submit slice after host-side prepare, before dispatch.

    The round-20 prepare/dispatch seam: ``prepare_submit_slice`` is pure
    host work (the r12 native prepare pass + accuracy scaling) and safe
    to run on a read-ahead thread, while ``submit_prepared`` only
    dispatches through the existing wire entries — so an open-loop
    caller (backfill/engine.py) overlaps prepare of slice k+1 with
    device execution of slice k without re-packing anything."""

    b: int                       # point bucket (padded length)
    ws: "list[int]"              # work indices (Morton order preserved)
    mode: int                    # 2 = i8 delta, 1 = i16 quantized, 0 = f32
    pts: Any                     # f32 points (mode 0 path)
    lens: Any
    origins: Any
    payload: Any
    scale: "np.ndarray | None"   # accuracy → emission scale, or None


class PreparedBatch(NamedTuple):
    """A whole match_many call's host prepare, done ahead of dispatch.

    The round-22 wave-level seam: ``prepare_many`` runs the full plan +
    per-slice prepare (all pure host work) so a read-ahead thread can
    overlap wave N+1's prepare with wave N's device occupancy; passing
    the result back via ``match_many(traces, prepared=...)`` makes the
    dispatch loop submit the prebuilt slices instead of re-preparing.
    Bit-identical by construction — the SAME plan_submit /
    prepare_submit_slice calls in the SAME order, only moved in time."""

    work: Any                    # plan_submit's work list
    slices: "list[PreparedSlice]"   # in submission order


class DispatchTimeout(RuntimeError):
    """A device dispatch exceeded ``matcher.dispatch_timeout_s``.

    A wedged device or host-device transfer HANGS rather than erroring
    — so this is raised by a watchdog, not caught from jax.
    Callers treat it as retryable: the streaming pipeline releases the
    wave's held rows for a later re-flush (columnar._harvest), the batch
    scheduler retries per submission, and the WSGI face maps it to 503."""


@dataclass
class Trace:
    """Normalized input trace (host-side)."""

    uuid: str
    xy: np.ndarray       # [T, 2] float32 tile-local meters
    times: np.ndarray    # [T] float64 seconds
    accuracy: "np.ndarray | None" = None  # [T] f32 reported GPS accuracy
    #                                       (m); None ⇒ sigma_z everywhere

    @classmethod
    def from_json(cls, payload: dict, ts: TileSet) -> "Trace":
        pts = payload.get("trace", [])
        lonlat = np.array([[p["lon"], p["lat"]] for p in pts], np.float64)
        times = np.array([p.get("time", i) for i, p in enumerate(pts)], np.float64)
        if len(lonlat) == 0:
            lonlat = np.zeros((0, 2))
        xy = lonlat_to_xy(lonlat, np.asarray(ts.meta.origin_lonlat))
        # Optional per-point accuracy (the reference schema's "(accuracy)"
        # field): worse-than-sigma_z points get down-weighted emissions.
        acc = None
        if any("accuracy" in p for p in pts):
            acc = np.array([float(p.get("accuracy", 0.0)) for p in pts],
                           np.float32)
        return cls(uuid=str(payload.get("uuid", "")), xy=xy.astype(np.float32),
                   times=times, accuracy=acc)


@dataclass
class MatchedPoint:
    """Per-point match output (diagnostics / tests)."""

    edge: int
    offset: float
    chain_start: bool


class MatchBatch(_SequenceABC):
    """Columnar `match_many` result (jax fast path).

    Behaves as a sequence of per-trace ``list[SegmentRecord]`` — existing
    consumers index or iterate it unchanged — but the records live as flat
    numpy columns (``.columns``, sorted by trace index, drive order within
    a trace) and per-trace Python objects are built lazily on access.
    Throughput consumers (histogram updates, bulk publishers) should read
    ``.columns`` directly: building ~10^5 SegmentRecord objects per batch
    costs ~5× the C walk itself and was the round-2 e2e/decode gap.
    """

    def __init__(self, columns, n_traces: int):
        from reporter_tpu.matcher.native_walk import (RecordColumns,
                                                      record_bounds)
        assert isinstance(columns, RecordColumns)
        if columns.n_records and np.any(np.diff(columns.trace) < 0):
            # per-trace slicing below is searchsorted-based; an unsorted
            # trace column (e.g. raw Morton-remapped slice output that
            # skipped _merge_columns) would silently misattribute records
            raise ValueError("MatchBatch requires trace-sorted columns")
        self.columns = columns
        self._n = n_traces
        self._bounds = record_bounds(columns, n_traces)

    def __len__(self) -> int:
        return self._n

    def __getitem__(self, i):
        from reporter_tpu.matcher.native_walk import materialize_records

        if isinstance(i, slice):
            return [self[j] for j in range(*i.indices(self._n))]
        if i < 0:
            i += self._n
        if not 0 <= i < self._n:
            raise IndexError(i)
        return materialize_records(self.columns, int(self._bounds[i]),
                                   int(self._bounds[i + 1]))

    @property
    def n_records(self) -> int:
        return self.columns.n_records


def _accuracy_scale(accuracy: "np.ndarray | None", sigma_z: float,
                    n: int) -> np.ndarray:
    """[n] f32 emission distance scale: sigma_z / max(sigma_z, accuracy),
    1.0 where accuracy is absent. THE accuracy rule — shared by the batch
    path (_submit_many) and the ranked-paths path (match_topk) so they
    cannot drift; the CPU oracle implements the same rule as a per-point
    sigma (cpu_reference.match_trace_cpu)."""
    scale = np.ones(n, np.float32)
    if accuracy is None:
        return scale
    a = np.asarray(accuracy[:n], np.float32)
    sz = np.float32(sigma_z)
    scale[:len(a)] = sz / np.maximum(sz, a)
    return scale


def _dijkstra_route_fn(ts: TileSet, bound: float,
                       cache: "cpu_reference.DijkstraCache"):
    def route(e1: int, e2: int):
        if e1 == e2:
            return []
        reached = cache.reached(ts, e1, bound)
        if e2 not in reached:
            return None
        return cpu_reference.walk_prev(reached, e2)

    return route


class _LocalWire:
    """Single-device wire dispatch: the three jitted wire entries over
    tables staged on the default device, with the resolved kernel plan
    as their static. Duck-type shared with
    parallel.dp_e2e.DpWireMatcher (mesh-sharded rows) — _submit_many
    speaks to whichever the matcher was constructed with."""

    def __init__(self, tables, meta, params: MatcherParams,
                 spec: "tuple | None", plan):
        self.tables = tables
        self.meta = meta
        self.params = params
        self.spec = spec
        self.plan = plan

    def f32(self, pts, lens, acc):
        import jax.numpy as jnp

        from reporter_tpu.ops.match import match_batch_wire
        return match_batch_wire(
            jnp.asarray(pts), jnp.asarray(lens), self.tables, self.meta,
            self.params, None if acc is None else jnp.asarray(acc),
            spec=self.spec, plan=self.plan)

    def q16(self, pts_q, origins, lens, acc):
        import jax.numpy as jnp

        from reporter_tpu.ops.match import match_batch_wire_q
        return match_batch_wire_q(
            jnp.asarray(pts_q), jnp.asarray(origins), jnp.asarray(lens),
            self.tables, self.meta, self.params,
            None if acc is None else jnp.asarray(acc), spec=self.spec,
            plan=self.plan)

    def q8(self, deltas_q, origins, lens, acc):
        import jax.numpy as jnp

        from reporter_tpu.ops.match import match_batch_wire_q8
        return match_batch_wire_q8(
            jnp.asarray(deltas_q), jnp.asarray(origins), jnp.asarray(lens),
            self.tables, self.meta, self.params,
            None if acc is None else jnp.asarray(acc), spec=self.spec,
            plan=self.plan)


class SegmentMatcher:
    """The backend boundary (reference: SegmentMatcher.Match, SURVEY §3.1).

    ``mesh``: a jax.sharding.Mesh makes THIS matcher (and everything built
    on it — ReporterApp, StreamPipeline) the multi-device product path:
    every device dispatch shards batch rows over the mesh
    (parallel/dp_e2e), while the host pipeline around it is unchanged and
    the results are bit-identical to single-device (test-asserted).
    jax backend only."""

    def __init__(self, tileset: TileSet, config: Config | None = None,
                 metrics: MetricsRegistry | None = None,
                 mesh=None, staged_tables=None):
        import dataclasses as _dc

        self.ts = tileset
        self.config = (config or Config()).validate()
        # deployment env overrides (RTPU_SWEEP_AUTOTUNE, RTPU_DISPATCH_*)
        # apply at construction, mirrored back into self.config so
        # anything that introspects/serializes the matcher's config sees
        # the values that serve
        params = self.config.matcher.with_env_overrides()
        if params is not self.config.matcher:
            self.config = _dc.replace(self.config, matcher=params)
        self.params: MatcherParams = params
        self.metrics = metrics or MetricsRegistry()
        # online quality telemetry (round 18, reporter_tpu/quality/):
        # per-metro signal window + drift sentinel over every
        # match_many harvest — host-side only, so the compiled-shape
        # manifest and wire programs are untouched by construction
        from reporter_tpu.quality.monitor import QualityMonitor
        self.quality = QualityMonitor(tileset.name, self.metrics)
        # per-thread unmatched-point count from the latest jax harvest
        # (match_many runs concurrently under the scheduler — a plain
        # attribute would cross-talk between batches)
        self._quality_tl = threading.local()
        backend = self.config.matcher_backend
        self._native_walker = None
        # the dense sweep's kernel plan (round 17, matcher/autotune.py),
        # resolved below for the jax backend — the one thing that
        # chooses the kernel arm; None on reference_cpu
        self.tuned_plan = None
        self.tuned_report: dict = {}
        # dispatch-watchdog degradation state (jax backend): the fallback
        # oracle matcher is built lazily on the FIRST timeout — a healthy
        # deployment never pays for it
        self._fallback: "SegmentMatcher | None" = None
        # TWO locks on purpose: _fallback_lock serializes the oracle
        # (DijkstraCache is not thread-safe) and is held for a whole —
        # slow — fallback match; the watchdog's lock guards only the
        # breaker bookkeeping and is held for nanoseconds. One lock for
        # both would let a single in-progress oracle batch block every
        # concurrent healthy dispatch at its breaker check until it
        # spuriously timed out too.
        self._fallback_lock = locks.named_lock("matcher.fallback")
        # circuit breaker: count of watchdog threads abandoned and still
        # stuck inside a dispatch. Each pins its wave's traces until the
        # wedge clears, so the count must be BOUNDED — past the cap the
        # matcher degrades immediately instead of feeding more threads
        # (and more memory) to a dead link. (The watchdog's own lock
        # guards only that bookkeeping, held for nanoseconds — see the
        # _fallback_lock note above.)
        self._watchdog = AbandonedThreadWatchdog(
            cap=4, thread_name="dispatch-watchdog")
        if mesh is not None and backend != "jax":
            raise ValueError("mesh sharding requires matcher_backend='jax'")
        if backend == "jax":
            # packed-u32 result wire for big metros (ops.match.wire_spec):
            # -33% of the device→host bytes that bound big-tile decode
            from reporter_tpu.ops.match import wire_spec
            self._wire_spec = wire_spec(
                tileset.num_edges,
                float(tileset.edge_len.max()) if tileset.num_edges else 0.0)
            # params is a jit STATIC: the host-only watchdog knobs must
            # not reach the wire entries, or two deployments differing
            # only in dispatch_timeout_s would compile disjoint
            # executable populations (and the first faulted retry would
            # stall on a pointless recompile)
            wire_params = params.replace(dispatch_timeout_s=0.0,
                                         dispatch_fallback="retry")
            if mesh is None:
                # stage only the layout the resolved candidate backend
                # sweeps (the unused one is the largest table at metro
                # scale). ``staged_tables`` injects pre-placed device
                # arrays instead (the fleet residency manager stages —
                # and meters — the device_put itself; passing the same
                # values through the same wire programs is what makes
                # fleet-resident wire bytes identical to a dedicated
                # matcher's by construction).
                if staged_tables is not None:
                    # injected dicts may be pinned/cached from an older
                    # code version — fail loudly at the staging seam,
                    # not as kernel garbage (tiles.tileset version tag)
                    from reporter_tpu.tiles.tileset import (
                        check_staged_layout)
                    check_staged_layout(staged_tables)
                self._tables = (staged_tables if staged_tables is not None
                                else tileset.device_tables(
                                    self.params.candidate_backend))
                # per-metro self-tuning (round 17, matcher/autotune.py):
                # staged-dict plan → on-disk plan cache → a short
                # bounded calibration of real dispatches on THIS metro's
                # staged tables — every arm is wire-byte-identical, so
                # the pick is pure perf. Runs at construction so the
                # first served batch already rides the tuned
                # executables; the fleet's first promotion lands here
                # too (fleet/residency.py copies the plan back into the
                # host-pinned dict).
                self.tuned_plan = self._autotune_resolve(wire_params)
                self._wire = _LocalWire(self._tables, self.ts.meta,
                                        wire_params, self._wire_spec,
                                        self.tuned_plan)
            else:
                if staged_tables is not None:
                    raise ValueError(
                        "staged_tables injection is single-device only; "
                        "the mesh path shards its own tables")
                from reporter_tpu.parallel.dp_e2e import DpWireMatcher
                self._wire = DpWireMatcher(mesh, tileset, wire_params,
                                           self._wire_spec)
                self._tables = self._wire.tables    # mesh-replicated
                # the mesh path never tunes: the static default serves
                self.tuned_plan = self._wire.plan
                self.tuned_report = {"source": self.tuned_plan.source}
            self._route_fn = reach_route_fn(tileset)
            # Native batch walker (walker.cc): same walk as build_segments
            # with the reach-table route_fn, multithreaded across traces.
            # None ⇒ per-trace Python fallback.
            from reporter_tpu.matcher.native_walk import make_native_walker
            self._native_walker = make_native_walker(tileset)
        elif backend == "reference_cpu":
            if staged_tables is not None:
                raise ValueError(
                    "staged_tables requires matcher_backend='jax'")
            self._tables = None
            # One bound-aware Dijkstra memo shared by the Viterbi pass and
            # segment-build routing, across every trace this matcher sees.
            self._dij_cache = cpu_reference.DijkstraCache()
            # Segment-build routing must reach every transition the Viterbi
            # pass could have accepted, so reuse its worst-case bound.
            self._route_fn = _dijkstra_route_fn(
                tileset, bound=cpu_reference.viterbi_bound(
                    self.params.breakage_distance, self.params),
                cache=self._dij_cache)
        else:  # pragma: no cover - Config.validate rejects earlier
            raise ValueError(f"unknown backend {backend!r}")
        self.backend = backend

    @property
    def wire_mesh(self):
        """The jax.sharding.Mesh this matcher's wire dispatch shards
        over, or None on every single-device/reference path — THE
        public seam for layers that must co-shard with the matcher (the
        backfill engine places its aggregate partials on the same mesh
        so one constructor argument can never drift from the wire)."""
        wire = getattr(self, "_wire", None)
        return getattr(wire, "mesh", None)

    @property
    def slice_rows(self) -> int:
        """Rows one device dispatch carries: ``max_device_batch`` per
        device the wire shards rows over (1 without a mesh), at most
        ``_MAX_DISPATCH_ROWS``. The one reader of the knob: ``_plan``
        cuts each bucket group into slices of this many rows, so a group
        over one slice overlaps the host walk of a slice with the
        device's work on the next."""
        mesh = self.wire_mesh
        ndev = 1
        if mesh is not None:
            from reporter_tpu.parallel.dp_e2e import flat_device_count
            ndev = flat_device_count(mesh)
        return min(max(1, self.params.max_device_batch) * ndev,
                   _MAX_DISPATCH_ROWS)

    # ---- fleet residency (device-table paging) ---------------------------

    @property
    def tables_staged(self) -> bool:
        """False while this matcher's device tables are paged out
        (fleet cold tier). reference_cpu has no device tables and always
        reads False."""
        return self._tables is not None

    def unstage_tables(self) -> None:
        """Drop this matcher's device-table references (fleet demotion:
        HBM frees once any in-flight dispatch that captured them
        completes). The matcher object — walker, route tables, compiled
        executables — survives; a later ``restage_tables`` makes it
        serve again without recompiling, because the wire entries take
        tables as call ARGUMENTS, not closures, so same-shape restaged
        tables reuse the existing executables. jax single-device only
        (the mesh path owns sharded placement)."""
        if self.backend != "jax" or not isinstance(self._wire, _LocalWire):
            raise ValueError(
                "table paging requires the single-device jax backend")
        self._tables = None
        self._wire.tables = None

    def restage_tables(self, tables: dict) -> None:
        """Re-point the wire dispatch at freshly placed device tables
        (fleet promotion). ``tables`` must be the same pytree the
        matcher was built with — the residency manager re-device_puts
        the host-pinned copy, so values (and therefore result wire
        bytes) are identical across any number of evict→promote
        cycles."""
        if self.backend != "jax" or not isinstance(self._wire, _LocalWire):
            raise ValueError(
                "table paging requires the single-device jax backend")
        # the paging seam's stale-layout guard: a host dict pinned before
        # a table-layout change (fleet cold tier outliving a code change,
        # external caches) fails loudly here instead of shipping an
        # incomplete layout to the kernel
        from reporter_tpu.tiles.tileset import check_staged_layout
        check_staged_layout(tables)
        self._tables = tables
        self._wire.tables = tables

    # ---- per-metro self-tuning (round 17) --------------------------------

    def _autotune_resolve(self, wire_params: MatcherParams,
                          ) -> "autotune.TunedPlan":
        """Resolve this metro's kernel plan (see matcher/autotune.py for
        the resolution order) and record how. The calibration
        measure times ``CAL_DISPATCHES`` chained ``match_batch_wire_q``
        dispatches per candidate with ONE host sync on a deterministic
        synthetic batch over the metro's own geometry; each candidate
        runs under the shared dispatch watchdog so a wedged device
        degrades to the static default plan instead of hanging
        construction/promotion."""
        from reporter_tpu.matcher import autotune

        state: dict = {}

        def measure(plan: "autotune.TunedPlan") -> float:
            import time as _time

            import jax

            from reporter_tpu.ops.match import match_batch_wire_q

            if not state:
                pts_q, origins, lens = autotune.calibration_batch(self.ts)
                state["args"] = (jax.device_put(pts_q),
                                 jax.device_put(origins),
                                 jax.device_put(lens))
                np.asarray(state["args"][0][0, 0])      # sync the uploads
            args = state["args"]
            wire = match_batch_wire_q(*args, self._tables, self.ts.meta,
                                      wire_params, None,
                                      spec=self._wire_spec, plan=plan)
            np.asarray(wire)        # compile + first readback, untimed
            t0 = _time.perf_counter()
            for _ in range(autotune.CAL_DISPATCHES):
                wire = match_batch_wire_q(*args, self._tables,
                                          self.ts.meta, wire_params, None,
                                          spec=self._wire_spec, plan=plan)
            np.asarray(wire)        # ONE sync for the whole chain
            return (_time.perf_counter() - t0) / autotune.CAL_DISPATCHES

        plan, info = autotune.resolve_plan(self.params, self.ts,
                                           self._tables, measure,
                                           watchdog=self._watchdog)
        self.tuned_report = info
        errors = info.get("errors") or {}
        if errors:
            # a candidate that failed to lower or run is a device fault,
            # not a tuning outcome: make it visible, never just skip it
            import logging

            logging.getLogger("reporter_tpu.autotune").warning(
                "autotune: %d candidate(s) failed on %s: %s", len(errors),
                info.get("device"), errors)
            self.metrics.count("autotune_candidate_errors_total",
                               float(len(errors)))
        if plan.chosen:
            self.metrics.count(f"autotune_{plan.source}_total")
        return plan

    def tuned_plan_array(self) -> "np.ndarray | None":
        """The resolved plan as the staged-layout ``tuned_plan`` i32
        member, or None when none was chosen (the static default serves)
        — the fleet promotion path copies it back into the host-pinned
        dict so every later promotion pages already-tuned tables
        (fleet/residency.py)."""
        if self.tuned_plan is None or not self.tuned_plan.chosen:
            return None
        from reporter_tpu.matcher import autotune

        return autotune.plan_array(self.tuned_plan)

    def _require_staged(self) -> None:
        """A paged-out matcher must fail loudly, not with a shape error
        three layers down — the fleet router promotes (and leases)
        before dispatch, so reaching this unstaged means a caller
        bypassed the residency manager. Guards EVERY device entry:
        the watchdog path, the submit choke point (match_many /
        matched_points / the walk path all funnel through
        _submit_many), and match_topk's separate candidate build."""
        if self._tables is None:
            raise RuntimeError(
                f"matcher for {self.ts.name!r} has its device tables "
                "unstaged (fleet cold tier); promote before dispatching")

    # ---- single-trace API (reference parity) ----------------------------

    def match(self, trace_json: dict) -> dict:
        """Reference-shaped entry: trace JSON in, segments JSON out."""
        trace = Trace.from_json(trace_json, self.ts)
        records = self.match_trace(trace)
        return {
            "mode": self.config.service.mode,
            "segments": [r.to_json() for r in records],
        }

    def match_trace(self, trace: Trace) -> list[SegmentRecord]:
        return self.match_many([trace])[0]

    # ---- batched API (the TPU throughput path) --------------------------

    def match_many(self, traces: Sequence[Trace], *,
                   prepared: "PreparedBatch | None" = None,
                   ) -> "Sequence[list[SegmentRecord]]":
        """Sequence of per-trace record lists; the jax fast path returns a
        lazy columnar MatchBatch (read .columns for bulk consumers).
        ``prepared`` (from ``prepare_many`` on a read-ahead thread)
        skips the inline host prepare — dispatch submits the prebuilt
        slices; everything downstream is identical."""
        from reporter_tpu.utils.profiling import device_trace

        tr = tracing.tracer()
        # the capture opens first, so the span sees its profiler session
        # and the capture holds rtpu.match_many over its children
        with device_trace(), tr.span("match_many", traces=len(traces)):
            with self.metrics.stage("match"):
                if self.backend == "reference_cpu":
                    out = [self._match_cpu(t) for t in traces]
                else:
                    out = self._guarded_jax_many(traces, prepared)
            self.metrics.count("traces", len(traces))
            probes = sum(len(t.xy) for t in traces)
            self.metrics.count("probes", probes)
            if self.quality.enabled and len(traces):
                with tr.span("quality"):
                    self._record_quality(traces, out, probes)
        return out

    def _record_quality(self, traces: Sequence[Trace], result,
                        probes: int) -> None:
        """Quality telemetry for one harvested batch (round 18): signal
        extraction over the columns the harvest already built, the
        window/drift sentinel, and the sampled shadow-oracle hook. All
        host-side; the audit decision is one leaf-lock draw and the
        oracle itself runs on the auditor's own bounded thread."""
        from reporter_tpu.quality import audit as quality_audit
        from reporter_tpu.quality import signals as quality_signals

        nonempty = np.fromiter((len(t.xy) > 0 for t in traces), bool,
                               len(traces))
        hold = getattr(self._quality_tl, "unmatched_hold", None)
        self._quality_tl.unmatched_hold = None
        unmatched = hold.get("unmatched") if hold else None
        sig = quality_signals.extract(
            result, len(traces), probes, nonempty,
            max_speed=self.quality.max_speed_mps, unmatched=unmatched)
        self.quality.record(sig)
        if self.backend == "jax" and hold is not None:
            # auditing the oracle against itself is vacuous — and a
            # degraded batch (watchdog fallback: _degrade nulls the
            # hold) WAS the oracle, so sampling it would burn the audit
            # interval/duty budget on a guaranteed-0 compare and bias
            # the disagreement proxy toward 0 exactly while the device
            # path is broken (r18 review). Only real device harvests
            # (hold survives) are audit-eligible.
            quality_audit.maybe_audit(self, traces, result)

    def _guarded_jax_many(self, traces: Sequence[Trace],
                          prepared: "PreparedBatch | None" = None):
        """Device dispatch under the watchdog (dispatch_timeout_s > 0).

        The watchdog runs the dispatch on a fresh daemon thread and
        bounds the wait: a wedged transfer's failure mode is an infinite
        stall inside a host transfer, which no try/except can catch. On
        timeout the stuck thread is ABANDONED (daemon — it can never
        block exit) and the call degrades per ``dispatch_fallback``:

          "retry"          raise DispatchTimeout — the caller re-flushes
                           (streaming held-row release / scheduler
                           per-submission retry); bit-identical when the
                           link recovers, because retried waves re-run
                           the same wire program on the same rows;
          "reference_cpu"  serve THIS batch from the in-process exact-
                           Dijkstra oracle — slow, but link-free.

        The ``dispatch`` fault site fires here (inside the guarded body)
        so an injected hang stalls exactly where a wedged transfer
        would."""
        self._require_staged()
        # quality-telemetry side channel: the harvest (possibly on the
        # watchdog's daemon thread) drops its unmatched-point count into
        # this caller-thread-owned holder — a thread-local written on
        # the watchdog thread would never reach match_many
        hold: dict = {}
        self._quality_tl.unmatched_hold = hold
        timeout = float(self.params.dispatch_timeout_s)
        if timeout <= 0:
            faults.fire("dispatch")
            return self._match_jax_many(traces, hold, prepared)
        if self._watchdog.tripped:
            # circuit open: enough abandoned dispatches are already stuck
            # on the dead link — degrade IMMEDIATELY rather than pin yet
            # another thread + trace batch (a permanently hung device
            # must cost bounded memory, not one thread per retry).
            # Counted as a timeout TOO: /stats' dispatch_timeout must
            # keep moving while the breaker is open, or an operator
            # reads "timeouts stopped" at exactly the worst moment.
            self.metrics.count("dispatch_breaker_open")
            self.metrics.count("dispatch_timeout")
            tracing.post_mortem("breaker_open", failing="device_dispatch",
                                traces=len(traces),
                                abandoned=self._watchdog.abandoned)
            return self._degrade(traces, timeout)
        tracing.tracer().instant("device_dispatch",
                                 traces=len(traces))
        # (recorded BEFORE the guarded body: a dispatch that hangs
        # forever still shows up in the post-mortem as the last thing
        # the matcher started)
        out = self._watchdog.run(
            lambda: self._match_jax_many(traces, hold, prepared),
            timeout, fault_site="dispatch")
        if out is not watchdog_mod.TIMED_OUT:
            return out
        self.metrics.count("dispatch_timeout")
        tracing.post_mortem("dispatch_timeout", failing="device_dispatch",
                            traces=len(traces), timeout_s=timeout)
        # dead-link signal into the link-health record (round 15): the
        # watchdog saw the stall minutes before the low-duty probe
        # would — the sample keeps mood/gauges current; the post-mortem
        # above is the one dump for this event (linkhealth only dumps
        # for its OWN probe detections)
        linkhealth.note_dispatch_timeout("dispatch_timeout")
        return self._degrade(traces, timeout)

    def _degrade(self, traces: Sequence[Trace], timeout: float):
        """What a bounded dispatch becomes: the oracle (link-free) under
        dispatch_fallback='reference_cpu', else a retryable
        DispatchTimeout for the caller's held-row/isolation machinery."""
        # drop the quality side channel: the ABANDONED harvest thread
        # still holds the dict and may write its device-path unmatched
        # count later — folding that into the fallback result's signals
        # could trip a spurious quality_drift exactly when the link is
        # degraded (r18 review)
        self._quality_tl.unmatched_hold = None
        if self.params.dispatch_fallback == "reference_cpu":
            self.metrics.count("dispatch_fallback")
            fb = self._fallback_matcher()
            with self._fallback_lock:   # DijkstraCache isn't thread-safe
                return fb.match_many(traces)
        raise DispatchTimeout(
            f"device dispatch exceeded {timeout:.3f}s "
            f"({len(traces)} traces); wave released for retry")

    def _fallback_matcher(self) -> "SegmentMatcher":
        """The degradation target: an exact-Dijkstra oracle matcher over
        the same tileset/params, built on first use. Its own metrics
        registry (the outer call already counts traces/probes); callers
        serialize on ``self._fallback_lock`` — the shared DijkstraCache
        is not thread-safe and the scheduler's workers dispatch
        concurrently."""
        import dataclasses as _dc

        with self._fallback_lock:
            if self._fallback is None:
                self._fallback = SegmentMatcher(
                    self.ts, _dc.replace(self.config,
                                         matcher_backend="reference_cpu"))
                # oracle instances keep their quality telemetry OFF
                # (r18 review): their signals would publish to a
                # registry nothing scrapes, and their drift sentinel
                # would consume the process 'quality' fault-site
                # counter / dump budget from inside the degrade path —
                # the OUTER matcher records this batch's signals either
                # way
                self._fallback.quality.enabled = False
        return self._fallback

    def matched_points(self, trace: Trace) -> list[MatchedPoint]:
        """Per-point decode (no segment association) — test/diagnostic hook."""
        if self.backend != "jax":
            raise NotImplementedError(
                "matched_points decodes through the device path; "
                "construct the matcher with matcher_backend='jax'")
        trip = self._decode_many([trace])[0]
        return [MatchedPoint(int(e), float(o), bool(s))
                for e, o, s in zip(*trip)]

    def match_topk(self, trace: Trace, exact: bool = False,
                   ) -> list[tuple[float, list[MatchedPoint]]]:
        """K-best path interpretations of one trace (Meili TopKSearch
        analog). Contract (oracle-pinned by tests/test_topk_oracle.py):
        the best path is the exact global optimum; with ``exact=False``
        (default, cheapest) each alternate is the exact optimal path
        ending at one of the final chain's terminal candidates — a subset
        of true K-best; with ``exact=True`` the alternates are the final
        chain's EXACT K globally-best paths (list Viterbi — the carry
        grows a rank axis, ops/hmm.viterbi_kbest_paths), which dominates
        Meili's penalized re-search approximation. jax backend only — the
        reference_cpu backend raises NotImplementedError by contract (it
        exists as a fidelity oracle for the primary path, and its own
        oracle for TopK is the exact list-Viterbi in the test above).
        Diagnostic surface — the reporting pipeline uses the best path.
        Defined over at most one max bucket (1024 points): K-best chunks
        do not compose into a global K-best the way match_many's
        independent-HMM chunks do, so longer traces are REJECTED rather
        than silently truncated — decimate or split the trace first."""
        if self.backend != "jax":
            raise NotImplementedError("match_topk requires the jax backend")
        if len(trace.xy) > _BUCKETS[-1]:
            raise ValueError(
                f"match_topk is defined over ≤{_BUCKETS[-1]} points "
                f"(got {len(trace.xy)}); ranked alternates do not compose "
                "across chunks — split or decimate the trace, or use "
                "match_many for the best-path decode")
        self._require_staged()
        import jax.numpy as jnp

        from reporter_tpu.ops.hmm import (viterbi_kbest_paths,
                                          viterbi_topk_paths)
        from reporter_tpu.ops.match import batch_candidates

        xy = trace.xy
        T = max(len(xy), 1)
        pts = np.zeros((1, _bucket_len(T), 2), np.float32)
        pts[0, :len(xy)] = xy
        valid = np.zeros((1, pts.shape[1]), bool)
        valid[0, :len(xy)] = True
        pj, vj = jnp.asarray(pts), jnp.asarray(valid)
        cands = batch_candidates(pj, vj, self._tables, self.ts.meta,
                                 self.params, self.tuned_plan)
        p = self.params
        trace_cands = type(cands)(*(x[0] for x in cands))
        if trace.accuracy is not None:
            # same emission down-weighting match() applies — the ranked
            # paths must agree with the primary decode
            scale = _accuracy_scale(trace.accuracy[:len(xy)], p.sigma_z,
                                    pts.shape[1])
            trace_cands = trace_cands._replace(
                dist=trace_cands.dist * jnp.asarray(scale)[:, None])
        if exact:
            choices, scores, ok = viterbi_kbest_paths(
                trace_cands, pj[0], vj[0], self._tables, p.sigma_z, p.beta,
                p.max_route_distance_factor, p.breakage_distance,
                p.backward_slack, p.interpolation_distance,
                num_paths=p.max_candidates)
        else:
            choices, scores, ok = viterbi_topk_paths(
                trace_cands, pj[0], vj[0], self._tables, p.sigma_z, p.beta,
                p.max_route_distance_factor, p.breakage_distance,
                p.backward_slack, p.interpolation_distance)
        ce = np.asarray(cands.edge[0])
        co = np.asarray(cands.offset[0])
        out = []
        for r in range(choices.shape[0]):
            if not bool(ok[r]):
                continue
            ch = np.asarray(choices[r])[:len(xy)]
            pts_r = [MatchedPoint(
                int(ce[t, c]) if c >= 0 else -1,
                float(co[t, c]) if c >= 0 else 0.0, False)
                for t, c in enumerate(ch)]
            out.append((float(scores[r]), pts_r))
        return out

    # ---- internals -------------------------------------------------------

    def _match_cpu(self, trace: Trace) -> list[SegmentRecord]:
        pts = cpu_reference.match_trace_cpu(self.ts, trace.xy.astype(np.float64),
                                            self.params, self._dij_cache,
                                            accuracy=trace.accuracy)
        chains = _to_chains(pts, trace.times)
        return build_segments(self.ts, chains, self._route_fn,
                              self.params.backward_slack)

    def plan_submit(self, traces: Sequence[Trace]):
        """The submit PLAN: work list + Morton-sorted bucket slices.

        Returns (work, sliced): work[w] = (trace index, chunk offset,
        xy); sliced = [(bucket, [work indices])] in submission order.
        Pure host bookkeeping — the first half of the round-20
        prepare/dispatch seam (see PreparedSlice)."""
        self._require_staged()
        with tracing.tracer().span("plan"):
            return self._plan(traces)

    def _plan(self, traces: Sequence[Trace]):
        max_b = _BUCKETS[-1]
        # Traces beyond the largest bucket are decoded in consecutive chunks
        # (each chunk is an independent HMM; at most the segment spanning a
        # chunk boundary is reported partial). (trace index, chunk offset).
        work: list[tuple[int, int, np.ndarray]] = []
        for i, t in enumerate(traces):
            if len(t.xy) <= max_b:
                work.append((i, 0, t.xy))
            else:
                for lo in range(0, len(t.xy), max_b):
                    work.append((i, lo, t.xy[lo:lo + max_b]))

        by_bucket: dict[int, list[int]] = {}
        for w, (_, _, xy) in enumerate(work):
            by_bucket.setdefault(_bucket_len(len(xy)), []).append(w)
        # Spatial sort within each bucket (Morton code of the first point):
        # neighbouring traces share point-chunks in the flattened dense
        # sweep, so co-locating them tightens chunk bboxes and lets the
        # kernel's block culling skip more of the map.
        keys = _morton_keys(work)
        for ws in by_bucket.values():
            arr = np.asarray(ws)
            ws[:] = arr[np.argsort(keys[arr], kind="stable")].tolist()
        chunk = self.slice_rows
        sliced = [(b, ws[i:i + chunk])
                  for b, ws in sorted(by_bucket.items())
                  for i in range(0, len(ws), chunk)]
        if any(len(ws) > chunk for ws in by_bucket.values()):
            # one plan per match_many call: a call whose harvest overlaps
            # the device's work on its next slice
            self.metrics.count("sliced_calls_total")
        return work, sliced

    def prepare_submit_slice(self, traces: Sequence[Trace], work,
                             b: int, ws: "list[int]") -> PreparedSlice:
        """Host-side prepare of one plan slice — NO device work, safe on
        a read-ahead thread.

        The per-slice prepare — pad → i16 quantize → i8 delta pack with
        the exact overflow fallbacks — is ONE implementation in two
        forms (matcher/native_prepare): the C entry when the library is
        up, the byte-identical numpy reference otherwise. Which form
        served is counted (prepare_native_total / prepare_python_total)
        so a silent native-build failure degrading to Python shows at
        /stats and /metrics.
        """
        from reporter_tpu.matcher import native_prepare

        B = len(ws)
        xys = [work[w][2] for w in ws]
        with tracing.tracer().span("prepare"):
            # Quantized infeed (half the host→device bytes): i16 0.25 m
            # offsets from per-trace origins, unless some trace spans
            # beyond the i16 range (±8.19 km from its first point);
            # preferred form is i8 per-step DELTAS of the i16 quanta —
            # integer diffs cumsum back to the exact same absolutes on
            # device, so it is bit-identical to the i16 path at half the
            # bytes. The mode decision + buffer fill is the prepare
            # entry (native C pass, or the byte-identical numpy form).
            prep = native_prepare.prepare_slice(xys, b)
            if prep is None:
                prep = native_prepare.prepare_slice_python(xys, b)
                self.metrics.count("prepare_python_total")
            else:
                self.metrics.count("prepare_native_total")
            mode, pts, lens, origins, payload = prep
            # Per-point GPS accuracy → emission distance scaling (see
            # ops/match.match_traces). None for accuracy-less slices: the
            # scale-free executable is traced separately, so the common
            # case pays neither transfer nor compute for the feature.
            scale = None
            if any(traces[work[w][0]].accuracy is not None for w in ws):
                scale = np.ones((B, b), np.float32)
                for r, w in enumerate(ws):
                    i, lo, xy = work[w]
                    a = traces[i].accuracy
                    if a is not None:
                        scale[r] = _accuracy_scale(
                            a[lo:lo + len(xy)], self.params.sigma_z, b)
        return PreparedSlice(b, list(ws), mode, pts, lens, origins,
                             payload, scale)

    def submit_prepared(self, ps: PreparedSlice):
        """Async dispatch of a prepared slice. The wire programs are the
        EXISTING entries (`ops.match.wire_from_*` via self._wire) — the
        seam adds no wire fork, only a submission boundary. Returns the
        in-flight wire device array (np.asarray harvests it)."""
        self.metrics.count("dispatch_slices_total")
        with tracing.tracer().span("dispatch"):
            if ps.mode == 2:
                return self._wire.q8(ps.payload, ps.origins, ps.lens,
                                     ps.scale)
            if ps.mode == 1:
                return self._wire.q16(ps.payload, ps.origins, ps.lens,
                                      ps.scale)
            return self._wire.f32(ps.pts, ps.lens, ps.scale)

    def _submit_many(self, traces: Sequence[Trace]):
        """Submit every trace slice to the device (async dispatches).

        Returns (work, inflight): work[w] = (trace index, chunk offset,
        xy); inflight = [(slice work indices, wire device array)] in
        submission order. Harvesting an inflight wire (np.asarray) blocks
        on the link; callers decide what to overlap with that wait.

        Two phases: submit every slice (dispatches are async), then
        harvest. Device compute and device→host transfers of slice k
        overlap with the transfer of slice k-1 — otherwise the
        host-device round-trip serializes with compute.
        """
        work, sliced = self.plan_submit(traces)
        inflight = []
        for b, ws in sliced:
            ps = self.prepare_submit_slice(traces, work, b, ws)
            inflight.append((ws, self.submit_prepared(ps)))
        return work, inflight

    # prepare_many is safe to call from a read-ahead thread; match_many
    # consumers probe for this attribute before preparing ahead (a
    # monkeypatched or duck-typed matcher without the seam gets the
    # plain match_many call, no prepared kwarg).
    supports_prepared = True

    def prepare_many(self, traces: Sequence[Trace],
                     ) -> "PreparedBatch | None":
        """Pure host prepare of a whole batch, ahead of dispatch (r22).

        Returns None (declining — the caller falls back to the plain
        ``match_many(traces)`` call) unless the interleaved columnar
        path would serve this batch: jax backend, tables staged, native
        walker up, >1 trace, every trace within the largest bucket.
        The decline checks mirror ``_match_jax_many``'s interleave
        predicate so a prepared batch is only ever handed to the code
        path that can consume it. Checks ``self._tables`` directly
        rather than ``_require_staged`` — a fleet-demoted matcher on
        the read-ahead thread must decline quietly (the promotion/lease
        discipline re-runs prepare inline after promote), not raise on
        a thread with no held lease."""
        if (self.backend != "jax" or self._tables is None
                or self._native_walker is None or len(traces) <= 1
                or any(len(t.xy) > _BUCKETS[-1] for t in traces)):
            return None
        work, sliced = self.plan_submit(traces)
        slices = [self.prepare_submit_slice(traces, work, b, ws)
                  for b, ws in sliced]
        return PreparedBatch(work, slices)

    def _decode_many(self, traces: Sequence[Trace]):
        """JAX decode for a list of traces → per-trace (edges, offsets,
        chain_starts) numpy triples, bucketed by padded length."""
        from reporter_tpu.ops.match import unpack_wire

        work, inflight = self._submit_many(traces)
        per_trace: list[list[tuple[int, Any]]] = [[] for _ in traces]

        # Same overlap trick as the walk path: unpack + per-trace split of
        # slice k runs in a worker thread while slice k+1's wire bytes
        # stream back over the link.
        def split_slice(_k, ws, arr):
            # mesh path pads rows to a device-count multiple: drop them
            with tracing.tracer().span("unpack"):
                edges, offs, starts = unpack_wire(arr[:len(ws)],
                                                  self._wire_spec)
            for r, w in enumerate(ws):
                i, lo, xy = work[w]
                T = len(xy)
                per_trace[i].append(
                    (lo, (edges[r, :T], offs[r, :T], starts[r, :T])))

        _harvest_overlapped(inflight, split_slice)

        out: list[Any] = []
        for chunks in per_trace:
            chunks.sort(key=lambda c: c[0])
            if len(chunks) == 1:
                out.append(chunks[0][1])
            else:
                out.append(tuple(np.concatenate(parts)
                                 for parts in zip(*(c[1] for c in chunks))))
        return out

    def _match_jax_many(self, traces: Sequence[Trace],
                        quality_hold: "dict | None" = None,
                        prepared: "PreparedBatch | None" = None,
                        ) -> "Sequence[list[SegmentRecord]]":
        # Interleaved harvest + walk: np.asarray on the next slice blocks
        # on the host-device transfer with the GIL released, and the
        # C++ walk is a GIL-releasing ctypes call — so a one-worker thread
        # walks slice k's records while slice k+1's wire bytes stream back.
        # On a one-core host this hides most of the walk behind the
        # transfer wait. Falls back to decode-then-walk when there is no
        # native walker or a trace needs cross-slice chunk reassembly.
        interleave = (self._native_walker is not None and len(traces) > 1
                      and all(len(t.xy) <= _BUCKETS[-1] for t in traces))
        if not interleave:
            with self.metrics.stage("decode"):
                decoded = self._decode_many(traces)
            unmatched = sum(int((e < 0).sum()) for e, _, _ in decoded)
            self.metrics.count("unmatched_points", unmatched)
            if quality_hold is not None:
                quality_hold["unmatched"] = unmatched
            with self.metrics.stage("walk"), tracing.tracer().span("walk"):
                return self._walk_decoded(traces, decoded)

        with self.metrics.stage("decode"):
            if prepared is not None:
                # read-ahead path: the host prepare already ran (same
                # calls, same order — see PreparedBatch); only the async
                # dispatches happen here, in the prepared slice order.
                work = prepared.work
                inflight = [(ps.ws, self.submit_prepared(ps))
                            for ps in prepared.slices]
            else:
                work, inflight = self._submit_many(traces)
        slice_cols: list = [None] * len(inflight)
        unmatched = 0

        def walk_slice(k, ws, arr):
            nonlocal unmatched
            cols, un = self.walk_wire_columns(traces, work, ws, arr)
            unmatched += un
            slice_cols[k] = cols

        with self.metrics.stage("walk"):
            _harvest_overlapped(inflight, walk_slice)
        self.metrics.count("unmatched_points", unmatched)
        if quality_hold is not None:
            quality_hold["unmatched"] = unmatched
        with tracing.tracer().span("merge"):
            merged = _merge_columns(slice_cols)
        return MatchBatch(merged, len(traces))

    def walk_wire_columns(self, traces: Sequence[Trace], work,
                          ws: "list[int]", arr: np.ndarray):
        """Unpack + native column-walk of ONE harvested slice's wire
        bytes → (RecordColumns with GLOBAL trace indices, unmatched
        point count). The harvest half of the round-20 seam — requires
        the native walker (the columnar product path's precondition)."""
        from reporter_tpu.ops.match import unpack_wire

        tr = tracing.tracer()
        with tr.span("walk"):
            with tr.span("unpack"):
                # mesh path pads rows to a device-count multiple: drop them
                edges, offs, starts = unpack_wire(arr[:len(ws)],
                                                  self._wire_spec)
            B, T = edges.shape
            times = np.zeros((B, T), np.float64)
            pad = 0
            for r, w in enumerate(ws):
                i, lo, xy = work[w]
                times[r, :len(xy)] = traces[i].times[lo:lo + len(xy)]
                pad += T - len(xy)          # padded tail decodes unmatched
            unmatched = int((edges < 0).sum()) - pad
            cols = self._native_walker.walk_columns(
                edges, offs, starts, times, self.params.backward_slack)
            # slice row → global trace index (ws is Morton-sorted)
            row_to_trace = np.asarray([work[w][0] for w in ws], np.int32)
            return cols._replace(trace=row_to_trace[cols.trace]), unmatched

    def _walk_decoded(self, traces: Sequence[Trace],
                      decoded) -> list[list[SegmentRecord]]:
        if self._native_walker is not None:
            B = len(traces)
            tmax = max((len(e) for e, _, _ in decoded), default=1) or 1
            edges = np.full((B, tmax), -1, np.int32)
            offs = np.zeros((B, tmax), np.float32)
            starts = np.zeros((B, tmax), np.uint8)
            times = np.zeros((B, tmax), np.float64)
            for b, (trace, (e, o, s)) in enumerate(zip(traces, decoded)):
                t = len(e)
                edges[b, :t] = e
                offs[b, :t] = o
                starts[b, :t] = s
                times[b, :t] = trace.times[:t]
            return self._native_walker.walk(edges, offs, starts, times,
                                            self.params.backward_slack)
        results = []
        for trace, (edges, offs, starts) in zip(traces, decoded):
            pts = [(int(e), float(o), bool(s))
                   for e, o, s in zip(edges, offs, starts)]
            chains = _to_chains(pts, trace.times)
            results.append(build_segments(self.ts, chains, self._route_fn,
                                          self.params.backward_slack))
        return results


def _harvest_overlapped(inflight, per_slice) -> None:
    """Harvest inflight wires in submit order with ONE worker thread:
    ``np.asarray`` on slice k+1's wire blocks on the transfer with the
    GIL released while the worker processes slice k —
    the shared overlap discipline of the walk and decode paths.
    ``per_slice(k, ws, host_array)`` runs on the worker; exceptions
    propagate via the futures. Each readback is a ``readback`` span on
    the caller's thread (it holds the wait for the slice's device work
    and the transfer)."""
    from concurrent.futures import ThreadPoolExecutor

    tr = tracing.tracer()
    with ThreadPoolExecutor(max_workers=1) as pool:
        futs = []
        for k, (ws, wire) in enumerate(inflight):
            with tr.span("readback"):
                host = np.asarray(wire)
            futs.append(pool.submit(per_slice, k, ws, host))
        for f in futs:
            f.result()


def _merge_columns(slices: list):
    """Concatenate per-slice RecordColumns (trace already remapped to
    global indices) and stable-sort rows by trace so per-trace ranges are
    contiguous. Pure numpy — ~10 ms for 10^5 records, vs ~1 s for the
    per-object path it replaces."""
    from reporter_tpu.matcher.native_walk import RecordColumns, empty_columns

    slices = [c for c in slices if c is not None and c.n_records]
    if not slices:
        return empty_columns()
    if len(slices) == 1:
        cat = slices[0]
    else:
        way_offs = []
        base = 0
        for c in slices:
            way_offs.append(c.way_off[:-1] + base)
            base += int(c.way_off[-1])
        way_offs.append(np.asarray([base], np.int64))
        cat = RecordColumns(
            *(np.concatenate([getattr(c, f) for c in slices])
              for f in ("trace", "segment_id", "start_time", "end_time",
                        "length", "queue_length", "internal")),
            np.concatenate(way_offs),
            np.concatenate([c.way_ids for c in slices]))
    order = np.argsort(cat.trace, kind="stable")
    if np.array_equal(order, np.arange(len(order))):
        return cat
    lens = cat.way_off[1:] - cat.way_off[:-1]
    new_lens = lens[order]
    new_off = np.concatenate([np.zeros(1, np.int64), np.cumsum(new_lens)])
    # gather each reordered record's way-id run from the old flat array
    idx = (np.repeat(cat.way_off[:-1][order], new_lens)
           + np.arange(int(new_off[-1]), dtype=np.int64)
           - np.repeat(new_off[:-1], new_lens))
    return RecordColumns(
        cat.trace[order], cat.segment_id[order], cat.start_time[order],
        cat.end_time[order], cat.length[order], cat.queue_length[order],
        cat.internal[order], new_off, cat.way_ids[idx])


def _bucket_len(n: int) -> int:
    for b in _BUCKETS:
        if n <= b:
            return b
    return _BUCKETS[-1]


def _morton_keys(work) -> np.ndarray:
    """Interleaved-bit keys of every work item's first point at 64 m
    resolution (biased positive so negative tile-local coordinates keep
    locality) — the same curve as the device-side segment blocking
    (ops.dense_candidates._morton), so host trace sorting matches the
    layout it exploits. One numpy pass + one _morton call: the earlier
    per-trace Python version cost ~0.5 s on a 16k-trace batch — a third
    of the host submit leg, ON the e2e critical path (submit precedes
    the first device dispatch). The key computation rides native_prepare
    (bit-equal C form when the library is up; the numpy reference
    otherwise)."""
    from reporter_tpu.matcher import native_prepare

    first = np.zeros((len(work), 2), np.float64)
    for w, (_, _, xy) in enumerate(work):
        if len(xy):
            first[w] = xy[0]
    keys = native_prepare.morton_keys(first)
    if keys is None:
        keys = native_prepare.morton_keys_python(first)
    return keys


def _to_chains(pts: list[tuple[int, float, bool]], times: np.ndarray,
               ) -> list[MatchedChain]:
    """Group per-point (edge, offset, chain_start) into MatchedChains,
    dropping unmatched points."""
    chains: list[MatchedChain] = []
    cur: MatchedChain | None = None
    for t, (e, off, start) in enumerate(pts):
        if e < 0:
            continue
        if cur is None or start:
            cur = MatchedChain(edges=[], offsets=[], times=[])
            chains.append(cur)
        cur.edges.append(int(e))
        cur.offsets.append(float(off))
        cur.times.append(float(times[t]))
    return chains
