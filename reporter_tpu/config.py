"""Configuration for reporter_tpu.

Mirrors the reference's two-layer config (SURVEY.md §5 "Config / flag system"):
a structured matcher/compiler config (the ``valhalla.json`` analog — sigma_z,
beta, search radius, costing-ish knobs) plus environment variables for service
wiring (``DATASTORE_URL``, port, thread count).
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Any


# The narrow-grid launch width LADDER (round 17): a kernel plan's
# ``nj_cap`` (matcher/autotune.TunedPlan) may only take these rungs, so
# the dense sweep's compiled-shape universe stays finite and
# manifest-pinned (analysis/compile_manifest.py enumerates ladder ×
# kernel arm). Results are EXACT at any rung — hits
# sort first in the culled id list and the kernel falls back to the
# full-width launch whenever a chunk's hits exceed the cap (the round-5
# lax.cond) — so rung choice is a pure perf decision the per-metro
# autotuner (matcher/autotune.py) measures. Lives HERE (not in
# ops/dense_candidates) so the tuner's plan encoding stays
# jax-import-free.
SWEEP_NJ_CAP_RUNGS = (64, 128, 256)


@dataclass(frozen=True)
class MatcherParams:
    """HMM map-matching parameters (the meili section of valhalla.json).

    Defaults follow Meili's documented defaults (SURVEY.md §2.2: emission =
    Gaussian(GPS error; sigma_z), transition = |route_dist − gc_dist| / beta).
    """

    sigma_z: float = 4.07          # GPS noise std-dev (m), emission model
    beta: float = 3.0              # transition scale (m)
    search_radius: float = 50.0    # candidate search radius (m)
    max_candidates: int = 8        # top-K candidates per point
    candidate_backend: str = "auto"  # "dense" = gather-free pallas sweep
                                   # (ops/dense_candidates.py, ~50x faster
                                   # than gathers on TPU); "grid" = cell-row
                                   # gather (ops/candidates.py, ~50x faster
                                   # than the sweep on CPU); "auto" picks by
                                   # the active jax backend
    sweep_autotune: bool = True    # per-metro self-tuning of the dense
                                   # sweep's kernel plan (arm, lowp
                                   # dtype, nj-cap rung; matcher/
                                   # autotune.py): at staging time serve
                                   # a cached plan or measure real
                                   # dispatches on the metro's own
                                   # tables — legal because every arm is
                                   # wire-byte-identical. Only measures
                                   # on accelerator backends with the
                                   # dense sweep resolved. False = the
                                   # static default plan. Either way a
                                   # plan pinned in the staged tables
                                   # serves (autotune.pin_plan).
    breakage_distance: float = 2000.0  # consecutive points farther apart break the HMM chain
    max_route_distance_factor: float = 5.0  # route dist > factor*gc ⇒ transition disallowed
    interpolation_distance: float = 10.0    # points closer than this are interpolated, not matched
    backward_slack: float = 10.0   # same-edge backward jitter tolerated as zero-cost (m);
                                   # GPS noise shifts projections backwards between samples —
                                   # Meili absorbs this via input interpolation, we absorb it
                                   # in the transition model (ops/hmm.route_distance)
    max_device_batch: int = 2048   # rows per data-parallel device in one
                                   # dispatch: a bucket group is cut into
                                   # slices of this many rows times the
                                   # devices the wire shards rows over,
                                   # at most 4,096 rows a dispatch
                                   # (SegmentMatcher.slice_rows), so the
                                   # host walk of slice k runs while the
                                   # device works on slice k+1. A group
                                   # at or under one slice (every /report
                                   # batch) dispatches whole. 2,048 was
                                   # the fastest of 512, 1,024 and 2,048
                                   # on one v5e chip (PERF.md)
    dispatch_timeout_s: float = 0.0  # device-dispatch watchdog: a wedged
                                   # device or transfer HANGS rather than
                                   # erroring, so a wedged dispatch must
                                   # be timed out, not caught. 0 = off (the
                                   # default: zero overhead, zero behavior
                                   # change). On timeout the dispatch raises
                                   # DispatchTimeout (matcher/api.py) —
                                   # streaming releases the wave's held rows
                                   # for retry, the scheduler retries per
                                   # submission. Set it ABOVE the worst-case
                                   # cold jit compile for your shapes (or
                                   # warm up first): the watchdog cannot
                                   # tell a compiling dispatch from a hung
                                   # one, and a too-tight timeout churns
                                   # retries until the cache warms.
    dispatch_fallback: str = "retry"  # what a timed-out dispatch degrades
                                   # to: "retry" = raise and let the caller
                                   # re-flush (bit-identical when the link
                                   # recovers); "reference_cpu" = serve the
                                   # batch from the in-process exact-
                                   # Dijkstra oracle (slow, link-free) —
                                   # graceful degradation when the device
                                   # is gone for good

    def replace(self, **kw: Any) -> "MatcherParams":
        return dataclasses.replace(self, **kw)

    def with_env_overrides(self, env: dict[str, str] | None = None,
                           ) -> "MatcherParams":
        """Deployment env overrides (the matcher analog of
        ServiceConfig.with_env_overrides): only set variables apply.
        RTPU_SWEEP_AUTOTUNE=0|1 turns the per-metro plan tuner off or
        on; RTPU_DISPATCH_TIMEOUT_S and RTPU_DISPATCH_FALLBACK set the
        dispatch watchdog. Values are validated strictly: a typo raises
        instead of silently serving the default.
        """
        e = os.environ if env is None else env
        kw: dict[str, Any] = {}
        if "RTPU_SWEEP_AUTOTUNE" in e:
            # tracing.env_flag is THE boolean parse (round-14 env-flag
            # lint); strict=True fails loudly on typos
            from reporter_tpu.utils.tracing import env_flag

            try:
                kw["sweep_autotune"] = env_flag(e["RTPU_SWEEP_AUTOTUNE"],
                                                strict=True)
            except ValueError:
                raise ValueError(
                    f"RTPU_SWEEP_AUTOTUNE={e['RTPU_SWEEP_AUTOTUNE']!r}: "
                    "use 0/1") from None
        if "RTPU_DISPATCH_TIMEOUT_S" in e:
            t = float(e["RTPU_DISPATCH_TIMEOUT_S"])
            if t < 0:
                raise ValueError(
                    f"RTPU_DISPATCH_TIMEOUT_S={t}: must be >= 0")
            kw["dispatch_timeout_s"] = t
        if "RTPU_DISPATCH_FALLBACK" in e:
            fb = e["RTPU_DISPATCH_FALLBACK"] or "retry"
            if fb not in ("retry", "reference_cpu"):
                raise ValueError(
                    f"RTPU_DISPATCH_FALLBACK={fb!r}: use 'retry' or "
                    "'reference_cpu'")
            kw["dispatch_fallback"] = fb
        return dataclasses.replace(self, **kw) if kw else self

    @classmethod
    def preset(cls, mode: str) -> "MatcherParams":
        """Mode-keyed matcher preset (the reference's per-mode Valhalla
        costing → meili tuning, SURVEY.md §2.1 "mode costing"). GPS noise
        is mode-independent (sigma_z stays), but plausible movement is
        not: slower modes cover less ground between samples, so chain
        breakage and route-deviation tolerances tighten, and the
        candidate radius narrows (a pedestrian 50 m from a path is more
        likely on another path than badly measured).

        Use with a tileset compiled for the same mode
        (``compile_network(net, params, mode=...)``) — the preset tunes
        the HMM; the tileset's subgraph decides legality.
        """
        if mode == "auto":
            return cls()
        if mode == "bicycle":
            return cls(search_radius=40.0, breakage_distance=1200.0,
                       max_route_distance_factor=4.0)
        if mode == "foot":
            return cls(search_radius=30.0, breakage_distance=400.0,
                       max_route_distance_factor=3.0,
                       interpolation_distance=5.0)
        raise ValueError(f"unknown mode {mode!r}; "
                         "one of ['auto', 'bicycle', 'foot']")


@dataclass(frozen=True)
class CompilerParams:
    """Offline tile-compiler parameters (the mjolnir/osmlr analog, SURVEY.md §7.1)."""

    cell_size: float = 64.0        # spatial-grid cell edge (m)
    cell_capacity: int = 64        # max line-segments indexed per cell (padded, -1 sentinel)
    index_radius: float = 50.0     # grid registration dilation (m): every segment is
                                   # indexed in all cells within this distance of its
                                   # bbox, so a query reads ONE cell row and still sees
                                   # every segment within search_radius <= index_radius
    reach_radius: float = 600.0    # reachability precompute radius (m)
    reach_max: int = 128           # max reachable targets kept per NODE row.
                                   # Node-keyed tables make a wide row cheap
                                   # (~3× fewer rows than per-edge); 128
                                   # keeps every audited transition at
                                   # 5s-sparse urban sampling (see
                                   # tiles/reach_audit.py; 32 truncated
                                   # coverage to ~170 m and dropped ~2% of
                                   # oracle-accepted transitions)
    osmlr_max_length: float = 1000.0  # OSMLR segment chaining target length (m)
    use_native: bool = True        # use the C++ reach/grid builder when available


@dataclass(frozen=True)
class ServiceConfig:
    """Service wiring (env-var layer of the reference, SURVEY.md §3.2)."""

    datastore_url: str = ""        # empty ⇒ publishing disabled (logged only)
    port: int = 8002
    threads: int = 4
    cache_ttl: float = 60.0        # per-uuid partial-trace cache TTL (s)
    cache_max_uuids: int = 100_000
    min_segment_length: float = 0.0
    mode: str = "auto"             # report transport mode tag
    # Request batching (service/scheduler.py). "scheduler" = continuous
    # in-flight batching: SLO-deadline batch close, shape-bucketed
    # padding, multiple device batches overlapping the link RTT.
    # "combine" = the round-4 queue-and-combine leader (one batch in
    # flight) — kept for A/B benches and as the conservative fallback.
    batching: str = "scheduler"
    batch_close_ms: float = 5.0    # a partial batch closes this many ms
    #                                after its oldest request was admitted
    #                                (the SLO deadline: a lone request is
    #                                never stuck waiting for peers)
    max_batch_traces: int = 256    # close-by-size threshold (traces)
    max_inflight_batches: int = 2  # device batches allowed in flight —
    #                                the serving twin of streaming's
    #                                pipeline_depth (submit wave N while
    #                                wave N-1 rides the link RTT)
    admission_queue_limit: int = 8192  # queued traces admitted before the
    #                                    service sheds with 503 (bounded
    #                                    memory; counted rejections)
    # Pipelined wave prepare (r22): run the PURE host prepare for wave
    # N+1 (column gather + lonlat→xy + native quantize/pack through the
    # matcher's prepared seam) on a read-ahead thread while wave N
    # occupies the device. Stateful steps (cache merge/retain, commit
    # floor, checkpoint) stay strictly in wave order, so wire bytes and
    # report streams are bit-identical to the serial loop — test- and
    # bench-asserted. False = the serial loop, kept as the same-run A/B
    # arm (r7-scheduler style). Only engages where overlap exists
    # (streaming pipeline_depth >= 1; scheduler prefab path).
    pipeline_prepare: bool = True
    # Publisher resilience (service/datastore.py). Defaults keep the
    # pre-chaos behavior exactly (one attempt, failures counted+dropped):
    # retries/dead-letter are DEPLOYMENT policy, opted into per worker.
    publish_retries: int = 0       # extra POST attempts per batch after
    #                                the first fails (bounded exponential
    #                                backoff with deterministic jitter —
    #                                faults.backoff_schedule)
    publish_backoff_ms: float = 50.0    # backoff base (doubles per retry)
    publish_backoff_cap_ms: float = 2000.0  # backoff ceiling
    publish_backoff_jitter: float = 0.1     # +[0, jitter)x seeded jitter
    # Span tracing / flight recorder (utils/tracing.py). The recorder is
    # PROCESS-GLOBAL (the fault sites in matcher/publisher/scheduler all
    # write the same ring); these knobs only ever turn it ON — an
    # env-enabled recorder (RTPU_TRACE=1) is never disabled by a second
    # component constructed with the defaults.
    trace: bool = False            # record host-side spans (consume /
    #                                prepare / device match / report
    #                                build / publish, wave-tagged).
    #                                Off = one attribute read per call
    #                                site (the 100k+ pps offer must not
    #                                pay for idle observability)
    trace_ring: int = 4096         # flight-recorder span capacity
    trace_dir: str = ""            # non-empty ⇒ post-mortem Chrome-trace
    #                                dumps are written here automatically
    #                                on dispatch-timeout, breaker-open,
    #                                dead-letter, and admission-shed
    #                                events (and on demand via
    #                                tracing.tracer().dump())
    dead_letter_dir: str = ""      # non-empty ⇒ batches that exhaust their
    #                                retries are SPOOLED to disk and
    #                                replayed automatically after the next
    #                                successful POST — an outage sheds to
    #                                disk, not to /dev/null. ONE DIR PER
    #                                WORKER PROCESS (like --checkpoint):
    #                                the spool file carries no inter-
    #                                process locking, and two workers
    #                                sharing it would corrupt each
    #                                other's torn-tail truncation and
    #                                prefix rewrites

    def with_env_overrides(self, env: dict[str, str] | None = None) -> "ServiceConfig":
        """Apply env vars on top of this config; only set variables override."""
        e = os.environ if env is None else env
        kw: dict[str, Any] = {}
        if "DATASTORE_URL" in e:
            kw["datastore_url"] = e["DATASTORE_URL"]
        if "REPORTER_PORT" in e:
            kw["port"] = int(e["REPORTER_PORT"])
        if "THREAD_POOL_COUNT" in e:
            kw["threads"] = int(e["THREAD_POOL_COUNT"])
        if "PARTIAL_TRACE_TTL" in e:
            kw["cache_ttl"] = float(e["PARTIAL_TRACE_TTL"])
        if "REPORTER_MODE" in e:
            kw["mode"] = e["REPORTER_MODE"]
        if "REPORTER_BATCHING" in e:
            kw["batching"] = e["REPORTER_BATCHING"]
        if "REPORTER_BATCH_CLOSE_MS" in e:
            kw["batch_close_ms"] = float(e["REPORTER_BATCH_CLOSE_MS"])
        if "REPORTER_MAX_INFLIGHT" in e:
            kw["max_inflight_batches"] = int(e["REPORTER_MAX_INFLIGHT"])
        if "DATASTORE_RETRIES" in e:
            kw["publish_retries"] = int(e["DATASTORE_RETRIES"])
        if "DATASTORE_BACKOFF_MS" in e:
            kw["publish_backoff_ms"] = float(e["DATASTORE_BACKOFF_MS"])
        if "DATASTORE_DEAD_LETTER_DIR" in e:
            kw["dead_letter_dir"] = e["DATASTORE_DEAD_LETTER_DIR"]
        if "RTPU_PIPELINE_PREPARE" in e:
            from reporter_tpu.utils.tracing import env_flag

            try:
                kw["pipeline_prepare"] = env_flag(
                    e["RTPU_PIPELINE_PREPARE"], strict=True)
            except ValueError:
                raise ValueError(
                    f"RTPU_PIPELINE_PREPARE={e['RTPU_PIPELINE_PREPARE']!r}: "
                    "expected a boolean (1/0/true/false/yes/no/on/off)")
        if "RTPU_TRACE" in e:
            from reporter_tpu.utils.tracing import env_flag

            kw["trace"] = env_flag(e["RTPU_TRACE"])
        if "RTPU_TRACE_RING" in e:
            kw["trace_ring"] = int(e["RTPU_TRACE_RING"])
        if "RTPU_TRACE_DIR" in e:
            kw["trace_dir"] = e["RTPU_TRACE_DIR"]
        return dataclasses.replace(self, **kw) if kw else self

    @classmethod
    def from_env(cls, env: dict[str, str] | None = None) -> "ServiceConfig":
        return cls().with_env_overrides(env)


@dataclass(frozen=True)
class StreamingConfig:
    """Streaming-ingest wiring (the reference's Kafka layer analog,
    SURVEY.md §3.3): partition counts and the matcher worker's flush policy
    ("when enough points/time elapsed: Match(buffered trace)")."""

    num_partitions: int = 4        # uuid-hash partitions (Kafka partition analog)
    poll_max_records: int = 4096   # records consumed per partition per step
    flush_min_points: int = 16     # buffered points per uuid that trigger a match
    flush_max_age: float = 30.0    # seconds a buffer may age before forced flush
    speed_bins: tuple[float, ...] = (0., 2.5, 5., 7.5, 10., 12.5, 15., 17.5,
                                     20., 25., 30., 40.)  # m/s histogram edges
    queue_bins: tuple[float, ...] = (0., 10., 25., 50., 100., 200.,
                                     400.)  # meters-of-queue histogram edges
    hist_flush_interval: float = 60.0  # seconds between per-segment speed
                                       # histogram flushes to the datastore
                                       # (0 = manual flush only)
    # Pipelined flush (columnar worker): how many flush waves may be in
    # flight on the device while the main loop keeps consuming and the
    # publisher thread POSTs completed waves. 0 = the sequential
    # consume→match→publish loop (the dict worker's only shape); 1 =
    # double buffering, the firehose deployment default — per-wave link
    # RTT and datastore RTT amortize across waves instead of serializing.
    pipeline_depth: int = 1
    # Adaptive wave sizing (columnar worker, opt-in): the controller
    # raises the effective flush_min_points while broker lag is rising
    # (bigger waves amortize per-flush overheads) and decays it toward
    # wave_target_latency once caught up (smaller waves bound the
    # probe→report buffer wait). flush_min_points is the starting point.
    wave_autotune: bool = False
    wave_min_points: int = 16          # controller floor (points/vehicle)
    wave_max_points: int = 960         # controller ceiling
    wave_target_latency: float = 2.0   # p50 probe→report target (s)


@dataclass(frozen=True)
class Config:
    """Top-level structured config (the valhalla.json analog)."""

    matcher: MatcherParams = field(default_factory=MatcherParams)
    compiler: CompilerParams = field(default_factory=CompilerParams)
    service: ServiceConfig = field(default_factory=ServiceConfig)
    streaming: StreamingConfig = field(default_factory=StreamingConfig)
    matcher_backend: str = "jax"   # {"jax", "reference_cpu"} — the backend boundary

    @classmethod
    def for_mode(cls, mode: str, **kw: Any) -> "Config":
        """Config serving one transport mode: the mode-keyed MatcherParams
        preset + the service mode tag (reports carry it; requests naming a
        different mode are rejected). Pair with a tileset compiled via
        ``compile_network(net, params, mode=...)`` — one deployment serves
        one mode, like the reference's per-mode valhalla config."""
        p = MatcherParams.preset(mode)    # validates the mode name
        svc = dataclasses.replace(kw.pop("service", ServiceConfig()),
                                  mode=mode)
        return cls(matcher=p, service=svc, **kw)

    def validate(self) -> "Config":
        """Cross-section invariants. The grid's single-cell candidate gather
        is only a superset of the radius ball when segment registration was
        dilated by at least the search radius (tiles/compiler._build_grid);
        the dense sweep visits every in-radius segment regardless."""
        if self.matcher.candidate_backend not in ("auto", "dense", "grid"):
            raise ValueError(
                f"unknown candidate_backend "
                f"{self.matcher.candidate_backend!r}; "
                "use 'auto', 'dense' or 'grid'")
        # Early error for explicitly-grid configs only: "auto" may resolve
        # to dense (no coverage requirement), and the authoritative check
        # against the ACTUAL tileset's index_radius happens at trace time
        # (ops/match._check_grid_coverage) — this one guards the common
        # case where one Config drives both compiler and matcher.
        if (self.matcher.candidate_backend == "grid"
                and self.compiler.index_radius < self.matcher.search_radius):
            raise ValueError(
                f"compiler.index_radius ({self.compiler.index_radius}) must be "
                f">= matcher.search_radius ({self.matcher.search_radius}) for "
                "the single-cell grid gather to cover the search radius")
        if self.matcher_backend not in ("jax", "reference_cpu"):
            raise ValueError(f"unknown matcher_backend {self.matcher_backend!r}")
        svc = self.service
        if svc.batching not in ("scheduler", "combine"):
            raise ValueError(f"unknown service.batching {svc.batching!r}; "
                             "use 'scheduler' or 'combine'")
        if svc.batch_close_ms <= 0:
            raise ValueError("service.batch_close_ms must be > 0")
        if svc.max_batch_traces < 1 or svc.max_inflight_batches < 1:
            raise ValueError("service.max_batch_traces and "
                             "service.max_inflight_batches must be >= 1")
        if svc.admission_queue_limit < 1:
            raise ValueError("service.admission_queue_limit must be >= 1")
        if svc.publish_retries < 0:
            raise ValueError("service.publish_retries must be >= 0")
        if svc.publish_backoff_ms <= 0 or svc.publish_backoff_cap_ms <= 0:
            raise ValueError("service.publish_backoff_ms and "
                             "publish_backoff_cap_ms must be > 0")
        if svc.publish_backoff_jitter < 0:
            raise ValueError("service.publish_backoff_jitter must be >= 0")
        if svc.trace_ring < 1:
            raise ValueError("service.trace_ring must be >= 1")
        if self.matcher.dispatch_timeout_s < 0:
            raise ValueError("matcher.dispatch_timeout_s must be >= 0")
        if self.matcher.dispatch_fallback not in ("retry", "reference_cpu"):
            raise ValueError(
                f"unknown matcher.dispatch_fallback "
                f"{self.matcher.dispatch_fallback!r}; use 'retry' or "
                "'reference_cpu'")
        s = self.streaming
        if s.num_partitions < 1 or s.poll_max_records < 1 or s.flush_min_points < 1:
            raise ValueError(
                "streaming num_partitions / poll_max_records / "
                "flush_min_points must all be >= 1")
        if s.flush_max_age <= 0:
            raise ValueError("streaming.flush_max_age must be > 0")
        if s.pipeline_depth < 0:
            raise ValueError("streaming.pipeline_depth must be >= 0")
        if not (1 <= s.wave_min_points <= s.wave_max_points):
            raise ValueError(
                "streaming wave bounds need 1 <= wave_min_points "
                "<= wave_max_points")
        if s.wave_target_latency <= 0:
            raise ValueError("streaming.wave_target_latency must be > 0")
        for bins in ("speed_bins", "queue_bins"):
            edges = getattr(s, bins)
            if len(edges) < 1 or list(edges) != sorted(set(edges)):
                raise ValueError(f"streaming.{bins} must be strictly ascending")
        return self

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2, sort_keys=True)

    @classmethod
    def from_json(cls, text: str) -> "Config":
        raw = json.loads(text)
        streaming = dict(raw.get("streaming", {}))
        for bins in ("speed_bins", "queue_bins"):
            if bins in streaming:
                streaming[bins] = tuple(streaming[bins])
        return cls(
            matcher=MatcherParams(**raw.get("matcher", {})),
            compiler=CompilerParams(**raw.get("compiler", {})),
            service=ServiceConfig(**raw.get("service", {})),
            streaming=StreamingConfig(**streaming),
            matcher_backend=raw.get("matcher_backend", "jax"),
        )

    @classmethod
    def load(cls, path: str | None = None) -> "Config":
        """Load from a JSON file if given/exists; env vars that are actually
        set override the file's service section (the reference's two-layer
        precedence, SURVEY.md §5)."""
        if path and os.path.exists(path):
            with open(path) as f:
                cfg = cls.from_json(f.read())
        else:
            cfg = cls()
        cfg = dataclasses.replace(cfg,
                                  service=cfg.service.with_env_overrides(),
                                  matcher=cfg.matcher.with_env_overrides())
        return cfg.validate()
