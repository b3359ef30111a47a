"""Per-metro self-tuning dispatch plans (round 17, ROADMAP item 1).

Three rounds of dense-sweep perf work (r8 two-level subcull, r13 MXU
arm, the bf16 lowp dtype) left several kernel arms, while the
arm/dtype/launch-width balance point is per-tile geometry — exactly
the filter/refine trade RTNN (arXiv:2201.01366) and SeGraM
(arXiv:2205.05883) show must be tuned per workload, not fixed.
Because all three arms are wire-BYTE-identical (asserted by
``detail.sweep_ab`` through evict→promote paging) and the narrow-grid
cap is exact at any ladder rung (the round-5 ``lax.cond`` fallback),
plan choice is a PURE perf decision: measure, pick, persist.

The plan space is finite by construction — ``CANDIDATE_ARMS`` (every
legal kernel-arm × lowp dtype combination) × the
``config.SWEEP_NJ_CAP_RUNGS`` ladder — and the committed compile-shape
manifest (analysis/compile_manifest.py) enumerates it, so tuning can
never grow the executable population past the pinned universe.

A ``TunedPlan`` is the ONE carrier of the kernel choice: the matcher
resolves it once and hands it to the wire entries as their jit static
(ops/match.py), which pass its arm, lowp and nj_cap to
``find_candidates_dense``. Resolution order (``resolve_plan``):

  1. a plan chosen earlier and riding the staged dict as a
     host-readable ``tuned_plan`` member: a pre-tuned dict paged by the
     fleet, an external cache, or a plan pinned by hand (``pin_plan``,
     source ``explicit`` — how tests and the chip smoke select an arm).
     It serves on every backend, CPU interpret included;
  2. the on-disk plan cache, keyed on tile content fingerprint + device
     kind — the fleet pages already-tuned tables without re-measuring;
  3. a short, bounded calibration on an accelerator: ``CAL_DISPATCHES``
     real dispatches per candidate on the metro's OWN staged tables,
     two phases (every arm at the default rung, then the winning arm
     across the remaining rungs), each candidate bounded by the shared
     ``AbandonedThreadWatchdog`` so a wedged device degrades to the
     static default plan instead of hanging promotion;
  4. the static default ``TunedPlan()`` — also what serves with the
     tuner off, on CPU (the grid gather or interpret timings), and on
     the mesh path, which never tunes.

The chosen plan persists as the ``tuned_plan`` member of the
version-tagged staged-layout dict (tiles/tileset.py, layout v3) — an
i32[5] vector ``[plan_version, arm, lowp, nj_cap, source]`` that rides
device_put / the multimetro stack as an unused wire argument, so a plan
change can never change wire bytes — plus the on-disk cache for fresh
processes. Calibration is injectable-timer deterministic for CPU tests:
``calibrate``/``resolve_plan`` take the measure callable, so the full
selection logic runs under synthetic timings with zero device access.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from dataclasses import dataclass
from typing import Callable

import numpy as np

from reporter_tpu.config import SWEEP_NJ_CAP_RUNGS, MatcherParams

__all__ = [
    "PLAN_VERSION", "CANDIDATE_ARMS", "CAL_DISPATCHES", "CAL_TIMEOUT_S",
    "CAL_BATCH_SHAPE", "TunedPlan", "CalibrationAborted", "default_plan",
    "default_plan_array", "plan_array", "plan_from_array", "plan_json",
    "pin_plan", "calibrate", "resolve_plan", "tile_fingerprint",
    "device_key", "cache_dir", "load_cached_plan", "store_cached_plan",
    "stamp_cached_plan", "calibration_batch",
]

PLAN_VERSION = 1

# encoding tables for the staged i32 vector (APPEND, never reorder —
# a persisted plan must decode identically forever)
_ARM_NAMES = ("block", "subcull", "mxu")
_LOWP_NAMES = ("off", "bf16")
_SOURCE_NAMES = ("default", "measured", "cache", "staged", "timeout",
                 "cpu", "explicit", "off")
# sources of a plan that was CHOSEN (measured, or pinned by hand), as
# against the static default served for want of a choice
_CHOSEN_SOURCES = ("measured", "cache", "staged", "explicit")

# every LEGAL (arm, lowp) combination, in tie-break preference order:
# the static default arm first, so equal timings keep today's behavior.
# block has no low-precision pass and the MXU arm's operand dtype is
# what lowp selects there. The one check of legal combinations:
# plan_from_array and load_cached_plan refuse anything else.
CANDIDATE_ARMS = (
    ("subcull", "off"),
    ("subcull", "bf16"),
    ("block", "off"),
    ("mxu", "off"),
    ("mxu", "bf16"),
)

# calibration budget: dispatches timed per candidate (one extra
# warm/compile dispatch precedes them, untimed), and the per-candidate
# watchdog bound. The bound must sit ABOVE a cold jit compile of one
# plan variant — the watchdog cannot tell a compiling dispatch from a
# hung one (the dispatch_timeout_s caveat, config.py).
CAL_DISPATCHES = 4
CAL_TIMEOUT_S = 120.0

# calibration dispatch shape [B, T]: B=128 is a scheduler trace rung and
# T=64 a matcher point bucket, so calibration reuses the pinned
# compiled-shape grid instead of growing it (compile_manifest records
# this shape next to the plan space)
CAL_BATCH_SHAPE = (128, 64)


class CalibrationAborted(RuntimeError):
    """A calibration measurement was abandoned (watchdog timeout or an
    already-open breaker): the whole calibration aborts and the static
    default plan serves — a wedged device must degrade promotion, never
    hang it."""


@dataclass(frozen=True)
class TunedPlan:
    """One point of the plan space: the dense sweep's kernel arm
    (``block`` whole-block, ``subcull`` two-level culling, ``mxu``
    matmul coarse pass), its low-precision dtype and its narrow-grid
    rung. ``TunedPlan()`` is the static default and the degradation
    target. ``source`` records how the plan was resolved; it takes no
    part in equality or hashing, so plans that run the same kernel are
    the same jit static (one executable, whatever their provenance)."""

    arm: str = "subcull"
    lowp: str = "off"
    nj_cap: int = 128               # == ops/dense_candidates._NJ_CAP
    source: str = dataclasses.field(default="default", compare=False)

    @property
    def chosen(self) -> bool:
        """Measured, cached, staged or pinned — not the static default
        served for want of a choice (default/timeout/cpu/off)."""
        return self.source in _CHOSEN_SOURCES

    @property
    def label(self) -> str:
        """Compact display form, e.g. ``mxu+bf16@128`` — the bench leg's
        candidate key and the summary token's plan slot."""
        tail = "+bf16" if self.lowp == "bf16" else ""
        return f"{self.arm}{tail}@{self.nj_cap}"


def default_plan(source: str = "default") -> TunedPlan:
    return TunedPlan(source=source)


# ---------------------------------------------------------------------------
# staged-dict encoding (the tiles/tileset layout-v3 member)

def plan_array(plan: TunedPlan) -> np.ndarray:
    """``tuned_plan`` as the staged i32[5] vector
    ``[plan_version, arm, lowp, nj_cap, source]`` — rides device_put and
    the multimetro stack like every other staged member."""
    return np.asarray([PLAN_VERSION, _ARM_NAMES.index(plan.arm),
                       _LOWP_NAMES.index(plan.lowp), int(plan.nj_cap),
                       _SOURCE_NAMES.index(plan.source)], np.int32)


def default_plan_array() -> np.ndarray:
    """What ``TileSet.host_tables`` stamps: the static default plan —
    the tuner (or a cache hit) overwrites it at staging time."""
    return plan_array(default_plan())


def plan_from_array(arr) -> "TunedPlan | None":
    """Decode a staged ``tuned_plan`` member. None when the leaf is not
    host-readable (a device-resident jnp array — reading it back would
    cost a link RTT on the promote path, the staged_layout discipline),
    malformed, or from a different plan version."""
    if not isinstance(arr, np.ndarray) or arr.shape != (5,) \
            or arr.dtype.kind not in "iu":
        return None
    v, arm, lowp, cap, src = (int(x) for x in arr)
    if v != PLAN_VERSION:
        return None
    if not (0 <= arm < len(_ARM_NAMES) and 0 <= lowp < len(_LOWP_NAMES)
            and 0 <= src < len(_SOURCE_NAMES)):
        return None
    if cap not in SWEEP_NJ_CAP_RUNGS:
        return None
    plan = TunedPlan(arm=_ARM_NAMES[arm], lowp=_LOWP_NAMES[lowp],
                     nj_cap=cap, source=_SOURCE_NAMES[src])
    if (plan.arm, plan.lowp) not in CANDIDATE_ARMS:
        return None
    return plan


def pin_plan(tables: dict, plan: TunedPlan) -> dict:
    """A copy of a staged dict whose ``tuned_plan`` member pins ``plan``
    (source ``explicit``): a matcher built on it serves that plan on
    every backend and never measures — THE way to select a kernel arm
    by hand. The member stays host-readable even when the other leaves
    are device arrays."""
    return dict(tables, tuned_plan=plan_array(
        dataclasses.replace(plan, source="explicit")))


def plan_json(plan: "TunedPlan | None") -> "dict | None":
    """The bench/occupancy artifact form."""
    if plan is None:
        return None
    return {"arm": plan.arm, "lowp": plan.lowp, "nj_cap": plan.nj_cap,
            "source": plan.source, "label": plan.label}


# ---------------------------------------------------------------------------
# the calibration harness

def calibrate(measure: Callable[[TunedPlan], "float | None"],
              rungs: "tuple[int, ...]" = SWEEP_NJ_CAP_RUNGS,
              arms: "tuple[tuple[str, str], ...]" = CANDIDATE_ARMS,
              ) -> "tuple[TunedPlan, dict]":
    """Pick the fastest legal plan from measured per-candidate times.

    ``measure(plan) -> seconds`` (lower is better); None or an exception
    skips that candidate (recorded — an arm that fails to lower must not
    sink the calibration, the sweep_ab arm-error discipline);
    ``CalibrationAborted`` aborts the WHOLE calibration to the static
    default (watchdog timeout / open breaker — a wedged device).

    Two bounded phases keep the dispatch budget small: every arm at the
    default rung first, then only the winning arm across the remaining
    rungs — ≤ ``len(arms) + len(rungs) - 1`` candidates total, each
    costing one warm/compile dispatch + ``CAL_DISPATCHES`` timed ones.
    Ties break toward the earlier candidate (the static default arm
    leads the enumeration), so equal timings keep today's behavior —
    and make selection deterministic under an injected timer.
    """
    cap0 = TunedPlan().nj_cap
    if cap0 not in rungs:
        cap0 = rungs[0]
    report: dict = {"candidates": {}, "errors": {}, "measured": 0}

    def timed(plan: TunedPlan) -> "float | None":
        try:
            dt = measure(plan)
        except CalibrationAborted:
            raise
        except Exception as exc:  # noqa: BLE001 — recorded, not raised
            report["errors"][plan.label] = repr(exc)[:200]
            return None
        if dt is None:
            return None
        report["measured"] += 1
        report["candidates"][plan.label] = {
            "device_ms_per_dispatch": round(dt * 1e3, 3)}
        return dt

    try:
        # phase 1: every legal arm at the default rung
        best: "tuple[float, TunedPlan] | None" = None
        for arm, lowp in arms:
            plan = TunedPlan(arm=arm, lowp=lowp, nj_cap=cap0,
                             source="measured")
            dt = timed(plan)
            if dt is not None and (best is None or dt < best[0]):
                best = (dt, plan)
        if best is None:
            report["note"] = "every candidate failed — static default"
            return default_plan(), report
        # phase 2: the winning arm across the remaining rungs (skip the
        # phase-1 rung — NOT the evolving winner's, or a better early
        # rung would make the loop re-measure cap0)
        winner = best[1]
        for cap in rungs:
            if cap == cap0:
                continue
            plan = dataclasses.replace(winner, nj_cap=int(cap))
            dt = timed(plan)
            if dt is not None and dt < best[0]:
                best = (dt, plan)
    except CalibrationAborted as exc:
        report["note"] = f"calibration aborted ({exc}) — static default"
        return default_plan(source="timeout"), report
    report["winner"] = best[1].label
    return best[1], report


# ---------------------------------------------------------------------------
# the on-disk plan cache (tile fingerprint × device kind)

def tile_fingerprint(ts) -> str:
    """Content fingerprint of the geometry the plan depends on: the
    segment arrays the dense sweep stages, plus the kernel blocking
    constants (a retuned _SBLK/_SUB invalidates cached plans). ~10 ms
    at metro scale — paid once per staging, amortized by the cache."""
    from reporter_tpu.ops import dense_candidates as dc

    h = hashlib.sha256()
    h.update(f"{ts.name}|{ts.num_edges}|{len(ts.seg_len)}"
             f"|{dc._SBLK}|{dc._SUB}|v{PLAN_VERSION}".encode())
    for arr in (ts.seg_a, ts.seg_b):
        h.update(np.ascontiguousarray(arr, np.float32).tobytes())
    return h.hexdigest()[:24]


def device_key() -> str:
    """What makes a measured plan portable: backend + device kind."""
    import jax

    devs = jax.devices()
    kind = devs[0].device_kind if devs else "none"
    return f"{jax.default_backend()}:{kind}"


def cache_dir() -> str:
    """RTPU_AUTOTUNE_CACHE, else a per-user cache directory."""
    if "RTPU_AUTOTUNE_CACHE" in os.environ:
        return os.environ["RTPU_AUTOTUNE_CACHE"]
    return os.path.join(os.path.expanduser("~"), ".cache",
                        "reporter_tpu", "autotune")


def _cache_path(directory: str, fingerprint: str, devkey: str) -> str:
    dev = "".join(c if c.isalnum() else "_" for c in devkey)
    return os.path.join(directory, f"{fingerprint}-{dev}.json")


def load_cached_plan(fingerprint: str, devkey: str,
                     directory: "str | None" = None,
                     ) -> "TunedPlan | None":
    """A previously measured plan for this (tile, device), or None.
    Corrupt/foreign files read as a miss, never an error."""
    path = _cache_path(directory or cache_dir(), fingerprint, devkey)
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    if doc.get("plan_version") != PLAN_VERSION:
        return None
    p = doc.get("plan") or {}
    try:
        plan = TunedPlan(arm=p["arm"], lowp=p["lowp"],
                         nj_cap=int(p["nj_cap"]), source="cache")
    except (KeyError, TypeError, ValueError):
        return None
    if (plan.arm, plan.lowp) not in CANDIDATE_ARMS \
            or plan.nj_cap not in SWEEP_NJ_CAP_RUNGS:
        return None
    return plan


def store_cached_plan(plan: TunedPlan, report: dict, fingerprint: str,
                      devkey: str, directory: "str | None" = None) -> None:
    """Persist a measured plan (atomic tmp+replace; best-effort — a
    read-only cache dir must not fail staging)."""
    directory = directory or cache_dir()
    path = _cache_path(directory, fingerprint, devkey)
    doc = {"plan_version": PLAN_VERSION, "device": devkey,
           "fingerprint": fingerprint, "plan": plan_json(plan),
           "candidates": report.get("candidates", {}),
           "errors": report.get("errors", {})}
    try:
        os.makedirs(directory, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1)
        os.replace(tmp, path)
    except OSError:
        pass


def stamp_cached_plan(ts, host_tables: dict, params: MatcherParams,
                      directory: "str | None" = None) -> "TunedPlan | None":
    """OFFLINE pre-staging helper: if a cached plan exists for (this
    tile, this device), stamp it into a host-pinned dict so any matcher
    later built on it resolves the plan from the staged member and
    never measures. For external cold-tier/table-cache builders; the
    fleet promotion path deliberately does NOT call this —
    ``device_key()`` touches ``jax.devices()``, which may be the
    process's first backend init, so only call it when a backend is
    known-alive. No-op when the tuner would not act anyway (autotune
    off / grid-only dict)."""
    if not getattr(params, "sweep_autotune", True) \
            or "tuned_plan" not in host_tables:
        return None
    try:
        plan = load_cached_plan(tile_fingerprint(ts), device_key(),
                                directory)
    except Exception:   # noqa: BLE001 — a broken cache must not block paging
        return None
    if plan is not None:
        host_tables["tuned_plan"] = plan_array(plan)
    return plan


# ---------------------------------------------------------------------------
# resolution (the one entry SegmentMatcher construction calls)

def resolve_plan(params: MatcherParams, ts, tables,
                 measure: Callable[[TunedPlan], "float | None"],
                 watchdog=None, timeout_s: float = CAL_TIMEOUT_S,
                 directory: "str | None" = None,
                 backend: "str | None" = None,
                 devkey: "str | None" = None,
                 ) -> "tuple[TunedPlan, dict]":
    """(plan to serve, info) — the module docstring's resolution order.
    The plan is always one to serve: the static default when nothing
    was chosen, with ``source`` (and ``info["source"]``) saying why.

    ``measure``/``backend``/``devkey`` are injectable — CPU tests drive
    the full resolution (cache hit, staged plan, watchdog degradation)
    with a synthetic timer and no device."""
    # 1) a chosen plan already riding the staged dict, on any backend
    arr = tables.get("tuned_plan") if hasattr(tables, "get") else None
    staged = plan_from_array(arr)
    if staged is not None and staged.chosen:
        source = "explicit" if staged.source == "explicit" else "staged"
        return (dataclasses.replace(staged, source=source),
                {"source": source})

    if not getattr(params, "sweep_autotune", True):
        return default_plan("off"), {"source": "off"}
    if backend is None:
        import jax

        backend = jax.default_backend()
    resolved = params.candidate_backend
    if resolved == "auto":
        resolved = "grid" if backend == "cpu" else "dense"
    if resolved != "dense" or backend == "cpu":
        # the CPU short-circuit: the grid gather has no kernel arms, and
        # interpret-mode timings on a CPU host are meaningless
        return default_plan("cpu"), {"source": "cpu"}

    fingerprint = tile_fingerprint(ts)
    if devkey is None:
        devkey = device_key()

    def _stamp(plan: TunedPlan) -> None:
        # persist into the staged dict when its leaf is host-backed (a
        # device-put dict keeps its leaf; the applied plan still rides
        # the matcher and the disk cache)
        if hasattr(tables, "get") \
                and isinstance(tables.get("tuned_plan"), np.ndarray):
            tables["tuned_plan"] = plan_array(plan)

    # 2) the on-disk plan cache
    cached = load_cached_plan(fingerprint, devkey, directory)
    if cached is not None:
        _stamp(cached)
        return cached, {"source": "cache", "device": devkey}

    # 3) measure — each candidate bounded by the shared watchdog
    import time as _time

    def guarded(plan: TunedPlan) -> "float | None":
        if watchdog is None:
            return measure(plan)
        from reporter_tpu.utils import watchdog as watchdog_mod

        if watchdog.tripped:
            raise CalibrationAborted("watchdog breaker open")
        out = watchdog.run(lambda: measure(plan), timeout_s,
                           fault_site="autotune")
        if out is watchdog_mod.TIMED_OUT:
            raise CalibrationAborted(
                f"candidate {plan.label} exceeded {timeout_s:.0f}s")
        return out

    t0 = _time.perf_counter()
    plan, report = calibrate(guarded)
    info = {"source": plan.source, "device": devkey,
            "calibration_seconds": round(_time.perf_counter() - t0, 2),
            "calibration_dispatches":
                report["measured"] * (CAL_DISPATCHES + 1),
            **report}
    if plan.source == "measured":
        _stamp(plan)
        store_cached_plan(plan, report, fingerprint, devkey, directory)
    # 4) timeout / all-failed degradation: the static default serves
    return plan, info


# ---------------------------------------------------------------------------
# the calibration workload

def calibration_batch(ts, shape: "tuple[int, int]" = CAL_BATCH_SHAPE,
                      seed: int = 1234):
    """Deterministic synthetic probe batch over the metro's OWN
    geometry: seeded random walks (~8 m steps) from sampled node
    positions, in the q16 infeed form (i16 quanta + f32 origins + i32
    lens) the measure dispatch feeds ``match_batch_wire_q``. Walks stay
    well inside the ±8.19 km i16 envelope."""
    B, T = shape
    rng = np.random.default_rng(seed)
    n = max(1, len(ts.node_xy))
    base = np.asarray(ts.node_xy, np.float64)[rng.integers(0, n, B)]
    steps = rng.normal(0.0, 8.0, (B, T, 2))
    steps[:, 0] = 0.0
    walk = base[:, None, :] + np.cumsum(steps, axis=1)
    origins = walk[:, 0, :].astype(np.float32)
    from reporter_tpu.ops.match import OFFSET_QUANTUM

    dq = np.round((walk.astype(np.float32) - origins[:, None, :])
                  / np.float32(OFFSET_QUANTUM))
    pts_q = np.clip(dq, -32768, 32767).astype(np.int16)
    lens = np.full(B, T, np.int32)
    return pts_q, origins, lens
