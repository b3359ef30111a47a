"""Metro fleet residency — many compiled metros per chip, LRU-paged HBM.

ROADMAP item 1: the staging plan says one bayarea-xl-scale metro uses
~176 MB of a ~12.8 GB HBM budget, so "one deployment = one metro" wastes
~98% of the chip. This module is the fleet layer that packs many
compiled metro tables onto one chip and pages the cold ones, following
the partition-the-planet strategy of large-scale map matching
(PAPERS.md, arXiv:1910.05312) with the hot/cold filter-refine residency
split of SeGraM (arXiv:2205.05883):

  hot tier   metros with device tables staged in HBM, serving;
  cold tier  metros demoted to HOST-PINNED staged arrays
             (``TileSet.host_tables`` — the expensive cell_pack /
             seg_pack build is done ONCE and kept), costing zero HBM;
  paging     a request for a cold metro promotes it behind a counted,
             traced ``fleet_promote`` span: one ``jax.device_put`` of
             the pinned host dict, then ``restage_tables`` on the
             metro's long-lived SegmentMatcher — the wire entries take
             tables as call arguments, so the matcher's compiled
             executables survive any number of evict→promote cycles
             and re-promotion never recompiles.

Capacity policy (``FleetConfig``): a max-resident-bytes budget, LRU
eviction that drains occupancy below a watermark fraction of the budget
(hysteresis — one promotion must not trigger an eviction per request at
the boundary), and a pin list for SLO metros that are never evicted.
Metros mid-dispatch (leased) are never evicted either: eviction drops
our references, and a dispatch that STARTED after the drop would see no
tables — the lease makes promote→dispatch atomic against eviction.

Bit-identity contract (test- and bench-asserted): a fleet-resident
metro's harvested wire bytes equal a dedicated single-metro
SegmentMatcher's for the same traces, including immediately after an
evict→promote cycle — promotion re-places the SAME host values through
the SAME wire programs, so this holds by construction and the tests
keep it that way.

Per-metro observability: ``rtpu_fleet_*`` labeled counters/gauges
(utils.metrics.labeled) plus a fixed-bucket ``fleet_promote_seconds``
histogram — aggregable across workers like every other exposition
series (round-10 discipline).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import time
from dataclasses import dataclass
from typing import Iterator, Sequence

from reporter_tpu.utils import locks
from reporter_tpu import faults
from reporter_tpu.config import Config
from reporter_tpu.utils import watchdog as watchdog_mod
from reporter_tpu.utils.watchdog import AbandonedThreadWatchdog
from reporter_tpu.matcher.api import SegmentMatcher
from reporter_tpu.service.scheduler import ServiceOverloaded
from reporter_tpu.tiles.tileset import TileSet
from reporter_tpu.utils import tracing
from reporter_tpu.utils.metrics import MetricsRegistry, labeled


class FleetCapacityError(ServiceOverloaded):
    """No way to make a metro resident: the budget is full of pinned or
    mid-dispatch metros (or the metro alone exceeds the budget).
    Subclasses ServiceOverloaded so the WSGI face sheds it as a
    retryable 503, exactly like admission-queue overflow — overload
    degrades explicitly (round-6 discipline), whichever resource ran
    out."""


@dataclass(frozen=True)
class FleetConfig:
    """Residency capacity policy. Env overrides (``RTPU_FLEET_*``)
    follow the matcher-lever discipline: applied at construction,
    validated strictly, so a typo fails loudly instead of silently
    serving an unbounded fleet."""

    max_resident_bytes: int = 0        # HBM budget for staged metro
    #                                    tables; 0 = unbounded (no
    #                                    paging — every metro promotes
    #                                    once and stays)
    evict_watermark: float = 0.85      # eviction drains occupancy (incl.
    #                                    the incoming metro) below this
    #                                    fraction of the budget, not just
    #                                    barely under it — hysteresis so
    #                                    a fleet at the boundary doesn't
    #                                    page on every alternate request
    pins: tuple[str, ...] = ()         # SLO metros never evicted (their
    #                                    bytes still count against the
    #                                    budget)
    promote_wait_s: float = 5.0        # a promotion blocked ONLY by
    #                                    in-flight leases waits up to this
    #                                    long for dispatches to release
    #                                    before shedding 503 — a lease is
    #                                    transient (one dispatch), unlike
    #                                    a pin; 0 = shed immediately
    promote_timeout_s: float = 0.0     # page-in watchdog: a wedged host-
    #                                    device transfer HANGS rather than
    #                                    erroring, and promotion's
    #                                    device_put is a device interaction
    #                                    on the serving path — unbounded,
    #                                    one wedged page-in
    #                                    wedges every request for that
    #                                    metro. >0 bounds the transfer on
    #                                    a watchdog thread (same
    #                                    abandoned-thread breaker
    #                                    discipline as the r9 dispatch
    #                                    watchdog); 0 = off, matching
    #                                    matcher.dispatch_timeout_s's
    #                                    opt-in default. Size it for the
    #                                    TABLE bytes (~7 s for a 176 MB
    #                                    metro at 25 MB/s), not for one
    #                                    dispatch.

    def validate(self) -> "FleetConfig":
        if self.max_resident_bytes < 0:
            raise ValueError("fleet.max_resident_bytes must be >= 0")
        if not 0.0 < self.evict_watermark <= 1.0:
            raise ValueError("fleet.evict_watermark must be in (0, 1]")
        if self.promote_wait_s < 0:
            raise ValueError("fleet.promote_wait_s must be >= 0")
        if self.promote_timeout_s < 0:
            raise ValueError("fleet.promote_timeout_s must be >= 0")
        return self

    def with_env_overrides(self, env: "dict[str, str] | None" = None,
                           ) -> "FleetConfig":
        e = os.environ if env is None else env
        kw: dict = {}
        if "RTPU_FLEET_MAX_BYTES" in e:
            kw["max_resident_bytes"] = int(float(e["RTPU_FLEET_MAX_BYTES"]))
        if "RTPU_FLEET_WATERMARK" in e:
            kw["evict_watermark"] = float(e["RTPU_FLEET_WATERMARK"])
        if "RTPU_FLEET_PROMOTE_WAIT" in e:
            kw["promote_wait_s"] = float(e["RTPU_FLEET_PROMOTE_WAIT"])
        if "RTPU_FLEET_PROMOTE_TIMEOUT" in e:
            kw["promote_timeout_s"] = float(e["RTPU_FLEET_PROMOTE_TIMEOUT"])
        if "RTPU_FLEET_PINS" in e:
            extra = tuple(p.strip() for p in e["RTPU_FLEET_PINS"].split(",")
                          if p.strip())
            kw["pins"] = tuple(dict.fromkeys(self.pins + extra))
        return dataclasses.replace(self, **kw) if kw else self


class _Metro:
    """One metro's residency entry (all mutation under the fleet lock)."""

    __slots__ = ("name", "tileset", "host", "matcher", "staged_bytes",
                 "resident", "pinned", "promoting", "reserved",
                 "last_used", "leases", "promotions", "demotions")

    def __init__(self, tileset: TileSet, pinned: bool):
        self.name = tileset.name
        self.tileset = tileset
        self.host: "dict | None" = None       # host-pinned staged arrays
        self.matcher: "SegmentMatcher | None" = None
        self.staged_bytes = 0                 # known after first staging
        self.resident = False
        self.pinned = pinned
        self.promoting = False                # a thread is paging it in
        #                                       (with the fleet lock
        #                                       dropped for the expensive
        #                                       phases) — other touches
        #                                       wait on the condvar
        self.reserved = False                 # staged_bytes are counted
        #                                       in the ledger (resident,
        #                                       or mid-promotion past the
        #                                       reservation point) — only
        #                                       reserved bytes can ever
        #                                       be freed by waiting
        self.last_used = 0                    # LRU clock (sequence, not
        #                                       wall time: monotone under
        #                                       bursts within one tick)
        self.leases = 0                       # dispatches in flight
        self.promotions = 0
        self.demotions = 0


class FleetResidency:
    """The registry of compiled metros + the HBM occupancy ledger.

    Construction registers every tileset COLD (zero HBM, zero staging
    work) — first traffic stages it. ``configs`` carries per-metro
    Config overrides (the FleetRouter's SLO plumbing); metros without an
    entry share ``config``. One lock guards the LEDGER (bytes, tiers,
    LRU, leases); the expensive promotion phases — first-touch staging
    build, device_put — run with that lock released behind a per-metro
    ``promoting`` flag, so a multi-second page-in of one cold metro
    never stalls other metros' leases (bytes are reserved in the ledger
    before the unlocked transfer, so concurrent promoters can't
    oversubscribe the budget). Matchers are jax-backend single-device
    by contract (``SegmentMatcher.unstage_tables``)."""

    def __init__(self, tilesets: Sequence[TileSet],
                 config: "Config | None" = None,
                 fleet: "FleetConfig | None" = None,
                 configs: "dict[str, Config] | None" = None,
                 metrics: "MetricsRegistry | None" = None):
        if not tilesets:
            raise ValueError("need at least one tileset")
        names = [ts.name for ts in tilesets]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate metro names: {names}")
        self.config = (config or Config()).validate()
        if self.config.matcher_backend != "jax":
            raise ValueError("fleet residency pages DEVICE tables; "
                             "matcher_backend must be 'jax'")
        self.fleet = (fleet or FleetConfig()).with_env_overrides().validate()
        unknown_pins = set(self.fleet.pins) - set(names)
        if unknown_pins:
            raise ValueError(f"pins for unknown metros: "
                             f"{sorted(unknown_pins)}")
        self._configs = dict(configs or {})
        unknown_cfg = set(self._configs) - set(names)
        if unknown_cfg:
            raise ValueError(f"configs for unknown metros: "
                             f"{sorted(unknown_cfg)}")
        non_jax = sorted(n for n, c in self._configs.items()
                         if c.matcher_backend != "jax")
        if non_jax:
            # fail at construction, not on the metro's first touch —
            # staged_tables injection requires the jax backend
            raise ValueError(f"per-metro configs must keep "
                             f"matcher_backend='jax': {non_jax}")
        self.metrics = metrics or MetricsRegistry()
        self._lock = locks.named_lock("fleet.ledger")
        # one condvar (same underlying lock — wait() drops it) for both
        # wake events: a lease release (a capacity-blocked promotion may
        # now have an evictable victim) and a promotion finishing (other
        # touches of that metro were waiting for its tables)
        self._cond = locks.named_condition("fleet.ledger", lock=self._lock)
        self._seq = 0
        self._resident_bytes = 0
        self._resident_count = 0
        # promote-watchdog breaker (its own internal lock: an abandoned
        # transfer thread must be able to un-count itself without
        # touching the fleet condvar lock)
        self._watchdog = AbandonedThreadWatchdog(
            cap=4, thread_name="fleet-promote-watchdog")
        self._metros = {ts.name: _Metro(ts, ts.name in self.fleet.pins)
                        for ts in tilesets}
        self.metrics.gauge("fleet_capacity_bytes",
                           self.fleet.max_resident_bytes)
        self.metrics.gauge("fleet_registered_metros", len(self._metros))
        self._publish_occupancy_locked()

    # ---- read side -------------------------------------------------------

    @property
    def names(self) -> "list[str]":
        return sorted(self._metros)

    @property
    def resident_names(self) -> "list[str]":
        with self._lock:
            return sorted(n for n, m in self._metros.items() if m.resident)

    @property
    def resident_bytes(self) -> int:
        with self._lock:
            return self._resident_bytes

    def tileset(self, name: str) -> TileSet:
        return self._metros[name].tileset

    def occupancy(self) -> dict:
        """The occupancy/paging report (/health's fleet block and the
        bench leg's artifact): ledger totals + per-metro residency."""
        with self._lock:
            metros = {
                n: {"resident": m.resident, "pinned": m.pinned,
                    "staged_bytes": m.staged_bytes,
                    "promotions": m.promotions, "demotions": m.demotions,
                    "leases": m.leases, "last_used_seq": m.last_used,
                    # round 17: the kernel plan serving this metro (None
                    # = no matcher built yet)
                    "tuned_plan": (m.matcher.tuned_plan.label
                                   if m.matcher is not None
                                   and m.matcher.tuned_plan is not None
                                   else None)}
                for n, m in sorted(self._metros.items())}
            occ = self._resident_bytes
        cap = self.fleet.max_resident_bytes
        return {
            "capacity_bytes": cap,
            "evict_watermark": self.fleet.evict_watermark,
            "resident_bytes": occ,
            "occupancy_frac": (occ / cap if cap else None),
            "resident_metros": sum(1 for m in metros.values()
                                   if m["resident"]),
            "registered_metros": len(metros),
            "promotions": int(self.metrics.value("fleet_promotions_total")),
            "demotions": int(self.metrics.value("fleet_demotions_total")),
            "metros": metros,
        }

    # ---- serving side ----------------------------------------------------

    @contextlib.contextmanager
    def lease(self, name: str) -> Iterator[SegmentMatcher]:
        """Promote-if-cold and HOLD the metro resident for the body —
        the only safe way to dispatch: eviction skips leased metros, so
        the tables a dispatch captured cannot be dropped under it."""
        with self._lock:
            m = self._touch_locked(name)
            m.leases += 1
        try:
            yield m.matcher
        finally:
            with self._lock:
                m.leases -= 1
                if m.leases == 0:
                    # a promotion may be waiting for this metro to
                    # become evictable
                    self._cond.notify_all()

    def matcher(self, name: str) -> SegmentMatcher:
        """Touch + promote-if-cold, WITHOUT a lease — for construction
        paths (the router building a metro's app). Dispatch through
        ``lease()``."""
        with self._lock:
            return self._touch_locked(name).matcher

    def promote(self, name: str) -> None:
        with self._lock:
            self._touch_locked(name)

    def demote(self, name: str) -> None:
        """Explicitly page a metro out (operational lever; eviction uses
        the same path). Pinned metros CAN be demoted explicitly — the
        pin only shields them from the LRU scan. No-op when cold.
        Refused while the metro is mid-dispatch: the leased body may
        dispatch again and would hit the unstaged-tables guard."""
        with self._lock:
            m = self._metros[name]
            if m.leases > 0:
                raise RuntimeError(
                    f"metro {name!r} has {m.leases} dispatch(es) in "
                    "flight; cannot demote under a lease")
            if m.resident:
                self._demote_locked(m)

    def set_capacity(self, max_resident_bytes: int) -> None:
        """Retune the budget live (and let the bench's promotion-storm
        leg shrink a steady-state fleet into a paging one). Shrinking
        below current occupancy evicts LRU immediately; pinned/leased
        metros can leave it over budget — counted, not silent."""
        with self._lock:
            # swap under the fleet lock: an in-flight promotion snapshots
            # self.fleet once, so it never mixes an old cap with a new
            # watermark mid-eviction
            self.fleet = dataclasses.replace(
                self.fleet, max_resident_bytes=int(max_resident_bytes)
            ).validate()
            self.metrics.gauge("fleet_capacity_bytes",
                               self.fleet.max_resident_bytes)
            cap = self.fleet.max_resident_bytes
            if cap:
                self._evict_locked(
                    need=0, budget=int(cap * self.fleet.evict_watermark))
            self._publish_occupancy_locked()

    # ---- internals (all under self._lock) --------------------------------

    def _touch_locked(self, name: str) -> _Metro:
        m = self._metros.get(name)
        if m is None:
            raise KeyError(f"unknown metro {name!r}; have {self.names}")
        self._seq += 1
        m.last_used = self._seq
        if m.resident:
            self.metrics.count(labeled("fleet_hits", metro=name))
            return m
        self.metrics.count(labeled("fleet_misses", metro=name))
        while True:
            if m.resident:              # a concurrent promoter finished
                return m
            if not m.promoting:
                self._promote_locked(m)
                return m
            # another thread is paging this metro in; wait for it to
            # finish (or fail — then the re-check promotes it ourselves)
            self._cond.wait()

    def _promote_locked(self, m: _Metro) -> None:
        """Page ``m`` in. Lock held on entry/exit; the EXPENSIVE phases
        (first-touch staging build, device_put) run with the lock
        RELEASED — ``m.promoting`` makes this thread the metro's only
        promoter, so a multi-second page-in of one cold metro never
        stalls other metros' leases behind the fleet lock. The ledger
        reserves ``staged_bytes`` before the unlocked transfer, so
        concurrent promoters can't oversubscribe the budget."""
        fleet = self.fleet      # ONE consistent (cap, watermark, wait)
        #                         snapshot — set_capacity may swap
        #                         self.fleet while we wait
        m.promoting = True
        try:
            if m.host is None:
                # first touch: the cell_pack/seg_pack build — done
                # once, pinned in host RAM for every later promotion
                # (metered apart from paging: staging is construction
                # cost, the promote histogram is the steady-state
                # paging cost). Staged layout follows the METRO'S
                # config (a per-metro candidate_backend override must
                # stage the tables its own matcher sweeps).
                cfg_m = self._configs.get(m.name, self.config)
                self._lock.release()
                try:
                    with self.metrics.stage("fleet_stage"):
                        host = m.tileset.host_tables(
                            cfg_m.matcher.candidate_backend)
                        # (round 17: no plan-cache lookup HERE — the
                        # matcher built after the guarded device_put
                        # does it. device_key()'s jax.devices() may be
                        # the process's FIRST backend init, which must
                        # not run outside any watchdog; after
                        # _device_put_guarded the backend exists and
                        # the transfer just worked.)
                finally:
                    self._lock.acquire()
                m.host = host
                m.staged_bytes = int(sum(v.nbytes for v in host.values()))
            cap = fleet.max_resident_bytes
            if cap:
                if m.staged_bytes > cap:
                    # no eviction can ever make it fit — shed BEFORE the
                    # LRU scan, or a hopeless promotion (retried on
                    # every 503) would mass-evict the whole resident
                    # fleet each attempt and keep every metro cold
                    self.metrics.count(labeled(
                        "fleet_promote_failures", metro=m.name))
                    raise FleetCapacityError(
                        f"metro {m.name!r} staged tables "
                        f"({m.staged_bytes} B) exceed the fleet budget "
                        f"({cap} B); no eviction can make it fit")
                # the watermark headroom target — but a metro bigger
                # than the watermark slice can still legally fit under
                # cap: clamp to the hard cap then, so eviction stops as
                # soon as the promotion fits instead of draining the
                # fleet toward an unreachable target
                target = int(cap * fleet.evict_watermark)
                if m.staged_bytes > target:
                    target = cap
                deadline = time.monotonic() + fleet.promote_wait_s
                while True:
                    if self._resident_bytes + m.staged_bytes <= cap:
                        break
                    self._evict_locked(need=m.staged_bytes, budget=target)
                    if self._resident_bytes + m.staged_bytes <= cap:
                        break
                    # Over budget even after the LRU scan. Occupancy
                    # held TRANSIENTLY — in-flight leases (one
                    # dispatch) or a concurrent promotion's reserved
                    # bytes (evictable once it lands and its lease
                    # releases) — is worth a brief wait; the condvar
                    # fires on both lease release and promotion
                    # completion. Blocked by pins (or the budget is
                    # just too small), shed now: waiting can't help.
                    # Only RESERVED bytes count as freeable: a promoter
                    # still parked in ITS capacity wait holds nothing in
                    # the ledger yet, and counting its staged_bytes
                    # would double-discount them — a doomed promotion
                    # would burn the whole promote_wait_s before the
                    # inevitable shed.
                    transient = [x for x in self._metros.values()
                                 if x is not m and not x.pinned
                                 and x.reserved
                                 and ((x.resident and x.leases > 0)
                                      or x.promoting)]
                    freeable = sum(x.staged_bytes for x in transient)
                    remaining = deadline - time.monotonic()
                    if (not transient or remaining <= 0
                            or self._resident_bytes - freeable
                            + m.staged_bytes > cap):
                        self.metrics.count(labeled(
                            "fleet_promote_failures", metro=m.name))
                        raise FleetCapacityError(
                            f"cannot make {m.name!r} resident "
                            f"({m.staged_bytes} B): "
                            f"{self._resident_bytes} B of {cap} B held "
                            f"by pinned/in-flight metros")
                    self.metrics.count(labeled("fleet_promote_waits",
                                               metro=m.name))
                    self._cond.wait(remaining)
            # reserve the bytes, then transfer with the lock released
            # (m stays invisible to eviction: resident is still False,
            # and `promoting` keeps us the only writer of m.matcher)
            self._resident_bytes += m.staged_bytes
            m.reserved = True
            placed = False
            self._lock.release()
            try:
                t0 = time.perf_counter()
                with tracing.span("fleet_promote", metro=m.name,
                                  bytes=m.staged_bytes):
                    tables = self._device_put_guarded(m, fleet)
                    # round 17: keep the tuned_plan leaf HOST-readable
                    # through the device dict — the plan seam reads it
                    # without a device readback (the staged_layout
                    # value-check discipline), so a pre-tuned host dict
                    # promotes without re-measuring even with no disk
                    # cache. The leaf is an unused 20 B wire argument;
                    # a host-backed copy costs nothing per dispatch.
                    if m.host is not None and "tuned_plan" in m.host:
                        tables = dict(tables)
                        tables["tuned_plan"] = m.host["tuned_plan"]
                    # paging cost = the transfer (+ pointer restage);
                    # first-touch matcher CONSTRUCTION is metered apart
                    # (fleet_matcher_build) so the promote histogram
                    # stays the steady-state number
                    dt = time.perf_counter() - t0
                    if m.matcher is None:
                        with self.metrics.stage("fleet_matcher_build"):
                            m.matcher = SegmentMatcher(
                                m.tileset,
                                self._configs.get(m.name, self.config),
                                staged_tables=tables)
                        # write the freshly resolved plan back into the
                        # host-pinned dict: every LATER promotion pages
                        # already-tuned tables (one device_put, never a
                        # re-measure). Values only — the plan leaf is an
                        # unused wire argument, so fleet wire bytes stay
                        # bit-identical through evict→promote regardless
                        # of plan (the r11 contract, unchanged).
                        arr = m.matcher.tuned_plan_array()
                        if arr is not None and m.host is not None \
                                and "tuned_plan" in m.host:
                            m.host["tuned_plan"] = arr
                    else:
                        m.matcher.restage_tables(tables)
                        dt = time.perf_counter() - t0
                placed = True
            finally:
                self._lock.acquire()
                if not placed:
                    self._resident_bytes -= m.staged_bytes
                    m.reserved = False
            self.metrics.observe("fleet_promote_seconds", dt)
            m.resident = True
            self._resident_count += 1
            m.promotions += 1
            self.metrics.count(labeled("fleet_promotions", metro=m.name))
            self.metrics.count("fleet_promotions_total")
            self._publish_metro_locked(m)
        finally:
            m.promoting = False
            self._cond.notify_all()     # waiters on this metro (and any
            #                             promoter waiting for capacity)

    def _device_put_guarded(self, m: _Metro, fleet: FleetConfig) -> dict:
        """One ``jax.device_put`` of the metro's host-pinned tables,
        bounded by the promote watchdog when ``promote_timeout_s`` > 0.

        A wedged transfer's failure mode is an infinite stall no
        try/except can catch, and this transfer is the fleet's only
        device interaction outside the matcher's own guarded dispatch —
        left unbounded, one wedged page-in holds ``m.promoting`` forever
        and every later toucher of the metro parks on the condvar. Runs
        with the fleet lock RELEASED (the caller holds only the
        promoting flag). On timeout the transfer thread is ABANDONED
        (daemon) and the promotion sheds as a retryable 503; abandoned
        threads trip the shared ``AbandonedThreadWatchdog`` breaker so a
        permanently dead link costs bounded memory — the r9 dispatch-
        watchdog machinery (utils/watchdog.py), applied to paging."""
        import jax

        timeout = float(fleet.promote_timeout_s)
        if timeout <= 0:
            faults.fire("fleet_promote")
            tables = jax.device_put(m.host)
            # the first real dispatch pays any residual transfer; the
            # bench storm measures promote→first-report, the honest
            # number
            jax.block_until_ready(tables)
            return tables
        if self._watchdog.tripped:
            # circuit open: enough abandoned transfers are already stuck
            # on the dead link — shed IMMEDIATELY rather than pin yet
            # another thread + host-table reference. Counted as a
            # timeout TOO, so the timeout series keeps moving while the
            # breaker is open.
            self.metrics.count("fleet_promote_breaker_open")
            self.metrics.count(labeled("fleet_promote_timeouts",
                                       metro=m.name))
            tracing.post_mortem("fleet_promote_breaker",
                                failing="fleet_promote", metro=m.name,
                                abandoned=self._watchdog.abandoned)
            raise ServiceOverloaded(
                f"fleet promote breaker open "
                f"({self._watchdog.abandoned} transfers already stuck); "
                f"{m.name!r} not promoted")

        def _transfer():
            t = jax.device_put(m.host)
            jax.block_until_ready(t)
            return t

        out = self._watchdog.run(_transfer, timeout,
                                 fault_site="fleet_promote")
        if out is not watchdog_mod.TIMED_OUT:
            return out
        self.metrics.count(labeled("fleet_promote_timeouts", metro=m.name))
        tracing.post_mortem("fleet_promote_timeout",
                            failing="fleet_promote", metro=m.name,
                            bytes=m.staged_bytes, timeout_s=timeout)
        raise ServiceOverloaded(
            f"fleet promote of {m.name!r} ({m.staged_bytes} B) exceeded "
            f"{timeout:.3f}s; shed for retry")

    def _evict_locked(self, need: int, budget: int) -> None:
        """Demote LRU unpinned, unleased metros until occupancy + need
        fits under ``budget`` (the watermark — hysteresis headroom), or
        nothing evictable remains."""
        victims = sorted(
            (m for m in self._metros.values()
             if m.resident and not m.pinned and m.leases == 0),
            key=lambda m: m.last_used)
        for v in victims:
            if self._resident_bytes + need <= budget:
                break
            self._demote_locked(v)
            self.metrics.count(labeled("fleet_evictions", metro=v.name))

    def _demote_locked(self, m: _Metro) -> None:
        assert m.matcher is not None
        m.matcher.unstage_tables()      # HBM frees once in-flight
        #                                 dispatches (none: leases==0 on
        #                                 the eviction path) release it
        m.resident = False
        m.reserved = False
        self._resident_count -= 1
        m.demotions += 1
        self._resident_bytes -= m.staged_bytes
        self.metrics.count(labeled("fleet_demotions", metro=m.name))
        self.metrics.count("fleet_demotions_total")
        self._publish_metro_locked(m)

    def _publish_metro_locked(self, m: _Metro) -> None:
        """Occupancy gauges for ONE metro + the ledger totals — O(1) per
        paging event. A thrashing fleet of hundreds of metros must not
        pay an all-metros gauge sweep under the fleet lock (the lock
        every lease needs) for every promote and every eviction
        victim."""
        self.metrics.gauge(labeled("fleet_resident_bytes", metro=m.name),
                           m.staged_bytes if m.resident else 0)
        self.metrics.gauge(labeled("fleet_resident", metro=m.name),
                           1.0 if m.resident else 0.0)
        self.metrics.gauge("fleet_resident_bytes_total",
                           self._resident_bytes)
        self.metrics.gauge("fleet_resident_metros", self._resident_count)

    def _publish_occupancy_locked(self) -> None:
        """Full-fleet republish — construction and capacity retune only;
        the paging paths publish just the affected metro."""
        for m in self._metros.values():
            self._publish_metro_locked(m)
