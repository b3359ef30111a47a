"""Partition-lease tests (round 23): table fencing edges, the pure
rebalance planner, runner handoff/conservation over real pipelines, and
the lease.table concurrency contract."""

import json
import os
import threading
import time

import pytest

from reporter_tpu.config import (CompilerParams, Config, ServiceConfig,
                                 StreamingConfig)
from reporter_tpu.distributed.lease import (LeaseError, LeaseRunner,
                                            LeaseTable, StaleLeaseError,
                                            plan_rebalance)
from reporter_tpu.netgen.synthetic import generate_city
from reporter_tpu.netgen.traces import synthesize_probe
from reporter_tpu.streaming import IngestQueue, StreamPipeline
from reporter_tpu.tiles.compiler import compile_network
from reporter_tpu.utils import locks


@pytest.fixture(scope="module")
def lease_tiles():
    return compile_network(
        generate_city("tiny"),
        CompilerParams(reach_radius=500.0, osmlr_max_length=200.0))


class FakeClock:
    def __init__(self, now=1000.0):
        self.now = now

    def __call__(self):
        return self.now


def _records(probes):
    """Interleave probes' points into a single firehose (round-robin)."""
    out = []
    T = max(len(p.times) for p in probes)
    for t in range(T):
        for p in probes:
            if t < len(p.times):
                out.append({"uuid": p.uuid, "lat": float(p.lonlat[t, 1]),
                            "lon": float(p.lonlat[t, 0]),
                            "time": float(p.times[t])})
    return out


def _kinds(table):
    return [e["event"] for e in table.events()]


# ---------------------------------------------------------------------------
# table protocol + fencing edges


class TestLeaseTable:
    def test_create_reopen_and_shape_mismatch(self, tmp_path):
        path = str(tmp_path / "leases")
        t = LeaseTable(path, num_partitions=4)
        assert t.num_partitions == 4
        # reopen infers the partition count from the existing state
        t2 = LeaseTable(path)
        assert t2.num_partitions == 4
        with pytest.raises(LeaseError):
            LeaseTable(path, num_partitions=8)
        with pytest.raises(LeaseError):
            LeaseTable(str(tmp_path / "absent"))     # nothing to reopen

    def test_acquire_renew_release_cycle(self, tmp_path):
        t = LeaseTable(str(tmp_path / "l"), 2)
        e = t.acquire("a", 0)
        assert e == 1                        # ownership change bumps epoch
        assert t.acquire("a", 0) == 1        # re-acquire own lease: no bump
        view = t.renew("a")
        assert view["owned"] == {0: 1}
        assert view["orphans"] == [1]
        t.commit("a", 0, 1, 7)
        assert t.committed(0) == 7
        assert t.release("a", 0, 1, floor=9) is True
        assert t.committed(0) == 9           # final fenced floor applied
        assert t.acquire("b", 0) == 2        # next owner bumps the epoch
        assert t.committed(0) == 9           # ...and resumes at the floor

    def test_live_lease_blocks_other_members(self, tmp_path):
        t = LeaseTable(str(tmp_path / "l"), 1)
        assert t.acquire("a", 0) == 1
        assert t.acquire("b", 0) is None

    def test_assignment_hint_reserves_partition(self, tmp_path):
        t = LeaseTable(str(tmp_path / "l"), 1)
        t.apply_plan({"assign": {0: "b"}})
        assert t.acquire("a", 0) is None     # reserved for b
        assert t.acquire("b", 0) == 1

    def test_commit_is_monotonic(self, tmp_path):
        t = LeaseTable(str(tmp_path / "l"), 1)
        e = t.acquire("a", 0)
        t.commit("a", 0, e, 5)
        t.commit("a", 0, e, 5)               # equal floor: no-op
        assert t.committed(0) == 5
        with pytest.raises(LeaseError):
            t.commit("a", 0, e, 3)           # regression is a caller bug

    def test_expired_lease_cannot_commit(self, tmp_path):
        clock = FakeClock()
        t = LeaseTable(str(tmp_path / "l"), 1, ttl_s=5.0, clock=clock)
        e = t.acquire("a", 0)
        clock.now += 6.0                     # expiry mid-in-flight wave
        with pytest.raises(StaleLeaseError) as exc:
            t.commit("a", 0, e, 10)
        assert exc.value.partitions == {0: "expired"}
        assert t.committed(0) == 0           # floor never moved
        # the audit event persisted THROUGH the fencing rejection
        assert "commit_rejected" in _kinds(t)

    def test_strict_expiry_renew_observes_loss(self, tmp_path):
        clock = FakeClock()
        t = LeaseTable(str(tmp_path / "l"), 2, ttl_s=5.0, clock=clock)
        t.acquire("a", 0)
        clock.now += 6.0
        view = t.renew("a")
        assert view["lost"] == [0]           # never resurrected
        assert view["owned"] == {}
        assert t.state()["partitions"]["0"]["owner"] is None
        assert "lease_lost" in _kinds(t)

    def test_zombie_commit_fenced_after_takeover(self, tmp_path):
        clock = FakeClock()
        t = LeaseTable(str(tmp_path / "l"), 1, ttl_s=5.0, clock=clock)
        e_a = t.acquire("a", 0)
        t.commit("a", 0, e_a, 4)
        clock.now += 6.0
        e_b = t.acquire("b", 0)              # takeover of the expired lease
        assert e_b == e_a + 1
        with pytest.raises(StaleLeaseError):
            t.commit("a", 0, e_a, 8)         # delayed zombie write
        assert t.committed(0) == 4
        t.commit("b", 0, e_b, 8)             # the real owner is unaffected
        assert t.committed(0) == 8
        ev = [e for e in t.events() if e["event"] == "acquire"
              and e["member"] == "b"]
        assert ev and ev[-1]["takeover_from"] == "a"

    def test_commit_many_applies_passing_updates_before_raising(
            self, tmp_path):
        clock = FakeClock()
        t = LeaseTable(str(tmp_path / "l"), 2, ttl_s=5.0, clock=clock)
        e0 = t.acquire("a", 0)
        t.acquire("a", 1)
        clock.now += 6.0
        t.renew("a")                         # loses both
        e0b = t.acquire("a", 0)              # re-takes only partition 0
        with pytest.raises(StaleLeaseError) as exc:
            t.commit_many("a", {0: (e0b, 3), 1: (e0, 5)})
        assert set(exc.value.partitions) == {1}
        assert t.committed(0) == 3           # the passing update applied
        assert t.committed(1) == 0

    def test_two_racers_exactly_one_wins(self, tmp_path):
        t = LeaseTable(str(tmp_path / "l"), 1)
        wins = [t.acquire(m, 0) for m in ("a", "b")]
        assert sorted(w is not None for w in wins) == [False, True]

    def test_racing_threads_exactly_one_wins(self, tmp_path):
        path = str(tmp_path / "l")
        LeaseTable(path, 1)
        results = {}
        barrier = threading.Barrier(4)

        def racer(name):
            tbl = LeaseTable(path)
            barrier.wait()
            results[name] = tbl.acquire(name, 0)

        threads = [threading.Thread(target=racer, args=(f"w{i}",))
                   for i in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        winners = [m for m, e in results.items() if e is not None]
        assert len(winners) == 1             # epoch fencing: one owner

    def test_release_after_loss_is_recorded_noop(self, tmp_path):
        clock = FakeClock()
        t = LeaseTable(str(tmp_path / "l"), 1, ttl_s=5.0, clock=clock)
        e = t.acquire("a", 0)
        clock.now += 6.0
        t.acquire("b", 0)
        assert t.release("a", 0, e, floor=99) is False
        assert t.committed(0) == 0           # the stale floor was ignored
        assert "release_noop" in _kinds(t)


# ---------------------------------------------------------------------------
# pure rebalance planner


def _ent(**over):
    ent = {"owner": None, "epoch": 0, "expires": 0.0, "committed": 0,
           "assigned": None, "revoke": False}
    ent.update(over)
    return ent


def _state(n, members, parts=None):
    return {"version": 1, "num_partitions": n,
            "members": {m: {"heartbeat": hb} for m, hb in members.items()},
            "partitions": {str(p): (parts or {}).get(p, _ent())
                           for p in range(n)}}


class TestPlanRebalance:
    def test_orphans_spread_fairly(self):
        st = _state(4, {"a": 1000.0, "b": 1000.0})
        plan = plan_rebalance(st, now=1000.0, member_ttl_s=10.0)
        assert plan["assign"] == {0: "a", 1: "b", 2: "a", 3: "b"}
        assert plan["revoke"] == {}

    def test_revoke_toward_least_loaded(self):
        st = _state(4, {"a": 1000.0, "b": 1000.0},
                    {p: _ent(owner="a", epoch=1, expires=2000.0)
                     for p in range(4)})
        plan = plan_rebalance(st, now=1000.0, member_ttl_s=10.0)
        assert list(plan["revoke"].values()) == ["b", "b"]
        assert len(plan["revoke"]) == 2      # stop at fair (spread < 2)

    def test_balanced_ownership_is_stable(self):
        st = _state(4, {"a": 1000.0, "b": 1000.0},
                    {0: _ent(owner="a", epoch=1, expires=2000.0),
                     1: _ent(owner="a", epoch=1, expires=2000.0),
                     2: _ent(owner="b", epoch=1, expires=2000.0),
                     3: _ent(owner="b", epoch=1, expires=2000.0)})
        plan = plan_rebalance(st, now=1000.0, member_ttl_s=10.0)
        assert plan == {"assign": {}, "revoke": {}, "clear": []}

    def test_running_filter_excludes_known_dead(self):
        # b's heartbeat is fresh (grace window) but the caller KNOWS its
        # process is gone: assignments must not pin partitions to a corpse
        st = _state(4, {"a": 1000.0, "b": 1000.0})
        plan = plan_rebalance(st, now=1000.0, member_ttl_s=10.0,
                              running={"a"})
        assert plan["assign"] == {p: "a" for p in range(4)}

    def test_stale_hint_to_dead_member_cleared(self):
        st = _state(2, {"a": 1000.0},
                    {0: _ent(assigned="dead")})
        plan = plan_rebalance(st, now=1000.0, member_ttl_s=10.0)
        assert 0 in plan["clear"]
        assert plan["assign"][0] == "a"      # reassigned, not stranded

    def test_no_live_members_plans_nothing(self):
        st = _state(2, {"a": 0.0})
        plan = plan_rebalance(st, now=1000.0, member_ttl_s=10.0)
        assert plan == {"assign": {}, "revoke": {}, "clear": []}


# ---------------------------------------------------------------------------
# runner over real pipelines: handoff conservation, loss discipline,
# checkpoint cross-restore across a rebalance


def _lease_worker(tiles, queue, published, clock, **stream_over):
    def transport(url, body):
        published.append(json.loads(body))
        return 200

    kw = dict(num_partitions=4, flush_min_points=16)
    kw.update(stream_over)
    cfg = Config(service=ServiceConfig(datastore_url="http://ds.test/"),
                 streaming=StreamingConfig(**kw))
    return StreamPipeline(tiles, cfg, queue=queue, transport=transport,
                          clock=clock, partitions=[])


class TestLeaseRunner:
    def test_elastic_handoff_zero_loss(self, lease_tiles, tmp_path):
        table = LeaseTable(str(tmp_path / "leases"), 4, ttl_s=30.0)
        queue = IngestQueue(4)
        published: list = []
        clock = FakeClock()
        pa = _lease_worker(lease_tiles, queue, published, clock)
        pb = _lease_worker(lease_tiles, queue, published, clock)
        ra = LeaseRunner(table, "a", pa)
        rb = LeaseRunner(table, "b", pb)
        assert ra.sync(force=True)           # a grabs every orphan
        assert sorted(ra.epochs) == [0, 1, 2, 3]

        probes = [synthesize_probe(lease_tiles, seed=50 + s, num_points=60,
                                   gps_sigma=3.0) for s in range(4)]
        recs = _records(probes)
        queue.append_many(recs[:len(recs) // 2])
        for _ in range(4):
            pa.step()
            ra.push_commits()

        # b joins mid-stream: heartbeat, rebalance, graceful handoff
        assert not rb.sync(force=True)       # everything still leased to a
        plan = plan_rebalance(table.state(), now=time.time(),
                              member_ttl_s=60.0)
        assert len(plan["revoke"]) == 2
        table.apply_plan(plan)
        assert ra.sync(force=True)           # flush → fenced floor → release
        assert ra.stats["revoked"] == 2
        assert rb.sync(force=True)           # adopt at the committed floors
        assert rb.stats["acquired"] == 2
        assert len(ra.epochs) == 2 and len(rb.epochs) == 2

        queue.append_many(recs[len(recs) // 2:])
        for _ in range(8):
            pa.step()
            ra.push_commits()
            pb.step()
            rb.push_commits()
        pa.drain()
        ra.push_commits()
        pb.drain()
        rb.push_commits()
        floors = table.floors()
        for p in range(4):
            assert floors[p] == queue.end_offset(p)   # zero lost
        assert ra.lag() == 0 and rb.lag() == 0
        assert ra.stats["stale_commits"] == 0
        assert rb.stats["stale_commits"] == 0
        assert published

    def test_lost_lease_discards_and_new_owner_replays(self, lease_tiles,
                                                       tmp_path):
        lclock = FakeClock(5000.0)
        table = LeaseTable(str(tmp_path / "leases"), 4, ttl_s=5.0,
                           clock=lclock)
        queue = IngestQueue(4)
        published: list = []
        clock = FakeClock()
        # a buffers everything (flush threshold unreachable): its lease
        # expires with a full in-flight wave of unflushed rows
        pa = _lease_worker(lease_tiles, queue, published, clock,
                           flush_min_points=10 ** 6)
        ra = LeaseRunner(table, "a", pa)
        ra.sync(force=True)
        old_epochs = dict(ra.epochs)

        probes = [synthesize_probe(lease_tiles, seed=70 + s, num_points=60,
                                   gps_sigma=3.0) for s in range(4)]
        queue.append_many(_records(probes))
        for _ in range(4):
            pa.step()
            ra.push_commits()
        assert pa.stats()["buffered_points"] > 0

        lclock.now += 6.0                    # every lease expires
        ra.sync(force=True)
        assert ra.stats["lost"] == 4
        assert ra.stats["discarded_points"] > 0   # dropped, NOT published

        # the zombie's in-flight commit is fenced out — rows stay in play
        with pytest.raises(StaleLeaseError):
            table.commit("a", 0, old_epochs[0], queue.end_offset(0))
        assert table.floors() == [0, 0, 0, 0]

        # the next owner replays the whole tail from the untouched floors
        pb = _lease_worker(lease_tiles, queue, published, clock)
        rb = LeaseRunner(table, "b", pb)
        rb.sync(force=True)
        assert rb.stats["acquired"] == 4
        for _ in range(8):
            pb.step()
            rb.push_commits()
        pb.drain()
        rb.push_commits()
        for p in range(4):
            assert table.committed(p) == queue.end_offset(p)
        assert published                     # zero loss despite the discard

    def test_checkpoint_cross_restore_across_rebalance(self, lease_tiles,
                                                       tmp_path):
        lclock = FakeClock(5000.0)
        table = LeaseTable(str(tmp_path / "leases"), 4, ttl_s=5.0,
                           clock=lclock)
        queue = IngestQueue(4)
        published: list = []
        clock = FakeClock()
        pa = _lease_worker(lease_tiles, queue, published, clock)
        ra = LeaseRunner(table, "a", pa)
        ra.sync(force=True)

        probes = [synthesize_probe(lease_tiles, seed=80 + s, num_points=80,
                                   gps_sigma=3.0) for s in range(4)]
        recs = _records(probes)
        queue.append_many(recs[:len(recs) // 2])
        for _ in range(4):
            pa.step()
            ra.push_commits()
        ckpt = str(tmp_path / "a.npz")
        pa.checkpoint(ckpt)                  # a dies right after this

        lclock.now += 6.0                    # its leases expire
        queue.append_many(recs[len(recs) // 2:])

        # successor restores the checkpoint, then adopts via the table:
        # adoption floors == the checkpointed commits (both fenced through
        # the same push), so replay starts exactly at the dead worker's tail
        p2 = _lease_worker(lease_tiles, queue, published, clock)
        p2.restore(ckpt)
        r2 = LeaseRunner(table, "a2", p2)
        r2.sync(force=True)
        assert r2.stats["acquired"] == 4
        assert p2.committed == table.floors()
        for _ in range(8):
            p2.step()
            r2.push_commits()
        p2.drain()
        r2.push_commits()
        for p in range(4):
            assert table.committed(p) == queue.end_offset(p)
        assert r2.lag() == 0


# ---------------------------------------------------------------------------
# the worker CLI in lease mode: global drain + the in-loop stall escape


@pytest.mark.parametrize("start", ["alone", "behind_dead_member"])
def test_lease_worker_cli_drains_tail_and_exits(lease_tiles, tmp_path,
                                                capsys, start):
    """``--exit-on-drain`` in lease mode ends the run on the GLOBAL drain
    (table floors over every partition) on its own, before
    ``--max-steps``: every record here is a tail below
    ``flush_min_points``, so only the in-loop force-flush stall escape
    publishes it and lets the floors reach the end offsets. Behind a
    dead member, the worker first owns nothing; it must not exit while
    that member's partitions hold uncommitted records, and drains them
    once their leases expire."""
    from reporter_tpu.streaming.__main__ import main
    from reporter_tpu.streaming.durable_queue import DurableIngestQueue

    tiles = str(tmp_path / "tiles.npz")
    lease_tiles.save(tiles)
    broker = str(tmp_path / "broker")
    q = DurableIngestQueue(broker, 4)
    probes = [synthesize_probe(lease_tiles, seed=90 + s, num_points=30,
                               gps_sigma=3.0) for s in range(4)]
    for r in _records(probes):
        q.append(r)
    ends = [q.end_offset(p) for p in range(4)]
    q.close()
    cfg = str(tmp_path / "config.json")
    with open(cfg, "w") as f:
        json.dump({"streaming": {"num_partitions": 4,
                                 "flush_min_points": 1000}}, f)
    lease_dir = str(tmp_path / "leases")
    if start == "behind_dead_member":
        ghost = LeaseTable(lease_dir, 4, ttl_s=1.0)
        for p in range(4):
            assert ghost.acquire("ghost", p) is not None
    max_steps = 2000
    assert main(["--tiles", tiles, "--broker-dir", broker,
                 "--config", cfg, "--lease-dir", lease_dir,
                 "--member", "w", "--lease-ttl", "5",
                 "--poll-interval", "0.01", "--exit-on-drain",
                 "--max-steps", str(max_steps)]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["steps"] < max_steps          # left on the drain, not the cap
    assert out["lag"] == 0 and out["buffered_points"] == 0
    assert out["reports"] > 0
    assert out["lease"]["acquired"] == 4
    assert out["lease"]["stale_commits"] == 0
    table = LeaseTable(lease_dir, 4)
    assert table.floors() == ends                # zero lost
    acquired_t = [e["t"] for e in table.events()
                  if e["event"] == "acquire" and e.get("member") == "w"]
    if start == "behind_dead_member":
        ghost_t = max(e["t"] for e in table.events()
                      if e["event"] == "acquire"
                      and e.get("member") == "ghost")
        # adopted only after the dead member's leases ran out
        assert min(acquired_t) >= ghost_t + 1.0


# ---------------------------------------------------------------------------
# concurrency contract (r14 pattern: seed a synthetic violation for the
# new lock class so the gate guarding it can't rot vacuous-green)


def test_lease_lock_blocking_hold_would_be_flagged(tmp_path):
    dep = locks.Lockdep()
    lk = locks.NamedLock("lease.table", dep=dep)
    with open(tmp_path / "f", "w") as f:
        with locks.use(dep):
            with lk:
                os.fsync(f.fileno())         # a txn write under the lock
    assert any(v["kind"] == "blocking-under-lock"
               and v["call"] == "os.fsync" for v in dep.violations), (
        "an fsync under lease.table must be a lockdep violation absent the "
        "dated BLOCKING_ALLOW entry — the allowlist is load-bearing")


def test_table_txn_fsync_is_allowlisted(tmp_path):
    """Behavioral twin of the seeded test: real table transactions under
    the session's armed lockdep record no violations (the state-file
    fsync is the dated load-bearing hold; everything else is a leaf)."""
    before = len(locks.global_dep().violations) if locks.armed() else 0
    t = LeaseTable(str(tmp_path / "leases"), 2)
    e = t.acquire("a", 0)
    t.commit("a", 0, e, 3)
    t.renew("a")
    t.release("a", 0, e)
    if locks.armed():
        assert len(locks.global_dep().violations) == before


def test_contract_names_the_lease_edge():
    from reporter_tpu.analysis import concurrency_contract as contract

    assert ("lease.table", "os.fsync") in contract.BLOCKING_ALLOW
    contract.validate()                      # still dated + acyclic
