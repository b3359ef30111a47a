"""Self-tests of the closed-loop ``/report`` cell (``bayarea-report-closed``),
run on the CPU:

    python -m pytest benchmark/tests/test_report_closed.py -q

- the generator (``traffic/closedgen.py``) keeps exactly its fleet of
  requests in flight, sends a fresh uuid every time, and the window
  counts only what was answered inside it;
- its pool is the same work for every seed;
- the counter readers of the service's series;
- a rehearsal of the cell is ``correct``, and each fault planted under the
  timed path (``test_harness.FAULT_SNIPPET``) turns it false; the control
  (the reference in bfloat16) comes out not correct through the judge.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark.run import load_module  # noqa: E402
from benchmark.tests.test_harness import FAULT_SNIPPET  # noqa: E402
from benchmark.traffic import closedgen, loadgen  # noqa: E402

CELL = "bayarea-report-closed"
ANSWER = b'{"segments": [], "reports": []}'


def _uuid(body: bytes) -> str:
    return json.loads(body)["uuid"]


def test_keeps_exactly_n_requests_in_flight():
    n, state = 5, {"in": 0, "max": 0, "entered": 0, "release": 0}
    cv = threading.Condition()

    def post(body):
        with cv:
            state["in"] += 1
            state["entered"] += 1
            state["max"] = max(state["max"], state["in"])
            cv.notify_all()
            cv.wait_for(lambda: state["release"] > 0, timeout=30)
            state["release"] -= 1
            state["in"] -= 1
            cv.notify_all()
        return 200, ANSWER

    out = {}
    tails = [closedgen.body_tail(b'{"uuid":"","trace":[]}')] * 3
    bg = threading.Thread(target=lambda: out.update(closedgen.run_closed_loop(
        "http://127.0.0.1:1/report", tails, [0, 1, 2], clients=n,
        seconds=4.0, drain_s=30.0, prefix="s", post=post)), daemon=True)
    t0 = time.monotonic()
    bg.start()
    with cv:
        assert cv.wait_for(lambda: state["in"] == n, timeout=10)
    time.sleep(0.2)
    with cv:                      # every client waits: nobody sends more
        assert state["entered"] == n
        state["release"] += 3     # three answers, three new requests
        cv.notify_all()
        assert cv.wait_for(lambda: state["entered"] == n + 3
                           and state["in"] == n, timeout=10)
    time.sleep(max(0.0, t0 + 4.3 - time.monotonic()))   # past the window
    with cv:
        state["release"] += 10 * n
        cv.notify_all()
    bg.join(timeout=30)
    assert not bg.is_alive()
    assert state["max"] == n and state["entered"] == n + 3
    assert out["status"] == [200] * (n + 3)
    assert out["item"] == [k % 3 for k in range(n + 3)]


def test_fresh_uuid_every_send():
    seen = []

    def post(body):
        seen.append(body)
        time.sleep(0.002)
        return 200, ANSWER

    lonlat = np.array([[-122.27, 37.80], [-122.26, 37.81]])
    tails = [closedgen.body_tail(loadgen.body("", lonlat + i, np.arange(2.0)))
             for i in range(4)]
    res = closedgen.run_closed_loop("http://127.0.0.1:1/report", tails,
                                    [2, 0, 3, 1], clients=3, seconds=0.3,
                                    drain_s=10.0, prefix="77", post=post)
    uuids = [_uuid(b) for b in seen]
    assert len(seen) == len(res["status"]) > 8     # the pool wraps
    assert len(set(uuids)) == len(uuids)
    assert sorted(uuids) == sorted(f"77-{k}" for k in range(len(seen)))
    for b in seen:                 # the pool's body with the send's uuid
        k = int(_uuid(b).split("-")[1])
        i = res["item"][k]
        assert b == loadgen.body(f"77-{k}", lonlat + i, np.arange(2.0))


def test_counts_only_what_is_answered_inside_the_window():
    # one client, 0.3 s a request, a 1 s window: sends at 0, 0.3, 0.6 and
    # 0.9 s; the last is answered at 1.2 s, drained and not counted; a 503
    # in the window is a failure and no probes
    calls = []

    def post(body):
        calls.append(body)
        time.sleep(0.3)
        return (503, b"") if len(calls) == 2 else (200, ANSWER)

    res = closedgen.run_closed_loop("http://127.0.0.1:1/report",
                                    [b'","trace":[]}'] * 4, [0, 1, 2, 3],
                                    clients=1, seconds=1.0, drain_s=10.0,
                                    prefix="w", post=post)
    lengths = np.array([10, 20, 40, 80])
    t = closedgen.tally(res, lengths, 1.0)
    assert res["status"] == [200, 503, 200, 200]
    assert res["done"][-1] > 1.0 >= res["sent"][-1]
    assert t == {"sends": 4, "answered": 3, "answered_in_window": 2,
                 "drained": 1, "failed": 1, "probes": 10 + 40}


def test_unanswered_send_fails():
    def post(body):
        time.sleep(5.0)
        return 200, ANSWER

    res = closedgen.run_closed_loop("http://127.0.0.1:1/report",
                                    [b'","trace":[]}'], [0], clients=2,
                                    seconds=0.1, drain_s=0.3, prefix="u",
                                    post=post)
    t = closedgen.tally(res, np.array([50]), 0.1)
    assert res["clients_alive"] == 2
    assert t["sends"] == 2 and t["failed"] == 2 and t["probes"] == 0


def test_pool_same_work_for_every_seed():
    from benchmark.traffic.city import generate_grid_city
    from benchmark.traffic.graph import build_graph

    g = build_graph(generate_grid_city("t", seed=4, nx=12, ny=12,
                                       center=(-122.27, 37.80)), 1000.0)
    mix = {"pool_traces": 96, "min_points": 33, "max_points": 128,
           "probes": {"dt": 1.0, "gps_sigma": 5.0,
                      "speed_range": [7.0, 16.0]}}
    a = closedgen.pool(g, mix, 2**33 + 1)
    b = closedgen.pool(g, mix, 2**33 + 2)
    assert sorted(a[1]) == sorted(b[1]) == list(range(33, 129))
    assert sorted(a[2]) == sorted(b[2]) == list(range(96))
    assert not np.array_equal(a[2], b[2])          # another order
    assert not np.array_equal(a[0], b[0])          # other routes
    c = closedgen.pool(g, mix, 2**33 + 1)
    assert all(np.array_equal(x, y) for x, y in zip(a, c))


@pytest.mark.parametrize("metric,series", [
    ("parse_ms_per_request", "http_parse_seconds"),
    ("match_ms_per_batch", "batch_match_seconds")])
def test_counter_readers(metric, series):
    read = load_module("layer_metrics", metric).read
    before = {"counters": {f"{series}_total": 1.0, f"{series}_count": 4.0}}
    after = {"counters": {f"{series}_total": 1.5, f"{series}_count": 14.0}}
    got = read({"window": {"registry_before": before,
                           "registry_after": after}})
    assert got == pytest.approx(50.0)               # 0.5 s over 10
    # a program without the series (an older parent) reads nothing
    empty = {"counters": {}}
    assert read({"window": {"registry_before": empty,
                            "registry_after": empty}}) is None
    assert read({"window": {}}) is None


@pytest.mark.parametrize("fault", [None, "half_batch", "altered",
                                   "unpublished", "shed"])
def test_rehearsal_and_faults_under_the_timed_path(tmp_path, fault):
    argv = ["--workload", CELL, "--seed", str(2**31 + 77),
            "--seconds", "2", "--trace", "0", "--rehearse"]
    script = tmp_path / "faulty_run.py"
    script.write_text(FAULT_SNIPPET.format(checkout=ROOT, root=ROOT,
                                           fault=fault, chips=1,
                                           argv=argv))
    p = subprocess.run([sys.executable, str(script)], cwd=ROOT,
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": ROOT})
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is (fault is None), out["checks"]
    assert out["attempted"] > 0


def test_control_in_bfloat16_is_not_correct():
    """The control at a test's size (the configuration's own city, the
    rehearsal's share of the traffic); the verdict at the cell's own size
    comes from ``python benchmark/tests/control.py --workload
    bayarea-report-closed`` (PERF.md)."""
    from benchmark.tests import control

    ok, numbers = control.verdict(CELL, 2**31 + 7, 4.0, small=True)
    dis = numbers["segment_disagreement_pct"]
    assert ok is False and dis["value"] > 3 * dis["limit"], numbers
    assert numbers["compared_traces"]["value"] >= \
        numbers["compared_traces"]["limit"], numbers
