"""Self-tests of the benchmark harness, run on the CPU:

    python -m pytest benchmark/tests -q

- the trace reduction on a trace recorded on the chip, and on a hand-made
  one whose answer is known;
- the open-loop driver times each request from its due time and reports
  how late it was sent;
- discovery by name: a traffic mix and a cell added to a copy of the
  benchmark run in rehearsal with no other file edited;
- a fault planted under the timed path turns ``correct`` false, once per
  fault a cell can have (half a batch, one chip's share, altered answers,
  reports that never reach the sink, shed requests), and the control (the
  reference in bfloat16) comes out not correct through the run's judge.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import trace_reduce  # noqa: E402
from benchmark.traffic import loadgen  # noqa: E402


# ---- trace reduction ------------------------------------------------------

def test_reduce_hand_made_trace():
    ms = 1_000_000
    ev = {"host": [[0, 100 * ms, "window"],
                   [10 * ms, 40 * ms, "match_many"],
                   [60 * ms, 30 * ms, "match_many"]],
          "devices": {"/device:TPU:0": [
              [20 * ms, 20 * ms, "%while.1"],           # holds the next two
              [22 * ms, 5 * ms, "%fusion.2"],
              [30 * ms, 5 * ms, "%branch_1_fun.3 [tpu_custom_call]"],
              [70 * ms, 10 * ms, "%cond.4"],
              [72 * ms, 4 * ms, "%branch_1_fun.5 [tpu_custom_call]"],
              [95 * ms, 10 * ms, "%fusion.6"]]}}          # past the window
    r = trace_reduce.reduce(ev)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.035)           # 20 + 10 + 5
    assert r["sweep_s"] == pytest.approx(0.009)
    assert r["calls"] == 2
    gaps = {lab: s for lab, s in r["idle_gaps"]}
    assert gaps == {"between calls": pytest.approx(0.03),     # 40..70
                    "prepare+dispatch": pytest.approx(0.02),  # 0..20
                    "harvest+walk": pytest.approx(0.015)}     # 80..95
    assert [lab for lab, _ in r["idle_gaps"]] == [
        "between calls", "prepare+dispatch", "harvest+walk"]
    assert r["device_ops"][0] == ["%while.1", pytest.approx(0.02)]


def test_reduce_recorded_chip_trace():
    with open(os.path.join(BENCH, "testdata", "trace_two_calls.json")) as f:
        ev = json.load(f)
    r = trace_reduce.reduce(ev)
    assert r["calls"] == 2 and r["devices"] == 1
    assert 0 < r["sweep_s"] < r["busy_s"] < r["window_s"]
    # every idle second is accounted for by the labelled gaps
    merged = trace_reduce.union(
        [(s, s + d) for s, d, _ in ev["devices"]["/device:TPU:0"]])
    assert r["busy_s"] == pytest.approx(
        trace_reduce._length(merged) * 1e-9, rel=1e-6)
    assert any(lab in ("prepare+dispatch", "harvest+walk")
               for lab, _ in r["idle_gaps"])
    assert any(n.endswith(trace_reduce.SWEEP) for n, _ in r["device_ops"])


def test_op_name_marks_the_pallas_kernel():
    hlo = ('%branch_1_fun.3 = (s32[8]) custom-call(s32[8] %x), '
           'custom_call_target="tpu_custom_call", backend_config=...')
    assert trace_reduce.op_name(hlo) == "%branch_1_fun.3 [tpu_custom_call]"
    assert trace_reduce.op_name("%fusion.54 = f32[8] fusion(...)") == \
        "%fusion.54"


# ---- the open-loop driver -------------------------------------------------

def test_open_loop_times_from_due_and_reports_lateness():
    # one client thread and a server that takes 50 ms: requests due every
    # 10 ms queue behind each other, so each is sent later than due and
    # its latency counts that wait
    def slow_post(body):
        time.sleep(0.05)
        return 200, b'{"segments": []}'

    dues = [i * 0.01 for i in range(6)]
    res = loadgen.run_open_loop("http://127.0.0.1:1/report", dues,
                                [b"{}"] * 6, {5}, threads=1, drain_s=10.0,
                                post=slow_post)
    lag = [s - d for s, d in zip(res["sent"], res["due"])]
    lat = [d1 - d for d1, d in zip(res["done"], res["due"])]
    assert lag[0] < 0.02
    assert lag[-1] > 0.2                      # 5 x 50 ms behind
    assert lat[-1] > 0.25                     # latency counts from due
    assert all(a >= b - 1e-9 for a, b in zip(lat, lag))
    assert res["status"] == [200] * 6 and set(res["sampled"]) == {5}
    assert set(res["answers"]) == set(range(6))


def test_publish_mismatch_counts_rows_either_side():
    from collections import Counter

    row = {"id": 3, "next_id": None, "t0": 1.5, "t1": 9.0, "length": 88.0,
           "queue_length": 0.0}
    answers = {0: json.dumps({"segments": [], "reports": [row]}),
               1: json.dumps({"segments": [], "reports": []})}
    sink = Counter([loadgen.canonical(dict(reversed(list(row.items()))))])
    assert loadgen.publish_mismatch(sink, answers) == 0
    assert loadgen.publish_mismatch(Counter(), answers) == 1
    assert loadgen.publish_mismatch(sink + sink, answers) == 1
    other = Counter([loadgen.canonical({**row, "length": 87.0})])
    assert loadgen.publish_mismatch(other, answers) == 2


def test_schedule_same_work_for_every_seed():
    from benchmark.traffic.city import generate_grid_city
    from benchmark.traffic.graph import build_graph

    g = build_graph(generate_grid_city("t", seed=4, nx=12, ny=12,
                                       center=(-122.27, 37.80)), 1000.0)
    mix = {"rate_per_s": 50.0, "min_points": 33, "max_points": 128,
           "probes": {"dt": 1.0, "gps_sigma": 5.0,
                      "speed_range": [7.0, 16.0]}}
    a = loadgen.schedule(g, mix, 2**33 + 1, 4.0)
    b = loadgen.schedule(g, mix, 2**33 + 2, 4.0)
    assert sorted(a[2]) == sorted(b[2])          # same lengths

    def gaps(dues):            # the last gap runs to the window's end
        return np.sort(np.append(np.diff(dues), 4.0 - dues[-1]))

    assert np.allclose(gaps(a[0]), gaps(b[0]))
    assert not np.array_equal(a[2], b[2])        # another order
    c = loadgen.schedule(g, mix, 2**33 + 1, 4.0)
    assert np.array_equal(a[1], c[1])            # the seed fixes the traces


# ---- discovery by name, and faults under the timed path ---------------------

# bayarea-report is not declared (PERF.md §7: its tail has no admissible
# bound until the program's listen backlog is fixed). Its driver, load
# generator and readers stay for that cell, tested from a copy of the
# checkout whose BENCHMARK.json declares it as below.
REPORT_CONFIG = {
    "name": "bayarea", "file": "benchmark/configs/bayarea.json",
    "source": "https://github.com/opentraffic/reporter", "reduced": [],
    "why": "one city's tile as a per-metro /report deployment holds it"}
REPORT_CELL = {
    "name": "bayarea-report", "config": "bayarea",
    "traffic": "report_poisson", "chips": 1,
    "why": "POST /report, open-loop Poisson, 33-128 points, fresh uuids"}


def _checkout(tmp_path, declare_report: bool = True):
    """A copy of the benchmark's files (``paths`` and BENCHMARK.json)."""
    copy = tmp_path / "checkout"
    shutil.copytree(BENCH, copy / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    if declare_report:
        spec["configs"].append(REPORT_CONFIG)
        spec["workloads"].append(REPORT_CELL)
    with open(copy / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    return copy

def _tree_hashes(root):
    out = {}
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if x not in (".cache", "__pycache__")]
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def _rehearse(cwd, workload, seed):
    cmd = [sys.executable, "benchmark/run.py", "--workload", workload,
           "--seed", str(seed), "--seconds", "2", "--trace", "0",
           "--rehearse"]
    p = subprocess.run(cmd, cwd=cwd, env={**os.environ, "PYTHONPATH": ROOT},
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_a_new_cell_is_found_by_name(tmp_path):
    copy = _checkout(tmp_path, declare_report=False)
    before = _tree_hashes(copy / "benchmark")
    # the later PR's additions: one traffic file and one entry
    with open(copy / "benchmark" / "traffic" / "backfill_64x300.json",
              "w") as f:
        json.dump({"driver": "backfill", "batch_traces": 64,
                   "min_points": 300, "max_points": 300, "pool_batches": 2,
                   "sample_traces": 32,
                   "rehearsal": {"batch_traces": 16, "min_points": 60,
                                 "max_points": 60}}, f)
    with open(copy / "BENCHMARK.json") as f:
        spec = json.load(f)
    spec["workloads"].append({"name": "xl-longer", "config": "bayarea-xl",
                              "traffic": "backfill_64x300", "chips": 1,
                              "why": "test"})
    with open(copy / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    out = _rehearse(str(copy), "xl-longer", 2**32 + 5)
    assert out["rehearsal"] is True and out["correct"] is True
    assert "metrics" not in out
    after = _tree_hashes(copy / "benchmark")
    assert {k for k in after if before.get(k) != after[k]} == {
        os.path.join("traffic", "backfill_64x300.json")}


def test_no_tpu_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        "xl-backfill", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


FAULT_SNIPPET = r"""
import sys
sys.path[:0] = [{checkout!r}, {root!r}]
from reporter_tpu.matcher import api
from reporter_tpu.matcher.segments import SegmentRecord

orig = api.SegmentMatcher.match_many

def faulty(self, traces, **kw):
    out = [list(r) for r in orig(self, traces, **kw)]
    n = len(out)
    if {fault!r} == "half_batch":          # half of the batch left out
        out = out[: n - n // 2] + [[] for _ in range(n // 2)]
    elif {fault!r} == "one_shard":         # only the first chip's share
        k = max(1, n // {chips})
        out = out[:k] + [[] for _ in range(n - k)]
    elif {fault!r} == "altered":           # answers altered where made
        out = [[SegmentRecord(r.segment_id ^ 1, r.way_ids, r.start_time,
                              r.end_time, r.length, r.internal,
                              r.queue_length) for r in recs] for recs in out]
    return out

api.SegmentMatcher.match_many = faulty

from reporter_tpu.service import app as app_mod, datastore

if {fault!r} == "unpublished":           # half of each batch's reports
    pub = datastore.DatastorePublisher.publish   # never reach the sink

    def publish(self, reports, on_done=None):
        return pub(self, reports[: len(reports) // 2], on_done)

    datastore.DatastorePublisher.publish = publish
if {fault!r} == "shed":                  # every 7th request answered 503
    call = app_mod.ReporterApp.__call__
    seen = [0]

    def shed(self, environ, start_response):
        if environ.get("PATH_INFO") == "/report":
            seen[0] += 1
            if seen[0] % 7 == 0:
                start_response("503 Service Unavailable",
                               [("Content-Length", "0")])
                return [b""]
        return call(self, environ, start_response)

    app_mod.ReporterApp.__call__ = shed
sys.argv = ["run.py"] + {argv!r}
from benchmark import run
sys.exit(run.main(sys.argv[1:]))
"""


@pytest.mark.parametrize("workload,chips,fault", [
    ("xl-backfill", 1, None),
    ("xl-backfill", 1, "half_batch"),
    ("xl-backfill", 1, "altered"),
    ("xl-backfill-x4", 4, "one_shard"),
    ("xl-backfill-x4", 4, "altered"),
    ("bayarea-report", 1, None),
    ("bayarea-report", 1, "half_batch"),
    ("bayarea-report", 1, "altered"),
    ("bayarea-report", 1, "unpublished"),
    ("bayarea-report", 1, "shed"),
])
def test_fault_under_the_timed_path_fails_the_check(tmp_path, workload,
                                                    chips, fault):
    argv = ["--workload", workload, "--seed", str(2**31 + 99),
            "--seconds", "2", "--trace", "0", "--rehearse"]
    checkout = (str(_checkout(tmp_path)) if workload == "bayarea-report"
                else ROOT)
    script = tmp_path / "faulty_run.py"
    script.write_text(FAULT_SNIPPET.format(checkout=checkout, root=ROOT,
                                           fault=fault, chips=chips,
                                           argv=argv))
    p = subprocess.run([sys.executable, str(script)], cwd=checkout,
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": ROOT})
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is (fault is None), out["checks"]


@pytest.mark.parametrize("workload", ["xl-backfill", "bayarea-report"])
def test_control_in_bfloat16_is_not_correct(tmp_path, workload):
    """The control at a test's size (the configuration's own city, the
    rehearsal's share of the traffic): the reference in bfloat16 in the
    program's place, judged by the run's own ``judge``, fails on its
    disagreement, not on a count. The verdicts at the cells' own sizes
    come from ``python benchmark/tests/control.py`` on the chip machine
    (PERF.md)."""
    from benchmark.tests import control

    spec = str(_checkout(tmp_path) / "BENCHMARK.json")
    ok, numbers = control.verdict(workload, 2**31 + 7, 4.0, small=True,
                                  spec=spec)
    dis = numbers["segment_disagreement_pct"]
    assert ok is False and dis["value"] > 3 * dis["limit"], numbers
    assert numbers["compared_traces"]["value"] >= \
        numbers["compared_traces"]["limit"], numbers
