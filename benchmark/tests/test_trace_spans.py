"""Self-tests of ``benchmark/trace_spans.py``, run on the CPU:

    python -m pytest benchmark/tests/test_trace_spans.py -q

- a hand-made trace with program spans and scoped device ops gives the
  hand-computed Viterbi, walk and readback times, the idle split, and
  gaps labelled with their innermost span;
- on traces without spans or scopes (the hand-made one of
  ``test_harness.py`` and the one recorded on the chip) it returns what
  ``trace_reduce.reduce`` returns, value for value and label for label,
  and None for each new time;
- program spans are kept from every host thread; an op's scope comes
  from the optimized HLO the profiler keeps per program, resolved in
  the program the device's ``XLA Modules`` line shows running (a
  described TPU trace, and a real one recorded on the CPU).
"""

from __future__ import annotations

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))

from benchmark import trace_reduce, trace_spans  # noqa: E402

MS = 1_000_000


def _hand_made() -> dict:
    """Two calls in a 100 ms window on one device. Call 1: the sweep's
    cond 15-19 (candidates), the Viterbi while 20-40 holding a fusion
    22-27 (viterbi); readback 15-43, walk 43-47. Call 2: Viterbi 65-75,
    wire pack 75-78; readback 62-79, walk 79-88. An op past the window."""
    spans = [
        # call 1
        (11, 49, "match_many"), (11, 12, "plan"), (12, 14, "prepare"),
        (14, 15, "dispatch"), (15, 43, "readback"), (43, 47, "walk"),
        (43, 44, "unpack"), (47, 48, "merge"), (48, 49, "quality"),
        # call 2
        (61, 90, "match_many"), (61, 62, "plan"), (62, 62, "prepare"),
        (62, 62, "dispatch"), (62, 79, "readback"), (79, 88, "walk"),
        (79, 80, "unpack"), (88, 89, "merge"), (89, 90, "quality"),
    ]
    ops = [(15, 4, "%cond.3"), (20, 20, "%while.1"), (22, 5, "%fusion.2"),
           (65, 10, "%while.1"), (75, 3, "%fusion.4"), (95, 10, "%fusion.6")]
    dev = "/device:TPU:0"
    return {
        "host": [[0, 100 * MS, "window"], [10 * MS, 40 * MS, "match_many"],
                 [60 * MS, 30 * MS, "match_many"]],
        "devices": {dev: [[s * MS, d * MS, n] for s, d, n in ops]},
        "spans": [[s * MS, (e - s) * MS, "rtpu." + n] for s, e, n in spans],
        "scoped": {dev: [[s * MS, d * MS, scope] for s, d, scope in (
            (15, 4, "candidates"), (20, 20, "viterbi"), (22, 5, "viterbi"),
            (65, 10, "viterbi"), (75, 3, "wire_pack"))]},
    }


def test_hand_made_trace_with_spans_and_scopes():
    r = trace_spans.reduce(_hand_made())
    assert r["calls"] == 2
    assert r["busy_s"] == pytest.approx(0.042)       # 4 + 20 + 10 + 3 + 5
    assert r["viterbi_s"] == pytest.approx(0.030)    # 20 + 10
    assert r["scope_s"] == {"candidates": pytest.approx(0.004),
                            "viterbi": pytest.approx(0.030),
                            "wire_pack": pytest.approx(0.003)}
    assert r["walk_s"] == pytest.approx(0.013)       # 4 + 9
    # readback with the device idle: 19-20 and 40-43; 62-65 and 78-79
    assert r["readback_s"] == pytest.approx(0.008)
    assert r["span_s"]["rtpu.match_many"] == pytest.approx(0.067)
    assert r["span_s"]["rtpu.unpack"] == pytest.approx(0.002)
    # per call, as the readers would give it
    assert 1000 * r["viterbi_s"] / r["calls"] == pytest.approx(15.0)
    assert r["idle_gaps"] == [
        ["between calls", pytest.approx(0.025)],           # 40..65
        ["harvest+walk:rtpu.walk", pytest.approx(0.017)],  # 78..95
        ["between calls", pytest.approx(0.015)],           # 0..15
        ["inside call:rtpu.readback", pytest.approx(0.001)]]  # 19..20
    # the gaps cut at span and call ends: 0..10, 50..60 and 90..95 lie
    # outside the calls; 10..11 and 60..61 inside one before any span
    split = dict(r["idle_split"])
    assert split == {
        "between calls": pytest.approx(0.025),
        "prepare+dispatch": pytest.approx(0.002),
        "prepare+dispatch:rtpu.plan": pytest.approx(0.002),
        "prepare+dispatch:rtpu.prepare": pytest.approx(0.002),
        "prepare+dispatch:rtpu.dispatch": pytest.approx(0.001),
        "prepare+dispatch:rtpu.readback": pytest.approx(0.003),  # 62..65
        "inside call:rtpu.readback": pytest.approx(0.001),       # 19..20
        "harvest+walk:rtpu.readback": pytest.approx(0.004),  # 40..43, 78..79
        "harvest+walk:rtpu.unpack": pytest.approx(0.002),
        "harvest+walk:rtpu.walk": pytest.approx(0.011),      # 44..47, 80..88
        "harvest+walk:rtpu.merge": pytest.approx(0.002),
        "harvest+walk:rtpu.quality": pytest.approx(0.002),
        "harvest+walk": pytest.approx(0.001)}                # 49..50
    # every idle second is split once
    assert sum(split.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def _same_as_trace_reduce(ev):
    base = trace_reduce.reduce(ev)
    r = trace_spans.reduce(ev)
    for key, value in base.items():
        assert r[key] == value, key
    assert r["viterbi_s"] is None and r["walk_s"] is None
    assert r["readback_s"] is None and r["span_s"] == {}
    assert r["scope_s"] == {}
    return r


def test_no_spans_no_scopes_leaves_trace_reduce_as_it_was():
    ev = _hand_made()
    del ev["spans"], ev["scoped"]
    r = _same_as_trace_reduce(ev)
    assert r["calls"] == 2
    assert [lab for lab, _ in r["idle_gaps"]] == [
        "between calls", "harvest+walk", "between calls", "inside call"]


def test_recorded_chip_trace_reduces_as_before():
    with open(os.path.join(BENCH, "testdata", "trace_two_calls.json")) as f:
        ev = json.load(f)
    r = _same_as_trace_reduce(ev)
    assert r["calls"] == 2 and r["devices"] == 1
    assert sum(v for _, v in r["idle_split"]) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_load_keeps_program_spans_from_every_thread(tmp_path):
    import threading

    import jax

    ann = jax.profiler.TraceAnnotation
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with ann("window"), ann("match_many"), ann("rtpu.match_many"):
            worker = threading.Thread(target=lambda: ann("rtpu.walk")
                                      .__enter__().__exit__(None, None, None))
            worker.start()
            worker.join(10)
            with ann("rtpu.readback"):
                pass
    finally:
        jax.profiler.stop_trace()
    assert not worker.is_alive()
    ev = trace_spans.load(str(tmp_path))
    assert sorted(n for _, _, n in ev["spans"]) == [
        "rtpu.match_many", "rtpu.readback", "rtpu.walk"]
    assert sorted(n for _, _, n in ev["host"]) == ["match_many", "window"]
    assert ev["scoped"] == {}


def _pb(*fields) -> bytes:
    """One protobuf message from (field number, int | str | bytes)."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def _hlo_proto(module: str, ops: dict) -> bytes:
    instrs = [(2, _pb((1, name), (7, _pb((1, "x"), (2, path)))))
              for name, path in ops.items()]
    return _pb((1, _pb((1, module), (3, _pb((1, "main"), *instrs)))))


def test_scopes_resolved_in_the_running_program(tmp_path):
    """A described TPU trace: two programs reuse op names with other
    scopes (as the autotuner's arms do); the device's ``XLA Modules``
    line says which one ran, and only its scopes count."""
    path = "jit(wire_from_q8)/jit(main)/"
    meta = _pb(
        (2, "/host:metadata"),
        (5, _pb((1, 7), (2, _pb((1, 7), (2, "Hlo Proto"))))),
        (4, _pb((1, 1), (2, _pb((1, 1), (2, "jit_wire_from_q8(1)"), (5, _pb(
            (1, 7), (6, _hlo_proto("jit_wire_from_q8", {
                "while.9": path + "viterbi/while",
                "fusion.2": path + "candidates/cond",
                "copy.3": path + "copy"})))))))),
        (4, _pb((1, 2), (2, _pb((1, 2), (2, "jit_wire_from_q8(2)"), (5, _pb(
            (1, 7), (6, _hlo_proto("jit_wire_from_q8", {
                "while.9": path + "candidates/while",
                "fusion.2": path + "viterbi/cond"})))))))))

    def event(mid, start_ms, dur_ms):
        return (4, _pb((1, mid), (2, start_ms * 10**9),
                       (3, dur_ms * 10**9)))

    tpu = _pb(
        (2, "/device:TPU:0"),
        (3, _pb((2, "XLA Modules"), (3, 0), event(1, 0, 100))),
        (3, _pb((2, "XLA Ops"), (3, 0), event(2, 10, 30), event(3, 50, 5),
                event(4, 60, 5), event(2, 120, 5))),
        (4, _pb((1, 1), (2, _pb((1, 1), (2, "jit_wire_from_q8(1)"))))),
        (4, _pb((1, 2), (2, _pb((1, 2), (2, "%while.9 = (s32[]) while("
                                            "%t), body=%b"))))),
        (4, _pb((1, 3), (2, _pb((1, 3), (2, "%fusion.2 = f32[8] fusion("
                                            "%p), kind=kLoop"))))),
        (4, _pb((1, 4), (2, _pb((1, 4), (2, "%copy.3 = f32[8] copy(%q)"))))))
    host = _pb((2, "/host:CPU"), (3, _pb(
        (2, "python"), (3, 0), event(5, 0, 200), event(6, 12, 3))),
        (4, _pb((1, 5), (2, _pb((1, 5), (2, "window"))))),
        (4, _pb((1, 6), (2, _pb((1, 6), (2, "rtpu.walk"))))))
    (tmp_path / "t.xplane.pb").write_bytes(
        _pb((1, meta), (1, tpu), (1, host)))
    assert trace_spans.program_scopes(
        (tmp_path / "t.xplane.pb").read_bytes()) == {
        "jit_wire_from_q8(1)": {"%while.9": "viterbi",
                                "%fusion.2": "candidates"},
        "jit_wire_from_q8(2)": {"%while.9": "candidates",
                                "%fusion.2": "viterbi"}}
    ev = trace_spans.load(str(tmp_path))
    # the op at 120 ms lies outside any program on the modules line
    assert ev["scoped"] == {"/device:TPU:0": [
        [10 * MS, 30 * MS, "viterbi"], [50 * MS, 5 * MS, "candidates"]]}
    assert ev["spans"] == [[12 * MS, 3 * MS, "rtpu.walk"]]
    assert [n for _, _, n in ev["devices"]["/device:TPU:0"]] == [
        "%while.9", "%fusion.2", "%copy.3", "%while.9"]


def test_program_scopes_of_a_cpu_trace(tmp_path):
    """The profiler keeps each program's optimized HLO, with the named
    scopes in its op metadata, on the CPU too."""
    import glob

    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        with jax.named_scope("viterbi"):
            y = jax.lax.fori_loop(0, 3, lambda i, c: jnp.sin(c) * x, x)
        with jax.named_scope("wire_pack"):
            return (y * 3 + 1).astype(jnp.int32)

    x = jnp.ones(64)
    step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        step(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    with open(path, "rb") as f:
        programs = trace_spans.program_scopes(f.read())
    (ops,) = [v for k, v in programs.items() if k.startswith("jit_step(")]
    assert any(op.startswith("%while") and s == "viterbi"
               for op, s in ops.items())
    assert "wire_pack" in ops.values()
    assert set(ops.values()) <= set(trace_spans.SCOPES)


def test_scope_is_the_outermost_known_part():
    assert trace_spans.scope_of("jit(f)/shard_map/candidates/cond/"
                                "candidate_sweep") == "candidates"
    assert trace_spans.scope_of("jit(f)/wire_pack/or") == "wire_pack"
    assert trace_spans.scope_of("jit(f)/add") is None
