"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` the JAX profiler wrote into a compact
form: per device, the ops of its ``XLA Ops`` line (start, duration, name),
and the host annotations the benchmark itself made (``window``,
``match_many``). ``reduce`` computes, over the traced window:

- busy time per device: the union of its op intervals (ops nest: a while
  loop holds its body's ops), averaged over the devices;
- the sweep kernel's time: the union of the ops whose HLO is the Pallas
  custom call (``custom_call_target="tpu_custom_call"``; the program has
  one Pallas kernel, the candidate sweep);
- the device operations that took most time, and the idle gaps, each
  labelled by what the benchmark's host was doing: inside a
  ``match_many`` call before its first device op (``prepare+dispatch``)
  or after its last (``harvest+walk``), between device ops of one call
  (``inside call``), outside any call (``between calls``), or, where the
  benchmark made no call annotation, ``serving``.
"""

from __future__ import annotations

import glob
import os

KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
SWEEP = "[tpu_custom_call]"
ANNOTATIONS = ("window", "match_many")


def op_name(hlo: str) -> str:
    """The trace's op name (``%fusion.54``), marked when it is the Pallas
    kernel; the full HLO text is kept out of the compact form."""
    name = hlo.split(" = ", 1)[0]
    return f"{name} {SWEEP}" if KERNEL_MARK in hlo else name


def load(trace_dir: str) -> dict:
    """The compact form of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices: dict = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            names: dict = {}            # an op's HLO text repeats per call
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        hlo = e.name
                        n = names.get(hlo)
                        if n is None:
                            n = names[hlo] = op_name(hlo)
                        ops.append([e.start_ns, e.duration_ns, n])
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.start_ns, e.duration_ns, e.name]
                            for e in line.events if e.name in ANNOTATIONS)
    return {"devices": devices, "host": host}


def union(intervals) -> list:
    """Merged, sorted [start, end] intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(merged) -> float:
    return float(sum(e - s for s, e in merged))


def _clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce(events: dict, top: int = 10) -> dict:
    """Seconds per device averaged over the devices that ran ops, the
    call count, and the breakdown lists (seconds summed over devices)."""
    wins = [(s, s + d) for s, d, n in events["host"] if n == "window"]
    if not wins:
        raise ValueError("the trace holds no 'window' annotation")
    w0, w1 = wins[0]
    calls = sorted((s, s + d) for s, d, n in events["host"]
                   if n == "match_many" and s >= w0 and s + d <= w1)
    devs = {k: v for k, v in events["devices"].items() if v}
    if not devs:
        raise ValueError("no device ran an op in the trace")
    busy = sweep = 0.0
    op_time: dict = {}
    gaps: list = []
    for ops in devs.values():
        spans = _clip([(s, s + d) for s, d, _ in ops], w0, w1)
        merged = union(spans)
        busy += _length(merged)
        sweep += _length(union(_clip(
            [(s, s + d) for s, d, n in ops if n.endswith(SWEEP)], w0, w1)))
        for s, d, n in ops:
            if s >= w0 and s + d <= w1:
                op_time[n] = op_time.get(n, 0) + d
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gaps.append((g1 - g0, _label((g0 + g1) / 2, calls, merged)))
    n = len(devs)
    ns = 1e-9
    ranked = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "devices": n,
        "window_s": (w1 - w0) * ns,
        "busy_s": busy / n * ns,
        "sweep_s": sweep / n * ns,
        "calls": len(calls),
        "device_ops": [[k, v / n * ns] for k, v in ranked],
        "idle_gaps": [[lab, g * ns] for g, lab in
                      sorted(gaps, key=lambda x: -x[0])[:top]],
    }


def _label(t: float, calls: list, merged: list) -> str:
    if not calls:
        return "serving"
    for c0, c1 in calls:
        if c0 <= t <= c1:
            inside = [iv for iv in merged if iv[1] > c0 and iv[0] < c1]
            if not inside or t < inside[0][0]:
                return "prepare+dispatch"
            if t > inside[-1][1]:
                return "harvest+walk"
            return "inside call"
    return "between calls"
