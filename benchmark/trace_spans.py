"""The program's own spans and device scopes in a profiler trace: what
``trace_reduce`` leaves out of its compact form.

The program records its host spans on the profiler's host plane as
``rtpu.<name>`` (``reporter_tpu/utils/tracing.py``): per ``match_many``
call ``rtpu.match_many`` holding ``plan``, ``prepare`` and ``dispatch``
(per slice), ``readback`` (per slice, the caller's thread), ``walk`` with
``unpack`` inside it (per slice, the harvest worker's thread), ``merge``
and ``quality``. Its device program runs under named scopes:
``candidates`` (the sweep kernel inside it is ``candidate_sweep``),
``viterbi`` and ``wire_pack``.

``load`` returns ``trace_reduce.load``'s compact form with two more keys:

- ``spans``: [start, duration, name] of every host event whose name
  starts with ``rtpu.``, from every host line and thread;
- ``scoped``: per device, [start, duration, scope] of each op whose
  ``op_name`` metadata lies under one of ``SCOPES``.

On the TPU neither an op event's name (its HLO text, printed without
metadata) nor its stats carry ``op_name``. The profiler keeps each
loaded program's optimized HLO (``Hlo Proto``) in its ``/host:metadata``
plane, which ``ProfileData`` does not show: ``program_scopes`` reads it
from the file, and each op is resolved in the program that the device's
``XLA Modules`` line shows running at the op's start (programs compiled
side by side, such as the autotuner's arms, reuse op names).

``reduce`` returns what ``trace_reduce.reduce`` returns, computed by it,
with its ``idle_gaps`` labelled further, and these keys (seconds over
the traced window; None where the trace holds nothing of the kind, as
from a program without the spans or scopes):

- ``viterbi_s``: the union of the ops under ``viterbi``, averaged over
  the devices, and ``scope_s`` the same for each scope;
- ``walk_s``: the union of the ``rtpu.walk`` spans (the time some walk
  ran, however many ran at once);
- ``readback_s``: the time inside ``rtpu.readback`` spans in which the
  device ran no op, averaged over the devices: the transfer and the
  sync tail, not the wait for compute;
- ``span_s``: the union of each program span, by name;
- ``idle_split``: the device's idle time, averaged over the devices, cut
  at the program spans' and the calls' ends, each piece under
  ``trace_reduce``'s coarse label of its middle and the innermost
  program span (the one that started last) covering it;
- ``idle_gaps``: the longest gaps, each label followed by the innermost
  program span at the gap's middle (``harvest+walk:rtpu.walk``); where
  no span covers it, the label stays as ``trace_reduce`` gives it.
"""

from __future__ import annotations

import bisect
import glob
import os

from benchmark import trace_reduce
from benchmark.trace_reduce import _clip, _length, union

PREFIX = "rtpu."
SCOPES = ("candidates", "viterbi", "wire_pack")


def scope_of(path: str) -> "str | None":
    """The outermost of ``SCOPES`` among an ``op_name`` path's parts."""
    for part in path.split("/"):
        if part in SCOPES:
            return part
    return None


# ---- the few protobuf fields read (xplane.proto, xla hlo.proto) ----------

def _varint(buf, i: int) -> tuple:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field, skipping fixed-width ones."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} in a trace")
        yield key >> 3, value


def _sub(buf, number: int):
    """The values of one field of a message."""
    return (v for f, v in _fields(buf) if f == number)


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _hlo_scopes(hlo_proto) -> dict:
    """{"%op": scope} of one ``HloProto``: hlo_module (1) > computations
    (3) > instructions (2) > name (1), metadata (7) > op_name (2)."""
    out: dict = {}
    for module in _sub(hlo_proto, 1):
        for comp in _sub(module, 3):
            for instr in _sub(comp, 2):
                name = next(_sub(instr, 1), None)
                scope = next((scope_of(_text(op)) for meta in _sub(instr, 7)
                              for op in _sub(meta, 2)), None)
                if name is not None and scope:
                    out["%" + _text(name)] = scope
    return out


def program_scopes(xspace: bytes) -> dict:
    """{program name as the ``XLA Modules`` line names it: {"%op": scope}}
    from the ``Hlo Proto`` stats of the ``/host:metadata`` plane's event
    metadata (XSpace planes (1) > name (2), event_metadata (4) and
    stat_metadata (5) map entries, the value in field 2 of each;
    XEventMetadata name (2), stats (5); XStat metadata_id (1),
    bytes_value (6))."""
    out: dict = {}
    for plane in _sub(xspace, 1):
        if _text(next(_sub(plane, 2), b"")) != "/host:metadata":
            continue
        stat_names = {}
        for entry in _sub(plane, 5):
            for meta in _sub(entry, 2):
                fields = dict(_fields(meta))
                stat_names[fields.get(1, 0)] = _text(fields.get(2, b""))
        for entry in _sub(plane, 4):
            for meta in _sub(entry, 2):
                name = _text(next(_sub(meta, 2), b""))
                for raw in _sub(meta, 5):
                    stat = dict(_fields(raw))
                    if stat_names.get(stat.get(1)) == "Hlo Proto":
                        out[name] = _hlo_scopes(stat.get(6, b""))
    return out


def load(trace_dir: str) -> dict:
    """``trace_reduce.load`` plus ``spans`` and ``scoped``, from the same
    (newest) trace."""
    from jax.profiler import ProfileData

    events = trace_reduce.load(trace_dir)
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    path = max(paths, key=os.path.getmtime)
    with open(path, "rb") as f:
        raw = f.read()
    programs = program_scopes(raw)
    spans: list = []
    scoped: dict = {}
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            mods = sorted((e.start_ns, e.end_ns, e.name)
                          for e in getattr(lines.get("XLA Modules"),
                                           "events", ()))
            starts = [m[0] for m in mods]
            found = []
            for e in getattr(lines.get("XLA Ops"), "events", ()):
                k = bisect.bisect_right(starts, e.start_ns) - 1
                if k < 0 or e.start_ns > mods[k][1]:
                    continue
                scope = programs.get(mods[k][2], {}).get(
                    e.name.split(" = ", 1)[0])
                if scope is not None:
                    found.append([e.start_ns, e.duration_ns, scope])
            scoped[plane.name] = found
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.start_ns, e.duration_ns, e.name]
                             for e in line.events
                             if e.name.startswith(PREFIX))
    return {**events, "spans": spans, "scoped": scoped}


def _overlap(a: list, b: list) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


class _Innermost:
    """The innermost program span (the one that started last; of two,
    the shorter) at each instant, as a piecewise-constant timeline cut
    at every span's ends and at ``cuts`` (the calls' ends, so that no
    piece straddles a call boundary)."""

    def __init__(self, spans: list, cuts: list):
        cuts = sorted({x for s, e, _ in spans for x in (s, e)} | set(cuts))
        self.starts, self.ends, self.names = [], [], []
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            inside = [(s, -e, n) for s, e, n in spans if s <= mid <= e]
            self.starts.append(a)
            self.ends.append(b)
            self.names.append(max(inside)[2] if inside else None)

    def at(self, t: float) -> "str | None":
        k = bisect.bisect_right(self.starts, t) - 1
        return self.names[k] if 0 <= k and t <= self.ends[k] else None

    def pieces(self, g0: float, g1: float):
        """(lo, hi, innermost span or None) tiling [g0, g1]."""
        k = max(bisect.bisect_right(self.starts, g0) - 1, 0)
        t = g0
        while t < g1:
            if k >= len(self.starts) or t < self.starts[k]:
                hi = g1 if k >= len(self.starts) else min(self.starts[k], g1)
                yield t, hi, None
            elif t >= self.ends[k]:
                k += 1
                continue
            else:
                hi = min(self.ends[k], g1)
                yield t, hi, self.names[k]
                k += 1
            t = hi


def _join(label: str, span: "str | None") -> str:
    return label if span is None else f"{label}:{span}"


def _clip_named(spans: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi), name) for s, e, name in spans
            if e > lo and s < hi]


def reduce(events: dict, top: int = 10) -> dict:
    out = trace_reduce.reduce(events, top)
    w0, w1 = [(s, s + d) for s, d, n in events["host"] if n == "window"][0]
    calls = sorted((s, s + d) for s, d, n in events["host"]
                   if n == "match_many" and s >= w0 and s + d <= w1)
    devs = {k: v for k, v in events["devices"].items() if v}
    n = len(devs)
    ns = 1e-9
    spans = [(s, s + d, name) for s, d, name in events.get("spans", [])]
    names = sorted({name for _, _, name in spans})
    inner = _Innermost(_clip_named(spans, w0, w1),
                       [x for call in calls for x in call])

    def span_union(name):
        return union(_clip([(s, e) for s, e, m in spans if m == name],
                           w0, w1))

    readback = span_union(PREFIX + "readback")
    scoped = events.get("scoped", {})
    scope_len = dict.fromkeys(SCOPES, 0.0)
    readback_idle = 0.0
    split: dict = {}
    gaps: list = []
    for dev, ops in devs.items():
        merged = union(_clip([(s, s + d) for s, d, _ in ops], w0, w1))
        for name in SCOPES:
            scope_len[name] += _length(union(_clip(
                [(s, s + d) for s, d, scope in scoped.get(dev, ())
                 if scope == name], w0, w1)))
        readback_idle += _length(readback) - _overlap(readback, merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                mid = (g0 + g1) / 2
                gaps.append((g1 - g0, _join(
                    trace_reduce._label(mid, calls, merged), inner.at(mid))))
                for lo, hi, span in inner.pieces(g0, g1):
                    key = _join(trace_reduce._label((lo + hi) / 2, calls,
                                                    merged), span)
                    split[key] = split.get(key, 0.0) + hi - lo
    present = {scope for ops in scoped.values() for _, _, scope in ops}
    scope_s = {k: v / n * ns for k, v in scope_len.items() if k in present}
    out.update({
        "viterbi_s": scope_s.get("viterbi"),
        "scope_s": scope_s,
        "walk_s": (_length(span_union(PREFIX + "walk")) * ns
                   if PREFIX + "walk" in names else None),
        "readback_s": (readback_idle / n * ns
                       if PREFIX + "readback" in names else None),
        "span_s": {m: _length(span_union(m)) * ns for m in names},
        "idle_split": sorted(([k, v / n * ns] for k, v in split.items()),
                             key=lambda kv: -kv[1]),
        "idle_gaps": [[lab, g * ns] for g, lab in
                      sorted(gaps, key=lambda x: -x[0])[:top]],
    })
    return out
