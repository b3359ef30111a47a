"""Device-program contract checker — the round-14 static gates' device twin.

The wire programs' correctness/perf contracts (pinned u16/u32 wire
dtypes, i8/i16/f32 infeed, no host round-trips inside jitted bodies, one
jit boundary around ``shard_map``) were enforced only by running on
hardware, so between chip runs violations would ship blind. This module
enforces them by ABSTRACT interpretation: ``jax.make_jaxpr`` traces
every wire entry
(``ops.match.wire_from_*``) across the full audit matrix — three
dense-sweep kernel arms (whole-block / two-level subcull / MXU) × three
wire layouts (compact u16 2-lane / full u16 3-lane / packed u32 1-lane)
× {single-device, mesh} — on a CPU host, no device needed, and walks the
closed jaxprs. Rules:

  device-x64         a 64-bit aval (f64/i64) anywhere in a jitted wire
                     body. Tracing runs with x64 ENABLED so every
                     unpinned dtype derivation widens and becomes
                     visible; under the production x32 runtime the same
                     sites silently compute in 32 bits TODAY, but they
                     are one ``jax_enable_x64`` away from doubling the
                     device bytes (weak-typed Python literal scalars are
                     exempt — they never promote their consumers).
  device-callback    host callbacks / transfers inside the jitted body
                     (pure_callback / io_callback / debug_callback /
                     infeed / outfeed / device_put): each is a host
                     round-trip serialized into the device program.
  device-nested-jit  a ``jit`` of substance nested inside a
                     ``shard_map`` body (the lexical wire-fork lint sees
                     only the direct-argument spelling; this is the
                     semantic check over the traced program). jnp's own
                     tiny wrapper jits (where/clip/round, <= a handful
                     of eqns) are structural noise and exempt.
  device-wire-dtype  the traced entry's output aval does not carry its
                     layout's pinned wire dtype/lane shape (u16 [B,2,T]
                     compact, u16 [B,3,T] full, u32 [B,1,T] packed).
  device-trace       an audit case failed to trace at all (usually a
                     dtype mismatch a 64-bit widening forced into a scan
                     carry — the finding carries the trace error).

Findings are attributed to the source line the jaxpr equation's
traceback points at, so the r14 waiver grammar applies unchanged:
``# lint: allow[device-x64] YYYY-MM-DD reason`` on (or above) the line.

Run via ``python -m reporter_tpu.analysis --device`` (also checks the
committed compile-shape manifest and the static SMEM/HBM budgets —
analysis/compile_manifest.py); CI-pinned by tests/test_device_contract.py
and a named rung in ``__graft_entry__.py``'s multichip dry-run.
"""

from __future__ import annotations

import os
from typing import Any, Iterable

from reporter_tpu.analysis.lint_rules import (Finding, REPO_ROOT, _apply_waivers,
                                              _dedupe, _load)

__all__ = ["run_device_contract", "audit_jaxpr", "check_wire_avals",
           "AuditCase", "audit_cases", "main", "RULES"]

RULES = ("device-x64", "device-callback", "device-nested-jit",
         "device-wire-dtype", "device-trace")

# primitives that are host round-trips when they appear inside a jitted
# device body (callback-family names are also matched by substring —
# jax grows spellings faster than this list)
_DENY_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "infeed", "outfeed", "device_put",
})

# a jit inside shard_map smaller than this is one of jnp's own wrapper
# jits (where/clip/round/pad trace as 1-4 eqn jits — measured on the
# full wire program); a user-nested jit of any real kernel body is
# hundreds of eqns
_NESTED_JIT_MIN_EQNS = 12

# the audit's trace shapes: tiny on purpose — trace cost is essentially
# shape-independent and the jaxpr structure is identical at any [B, T]
_B, _T = 2, 16
# edge count for the big-metro layouts (> ops.match._COMPACT_WIRE_EDGES
# so the 3-lane / packed branches are the ones traced)
_E_BIG = 50_000
_BIG_MAX_EDGE_LEN = 500.0


class AuditCase:
    """One cell of the audit matrix."""

    __slots__ = ("entry", "arm", "layout", "path")

    def __init__(self, entry: str, arm: str, layout: str, path: str):
        self.entry = entry      # "f32" | "q16" | "q8"
        self.arm = arm          # "subcull" | "block" | "mxu"
        self.layout = layout    # "compact" | "full" | "packed"
        self.path = path        # "single" | "mesh"

    @property
    def label(self) -> str:
        return f"{self.entry}/{self.arm}/{self.layout}/{self.path}"


def audit_cases() -> "list[AuditCase]":
    import itertools

    return [AuditCase(*c) for c in itertools.product(
        ("f32", "q16", "q8"), ("subcull", "block", "mxu"),
        ("compact", "full", "packed"), ("single", "mesh"))]


# ---------------------------------------------------------------------------
# jaxpr walking

def _rel(path: str) -> str:
    try:
        rel = os.path.relpath(path, REPO_ROOT)
    except ValueError:          # pragma: no cover - windows drive mismatch
        return path
    return rel if not rel.startswith("..") else path


def _eqn_site(eqn) -> "tuple[str, int] | None":
    """(repo-relative path, line) of the reporter_tpu frame an equation
    was traced from, or None when the trace has no repo frame."""
    tb = getattr(eqn.source_info, "traceback", None)
    if tb is None:
        return None
    for f in tb.frames:
        if "reporter_tpu" in f.file_name and "analysis" not in f.file_name:
            return _rel(f.file_name), int(f.line_num)
    return None


def _sub_jaxprs(eqn) -> Iterable[Any]:
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for x in vs:
            if hasattr(x, "jaxpr"):        # ClosedJaxpr
                yield x.jaxpr
            elif hasattr(x, "eqns"):       # raw Jaxpr
                yield x


def _is_x64_leak(aval) -> bool:
    dt = getattr(aval, "dtype", None)
    if dt is None or dt.itemsize != 8:
        return False
    # weak-typed rank-0 avals are the jaxpr representation of Python
    # literal scalars: they never promote a 32-bit consumer, and under
    # the x32 runtime they are the same weak f32/i32 — not a leak
    if getattr(aval, "weak_type", False) and not aval.shape:
        return False
    return True


def audit_jaxpr(closed, label: str,
                fallback_site: "tuple[str, int]") -> "list[Finding]":
    """Walk one closed jaxpr, returning device-contract findings.
    ``fallback_site`` attributes equations with no repo frame (pure
    jax-internal provenance)."""
    findings: "list[Finding]" = []

    def visit(jaxpr, inside_shard_map: bool) -> None:
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            site = _eqn_site(eqn) or fallback_site
            if name in _DENY_PRIMITIVES or "callback" in name:
                findings.append(Finding(
                    "device-callback", site[0], site[1],
                    f"host primitive {name} inside the jitted device "
                    f"body ({label}) — a host round-trip serialized "
                    "into the device program; hoist it out of the wire "
                    "path"))
            if name == "jit" and inside_shard_map:
                inner = eqn.params.get("jaxpr")
                n = len(inner.jaxpr.eqns) if inner is not None else 0
                if n >= _NESTED_JIT_MIN_EQNS:
                    findings.append(Finding(
                        "device-nested-jit", site[0], site[1],
                        f"jit of substance ({n} eqns) nested inside "
                        f"shard_map ({label}) — jit goes OUTSIDE "
                        "shard_map (jax.jit(shard_map(wire_from_*)))"))
            for v in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(v, "aval", None)
                if aval is not None and _is_x64_leak(aval):
                    findings.append(Finding(
                        "device-x64", site[0], site[1],
                        f"64-bit aval {aval.dtype} at primitive {name} "
                        "in a jitted wire body — pin the dtype (the "
                        "x64 audit widens every unpinned derivation; "
                        "wire programs carry u16/u32/i8/i16/f32 only)"))
            nested = inside_shard_map or name == "shard_map"
            for sub in _sub_jaxprs(eqn):
                visit(sub, nested)

    visit(closed.jaxpr, False)
    return _dedupe(findings)


_WIRE_AVAL_EXPECT = {
    "compact": ("uint16", 2),
    "full": ("uint16", 3),
    "packed": ("uint32", 1),
}


def check_wire_avals(out_avals, layout: str, label: str,
                     site: "tuple[str, int]") -> "list[Finding]":
    """The end-to-end dtype pin: the traced entry must emit exactly its
    layout's wire array — one [B, lanes, T] array of the pinned dtype."""
    want_dtype, want_lanes = _WIRE_AVAL_EXPECT[layout]
    out: "list[Finding]" = []
    ok = (len(out_avals) == 1
          and str(out_avals[0].dtype) == want_dtype
          and len(out_avals[0].shape) == 3
          and int(out_avals[0].shape[1]) == want_lanes)
    if not ok:
        got = [f"{a.dtype}{list(a.shape)}" for a in out_avals]
        out.append(Finding(
            "device-wire-dtype", site[0], site[1],
            f"wire output of {label} is {got}, expected one "
            f"{want_dtype}[B,{want_lanes},T] array — the {layout} "
            "layout's pinned wire format"))
    return out


# ---------------------------------------------------------------------------
# the tracer

def _ensure_cpu_devices():
    """CPU devices for the mesh leg: the audit traces abstract programs
    only, so it never needs (or claims) the chip — restrict the platform
    to CPU before any backend exists, as the __graft_entry__ dry-run
    does. No-op when a backend (tier-1's pinned CPU) is already up."""
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass                    # a backend already exists; use it as-is
    devs = jax.local_devices(backend="cpu")
    if not devs:                # pragma: no cover - defensive
        raise RuntimeError("device-contract audit needs a CPU backend")
    return devs


def _tiny_tileset():
    from reporter_tpu.config import CompilerParams
    from reporter_tpu.netgen.synthetic import generate_city
    from reporter_tpu.tiles.compiler import compile_network

    return compile_network(generate_city("tiny"),
                           CompilerParams(reach_radius=400.0))


def _abstract_tables(ts, big_metro: bool):
    """The staged dense layout as ShapeDtypeStructs — shapes from a real
    tiny tileset's ``host_tables`` so the audit can never drift from the
    staging layout; the big-metro variant rescales only the edge-indexed
    arrays (the wire layout dispatches statically on the edge count)."""
    import jax

    host = ts.host_tables("dense")
    sds = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
           for k, v in host.items()}
    if big_metro:
        for k in ("edge_len", "reach_row", "edge_osmlr"):
            sds[k] = jax.ShapeDtypeStruct((_E_BIG,), sds[k].dtype)
    return sds


def _entry_args(entry: str):
    import jax
    import jax.numpy as jnp

    pts = jax.ShapeDtypeStruct((_B, _T, 2), jnp.float32)
    origins = jax.ShapeDtypeStruct((_B, 2), jnp.float32)
    lens = jax.ShapeDtypeStruct((_B,), jnp.int32)
    if entry == "f32":
        return (pts, lens)
    if entry == "q16":
        return (jax.ShapeDtypeStruct((_B, _T, 2), jnp.int16), origins, lens)
    return (jax.ShapeDtypeStruct((_B, _T, 2), jnp.int8), origins, lens)


def _arm_plan(arm: str):
    from reporter_tpu.matcher.autotune import TunedPlan

    # bf16 operands = the MXU arm the bench A/B measures
    return TunedPlan(arm=arm, lowp="bf16" if arm == "mxu" else "off")


def _layout_spec(layout: str):
    from reporter_tpu.ops.match import wire_spec

    if layout != "packed":
        return None
    spec = wire_spec(_E_BIG, _BIG_MAX_EDGE_LEN)
    if spec is None:            # pragma: no cover - layout math regressed
        raise RuntimeError(
            f"wire_spec({_E_BIG}, {_BIG_MAX_EDGE_LEN}) rejected the "
            "packed layout the audit exists to cover")
    return spec


def _entry_site(entry: str) -> "tuple[str, int]":
    """(path, def line) of the wire entry — the fallback attribution and
    the anchor for case-level findings."""
    import inspect

    from reporter_tpu.ops import match

    impl = {"f32": match.wire_from_f32, "q16": match.wire_from_q16,
            "q8": match.wire_from_q8}[entry]
    try:
        line = inspect.getsourcelines(impl)[1]
    except OSError:             # pragma: no cover - no source available
        line = 1
    return "reporter_tpu/ops/match.py", line


def _trace_case(case: AuditCase, ts, tables, mesh):
    """ClosedJaxpr of one audit cell. x64 must already be enabled and the
    pallas override active (run_device_contract holds both contexts)."""
    import jax

    from reporter_tpu.ops import match

    impl = {"f32": match.wire_from_f32, "q16": match.wire_from_q16,
            "q8": match.wire_from_q8}[case.entry]
    from reporter_tpu.config import MatcherParams

    params = MatcherParams(candidate_backend="dense")
    plan = _arm_plan(case.arm)
    spec = _layout_spec(case.layout)
    args = _entry_args(case.entry)
    if case.path == "single":
        def fn(tb, *a):
            return impl(*a, tb, ts.meta, params, None, spec, plan)

        return jax.make_jaxpr(fn)(tables, *args)
    from reporter_tpu.parallel.dp_e2e import mesh_wire_fn

    fn = mesh_wire_fn(mesh, case.entry, ts.meta, params, spec, tables,
                      has_acc=False, plan=plan)
    return jax.make_jaxpr(fn)(*args, tables)


def _audit_histogram() -> "list[Finding]":
    """The other jitted scatter on the product path: SpeedHistogram's
    fixed-shape accumulate (r12 — ONE batch shape). Same rules, same
    x64 widening discipline."""
    import jax
    import jax.numpy as jnp

    from reporter_tpu.streaming import histogram as hg

    cap = hg.SpeedHistogram._CAP
    closed = jax.make_jaxpr(hg._accumulate)(
        jax.ShapeDtypeStruct((64, 12), jnp.int32),
        jax.ShapeDtypeStruct((cap,), jnp.int32),
        jax.ShapeDtypeStruct((cap,), jnp.int32),
        jax.ShapeDtypeStruct((cap,), jnp.bool_))
    return audit_jaxpr(closed, "histogram/scatter",
                       ("reporter_tpu/streaming/histogram.py", 1))


def _audit_backfill_scatter(mesh) -> "list[Finding]":
    """Round 20: the backfill aggregates' shared FLAT scatter
    (ops/aggregate.py) — same fixed-batch-shape discipline as the
    histogram, audited under the same x64 widening rules. Round 21 adds
    the mesh-sharded case through the SAME program builder the serving
    path uses (agg.mesh_scatter_fn — per-device partial grids, leading
    dim sharded; the jaxpr structure is device-count independent, so the
    1-device audit mesh suffices here too)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from reporter_tpu.ops import aggregate as agg

    cap = agg._CAP
    site = ("reporter_tpu/ops/aggregate.py", 1)
    closed = jax.make_jaxpr(agg._scatter_add)(
        jax.ShapeDtypeStruct((4096,), jnp.int32),
        jax.ShapeDtypeStruct((cap,), jnp.int32),
        jax.ShapeDtypeStruct((cap,), jnp.bool_))
    findings = audit_jaxpr(closed, "backfill/scatter", site)
    ndev = int(np.prod(tuple(mesh.shape.values())))
    closed_mesh = jax.make_jaxpr(agg.mesh_scatter_fn(mesh))(
        jax.ShapeDtypeStruct((ndev, 4096), jnp.int32),
        jax.ShapeDtypeStruct((ndev, cap), jnp.int32),
        jax.ShapeDtypeStruct((ndev, cap), jnp.bool_))
    findings.extend(audit_jaxpr(closed_mesh, "backfill/scatter-mesh", site))
    return findings


def _merge_across_cases(findings: "list[Finding]") -> "list[Finding]":
    """One finding per (rule, path, line): a shared-code violation is hit
    by most of the 54 matrix cells (every case traces the same viterbi),
    and 54 near-identical lines would drown the gate output. The first
    case's message survives with a count of the rest."""
    merged: "dict[tuple, Finding]" = {}
    extra: "dict[tuple, int]" = {}
    for f in findings:
        key = (f.rule, f.path, f.line)
        if key in merged:
            if f.message != merged[key].message:
                extra[key] = extra.get(key, 0) + 1
        else:
            merged[key] = f
    for key, n in extra.items():
        merged[key].message += f" [+{n} more audit case(s) hit this site]"
    return list(merged.values())


def run_device_contract(root: str = REPO_ROOT) -> "list[Finding]":
    """Trace + audit the full matrix; returns waiver-applied findings."""
    import jax

    from reporter_tpu.ops import dense_candidates as dc
    from reporter_tpu.parallel.compat import shard_map  # noqa: F401  (import
    #             here so a broken shim fails the gate, not the serving path)
    from jax.sharding import Mesh

    import numpy as np

    devs = _ensure_cpu_devices()
    ts = _tiny_tileset()
    # ONE device is enough to trace the shard_map product program (the
    # jaxpr structure is device-count independent); it also keeps the
    # audit deterministic between the CLI (1 CPU device) and tier-1's
    # 8-device virtual mesh
    mesh = Mesh(np.asarray(devs[:1]), ("dp",))
    tables_small = _abstract_tables(ts, big_metro=False)
    tables_big = _abstract_tables(ts, big_metro=True)

    findings: "list[Finding]" = []
    with jax.enable_x64(True), dc.pallas_trace_override():
        for case in audit_cases():
            tables = tables_small if case.layout == "compact" else tables_big
            site = _entry_site(case.entry)
            try:
                closed = _trace_case(case, ts, tables, mesh)
            except Exception as exc:   # noqa: BLE001 - the finding carries it
                findings.append(Finding(
                    "device-trace", site[0], site[1],
                    f"audit case {case.label} failed to trace: "
                    f"{type(exc).__name__}: {str(exc).splitlines()[0][:160]}"))
                continue
            findings.extend(audit_jaxpr(closed, case.label, site))
            findings.extend(check_wire_avals(closed.out_avals, case.layout,
                                             case.label, site))
        findings.extend(_audit_histogram())
        findings.extend(_audit_backfill_scatter(mesh))

    findings = _merge_across_cases(findings)
    by_path: "dict[str, list[Finding]]" = {}
    for f in findings:
        by_path.setdefault(f.path, []).append(f)
    for path, group in by_path.items():
        mod = _load(os.path.join(root, path), root)
        if mod is not None:
            _apply_waivers(mod, group)
    return findings


def main(argv: "list[str] | None" = None) -> int:
    """The ``--device`` gate: jaxpr audit + compile-shape manifest +
    static SMEM/HBM budget checks. Exit 1 on any unwaived finding."""
    from reporter_tpu.analysis import compile_manifest

    findings = run_device_contract()
    problems = list(compile_manifest.check())
    for f in findings:
        print(f)
    for p in problems:
        print(f"compile-manifest: {p}")
    unwaived = [f for f in findings if not f.waived]
    n_cases = len(audit_cases())
    print(f"device contract: {n_cases} audit cases, {len(findings)} "
          f"finding(s), {len(unwaived)} unwaived; manifest "
          f"{'DRIFTED' if problems else 'pinned'}")
    return 1 if (unwaived or problems) else 0
