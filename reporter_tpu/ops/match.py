"""Fused per-trace match pipeline and its batched (vmapped) form.

This is the device program that replaces the region between
``segment_matcher.Match(`` and the edge walk in the reference's call stack
(SURVEY.md §3.5): candidates → emission/transition → Viterbi, all under one
`jit`, vmapped across a batch of padded traces. Host code (matcher/) turns
the per-point (edge, offset) output into OSMLR segment reports.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from reporter_tpu.config import MatcherParams
from reporter_tpu.matcher.autotune import TunedPlan
from reporter_tpu.ops.candidates import CandidateSet, find_candidates_trace
from reporter_tpu.ops.dense_candidates import find_candidates_dense
from reporter_tpu.ops.hmm import viterbi_decode_batched
from reporter_tpu.tiles.tileset import TileMeta


class MatchOutput(NamedTuple):
    """Per-point match result (fixed [.., T] shapes; -1 = unmatched)."""

    edge: jnp.ndarray         # i32 [.., T]
    offset: jnp.ndarray       # f32 [.., T]
    chain_start: jnp.ndarray  # bool [.., T]
    matched: jnp.ndarray      # bool [.., T]


def _check_grid_coverage(params: MatcherParams, meta) -> None:
    if params.search_radius > meta.index_radius:
        # Trace-time check (both are static): the single-cell gather only
        # covers the registration dilation, so a radius beyond index_radius
        # silently drops roads. (Dense backend sweeps everything — exempt.)
        raise ValueError(
            f"search_radius ({params.search_radius}) exceeds tile "
            f"index_radius ({meta.index_radius}); recompile tiles with "
            "index_radius >= radius")


_DEFAULT_PLAN = TunedPlan()


@jax.named_scope("candidates")
def batch_candidates(points, valid_pt, tables, meta,
                     params: MatcherParams,
                     plan: TunedPlan = _DEFAULT_PLAN) -> CandidateSet:
    """Candidates for a batch of traces: points f32 [B, T, 2] → [B, T, K].

    Runs under the named scope ``candidates`` (op metadata only: the
    device trace attributes its ops by it; the program is unchanged).

    Backend dispatch (params.candidate_backend is static):
      dense — ONE pallas sweep over the flattened [B*T] point batch (the
              kernel amortizes its segment-block DMA across every trace),
              with the kernel arm, lowp dtype and narrow-grid rung of
              ``plan`` (matcher/autotune.TunedPlan, static);
      grid  — per-point cell-row gather, vmapped per trace.
    """
    B, T = points.shape[:2]
    backend = params.candidate_backend
    if backend == "auto":
        # trace-time resolution: the sweep wins ~50x on accelerators, the
        # gather wins ~50x on CPU (XLA CPU gathers are cheap; an O(S)
        # sweep per chunk is not)
        backend = "grid" if jax.default_backend() == "cpu" else "dense"
    if backend == "dense":
        flat = find_candidates_dense(
            points.reshape(B * T, 2),
            (tables["seg_pack"], tables["seg_bbox"],
             tables.get("seg_sub"), tables.get("seg_feat")),
            params.search_radius, params.max_candidates,
            valid=valid_pt.reshape(B * T),
            subcull=plan.arm != "block", lowp=plan.lowp,
            mxu=plan.arm == "mxu", nj_cap=plan.nj_cap)
        return CandidateSet(*(x.reshape(B, T, -1) for x in flat))
    if backend != "grid":
        raise ValueError(
            f"unknown candidate_backend {params.candidate_backend!r}; "
            "use 'auto', 'dense' or 'grid'")
    _check_grid_coverage(params, meta)
    return jax.vmap(lambda p: find_candidates_trace(
        p, tables, meta, params.search_radius, params.max_candidates))(points)


def match_trace(points, valid_pt, tables, meta,
                params: MatcherParams) -> MatchOutput:
    """Match ONE padded trace: points f32 [T, 2], valid_pt bool [T].

    meta: TileMeta (static) or ops.candidates.GridMeta (scalars, possibly
    traced — the multimetro sharded path).
    """
    out = match_traces(points[None], valid_pt[None], tables, meta, params)
    return MatchOutput(*(x[0] for x in out))


def match_traces(points, valid_pt, tables, meta,
                 params: MatcherParams, acc_scale=None,
                 plan: TunedPlan = _DEFAULT_PLAN) -> MatchOutput:
    """Match a batch (not jitted — compose under jit/vmap/shard_map):
    points f32 [B, T, 2], valid_pt bool [B, T].

    acc_scale f32 [B, T] (optional): per-point GPS-accuracy emission
    scaling. Meili scales the emission sigma by each point's reported
    accuracy; since emission = d²/(2σ²) = (d·σ_z/σ)²/(2σ_z²), scaling the
    candidate DISTANCES by σ_z/σ_point implements per-point σ without
    touching the cost model or the wire format (scaling is uniform within
    a point, so top-K candidate selection is unchanged).

    plan: the dense sweep's kernel plan (see batch_candidates).
    """
    cands = batch_candidates(points, valid_pt, tables, meta, params, plan)
    if acc_scale is not None:
        cands = cands._replace(dist=cands.dist * acc_scale[..., None])
    vit = viterbi_decode_batched(
        cands, points, valid_pt, tables,
        params.sigma_z, params.beta, params.max_route_distance_factor,
        params.breakage_distance, params.backward_slack,
        params.interpolation_distance)
    return MatchOutput(edge=vit.edge, offset=vit.offset,
                       chain_start=vit.chain_start, matched=vit.matched)


@functools.partial(jax.jit, static_argnames=("meta", "params"))
def match_batch(points, valid_pt, tables: dict[str, Any], meta: TileMeta,
                params: MatcherParams) -> MatchOutput:
    """Match a batch: points f32 [B, T, 2], valid_pt bool [B, T].

    meta and params are hashable statics — one compilation per (T, K, tile
    geometry, param set), then every batch reuses the executable
    (SURVEY.md §7.5 "jit persistence").
    """
    return match_traces(points, valid_pt, tables, meta, params)


# Wire format (match_batch_wire): ONE array so the decode result crosses
# the device→host link as a single transfer. Three layouts, chosen
# statically from the tile (unpack_wire dispatches on lane count/dtype):
#   compact u16 [B, 2, T]  — metros ≤ _COMPACT_WIRE_EDGES edges:
#     lane 0 offset (0.25 m fixed point), lane 1 id(14)|start|matched
#   packed  u32 [B, 1, T]  — bigger metros whenever wire_spec() accepts:
#     offset(ob) | edge(30-ob) | start<<30 | matched<<31 (same bytes as
#     compact; -33% vs the 3-lane fallback on the readback-bound path)
#   full    u16 [B, 3, T]  — the fallback (multi-km edges at ~0.5M ids):
#     lane 0 offset, lane 1 id low 16, lane 2 id hi(13)|start|matched
OFFSET_QUANTUM = 0.25


def wire_from_f32(points, lengths, tables: dict[str, Any], meta: TileMeta,
                  params: MatcherParams, acc_scale=None, spec=None,
                  plan: TunedPlan = _DEFAULT_PLAN):
    """points f32 [B, T, 2], lengths i32 [B] (valid prefix per trace) →
    u16 [B, 2|3, T] wire array; unpack with unpack_wire(). acc_scale: see
    match_traces (None traces a separate, scale-free executable, so
    accuracy-less batches pay nothing). Undecorated body: jit via
    match_batch_wire, or wrap in shard_map (parallel/dp_e2e) — the SAME
    device program serves both so the sharded product path cannot drift.
    plan: the sweep's kernel plan, a jit static like params."""
    T = points.shape[1]
    valid = jnp.arange(T, dtype=jnp.int32)[None, :] < lengths[:, None]
    out = match_traces(points, valid, tables, meta, params, acc_scale, plan)
    return _pack_wire(out, tables["edge_len"].shape[0], spec)


def wire_from_q16(points_q, origins, lengths, tables: dict[str, Any],
                  meta: TileMeta, params: MatcherParams, acc_scale=None,
                  spec=None, plan: TunedPlan = _DEFAULT_PLAN):
    """Quantized-input variant: points_q i16 [B, T, 2] are 0.25 m
    fixed-point offsets from per-trace origins f32 [B, 2] (host→device
    bytes halve vs f32; 0.125 m quantization ≪ sigma_z). Traces spanning
    beyond ±8.19 km of their origin don't fit i16 — the host batcher
    (matcher/api._decode_many) falls back to the f32 entry for those."""
    T = points_q.shape[1]
    points = origins[:, None, :] + points_q.astype(jnp.float32) * jnp.float32(
        OFFSET_QUANTUM)
    valid = jnp.arange(T, dtype=jnp.int32)[None, :] < lengths[:, None]
    out = match_traces(points, valid, tables, meta, params, acc_scale, plan)
    return _pack_wire(out, tables["edge_len"].shape[0], spec)


def wire_from_q8(deltas_q, origins, lengths, tables: dict[str, Any],
                 meta: TileMeta, params: MatcherParams,
                 acc_scale=None, spec=None,
                 plan: TunedPlan = _DEFAULT_PLAN):
    """Delta-quantized input: deltas_q i8 [B, T, 2] are the per-step
    DIFFERENCES of the i16 0.25 m quanta (first step 0 — the origin is
    the first point). Integer cumsum reconstructs the i16 absolutes
    EXACTLY, so this path is bit-identical to match_batch_wire_q on every
    valid point at half the host→device bytes — consecutive GPS points
    at 1 Hz move well under the ±31.75 m an i8 delta can express; the
    host batcher zeroes pad-region deltas (padded positions sit at the
    last valid point, mask-excluded) and falls back to i16 when a real
    step doesn't fit."""
    q = jnp.cumsum(deltas_q.astype(jnp.int32), axis=1)
    points = origins[:, None, :] + q.astype(jnp.float32) * jnp.float32(
        OFFSET_QUANTUM)
    T = deltas_q.shape[1]
    valid = jnp.arange(T, dtype=jnp.int32)[None, :] < lengths[:, None]
    out = match_traces(points, valid, tables, meta, params, acc_scale, plan)
    return _pack_wire(out, tables["edge_len"].shape[0], spec)


_WIRE_STATICS = ("meta", "params", "spec", "plan")
match_batch_wire = jax.jit(wire_from_f32, static_argnames=_WIRE_STATICS)
match_batch_wire_q = jax.jit(wire_from_q16, static_argnames=_WIRE_STATICS)
match_batch_wire_q8 = jax.jit(wire_from_q8, static_argnames=_WIRE_STATICS)


# Compact 2-lane format: metros under _COMPACT_WIRE_EDGES directed edges
# (most single-city tiles — sf's 5.3k qualifies, bayarea's 54k does not)
# fit the edge id in 14 bits, so lane 1 carries id | start | matched and
# lane 0 the offset — one third fewer device→host bytes on exactly the
# link-bound path. The format is chosen statically from the edge count
# (tables shape → trace-time constant); unpack_wire dispatches on the
# lane-count axis, so every consumer handles both.
_COMPACT_WIRE_EDGES = 1 << 14


def wire_spec(num_edges: int, max_edge_len: float) -> "tuple | None":
    """Packed-u32 wire layout for metros past the compact-u16 range, or
    None where the 3-lane u16 fallback must carry the result.

    Layout: offset quanta in the low ``ob`` bits, edge id in the next
    30-ob bits, chain_start at 30, matched at 31 — ONE u32 lane instead
    of three u16 lanes (-33% of the device→host bytes that bound big-
    metro decode; the downlink streams ~11 MB/s). ``ob`` shrinks as the
    edge count grows; the offset quantum is max(0.25 m, Lmax/(2^ob-1)),
    and when that would exceed 0.5 m (multi-km edges on a ~500k-edge
    tile) the format is rejected (None) rather than degrading offsets."""
    if num_edges <= _COMPACT_WIRE_EDGES:
        return None                      # compact u16 is already 4 B/pt
    eb = max(15, int(np.ceil(np.log2(max(num_edges, 2)))))
    ob = 30 - eb
    if ob < 8:
        return None
    q = max(OFFSET_QUANTUM, float(max_edge_len) / ((1 << ob) - 1))
    return (ob, q) if q <= 0.5 else None


@jax.named_scope("wire_pack")
def _pack_wire(out: MatchOutput, num_edges: int,
               spec: "tuple | None" = None):
    edge = jnp.maximum(out.edge, 0).astype(jnp.uint32)
    if spec is not None and num_edges > _COMPACT_WIRE_EDGES:
        ob, q = spec
        off_q = jnp.clip(jnp.round(out.offset / q),
                         0, (1 << ob) - 1).astype(jnp.uint32)
        w = (off_q | (edge << ob)
             | (out.chain_start.astype(jnp.uint32) << 30)
             | (out.matched.astype(jnp.uint32) << 31))
        return w[:, None, :]
    off_q = jnp.clip(jnp.round(out.offset / OFFSET_QUANTUM), 0, 65535)
    w0 = off_q.astype(jnp.uint16)
    if num_edges <= _COMPACT_WIRE_EDGES:
        w1 = (edge & 0x3FFF
              | (out.chain_start.astype(jnp.uint32) << 14)
              | (out.matched.astype(jnp.uint32) << 15)).astype(jnp.uint16)
        return jnp.stack([w0, w1], axis=1)
    w1 = (edge & 0xFFFF).astype(jnp.uint16)
    w2 = ((edge >> 16) & 0x1FFF
          | (out.chain_start.astype(jnp.uint32) << 14)
          | (out.matched.astype(jnp.uint32) << 15)).astype(jnp.uint16)
    return jnp.stack([w0, w1, w2], axis=1)


def unpack_wire(wire, spec: "tuple | None" = None) -> tuple[Any, Any, Any]:
    """numpy unpack: u16 [B, 2|3, T] (or packed u32 [B, 1, T] with its
    ``spec`` from wire_spec) → (edges i32 [B,T] with -1 unmatched,
    offsets f32 [B,T], chain_starts bool [B,T])."""
    if wire.dtype == np.uint32:             # packed u32: off | edge | s | m
        if spec is None:
            raise ValueError(
                "unpack_wire: uint32 wire requires the wire_spec it was "
                "packed with (pass spec=wire_spec(...) from the matcher)")
        ob, q = spec
        w = np.asarray(wire[:, 0], np.int64)
        matched = (w >> 31) & 1
        edges = np.where(matched == 1,
                         (w >> ob) & ((1 << (30 - ob)) - 1), -1)
        starts = ((w >> 30) & 1).astype(bool)
        offsets = ((w & ((1 << ob) - 1)) * q).astype(np.float32)
        return edges.astype(np.int32), offsets, starts
    w0 = wire[:, 0].astype(np.int64)
    w1 = wire[:, 1].astype(np.int64)
    if wire.shape[1] == 2:                  # compact: id(14) | start | matched
        matched = (w1 >> 15) & 1
        edges = np.where(matched == 1, w1 & 0x3FFF, -1)
        starts = ((w1 >> 14) & 1).astype(bool)
    else:
        w2 = wire[:, 2].astype(np.int64)
        matched = (w2 >> 15) & 1
        edges = np.where(matched == 1, w1 | ((w2 & 0x1FFF) << 16), -1)
        starts = ((w2 >> 14) & 1).astype(bool)
    offsets = (w0 * OFFSET_QUANTUM).astype(np.float32)
    return edges.astype(np.int32), offsets, starts
