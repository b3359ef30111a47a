"""Dense candidate search: a Pallas TPU sweep kernel, zero gathers.

Replaces the grid-gather candidate path (ops/candidates.py) on TPU. The
grid path is the idiomatic CPU design (Meili's CandidateGridQuery,
SURVEY.md §2.2 "Candidate search"): hash into a cell, inspect only local
segments. On TPU that turns into a data-dependent row gather per probe
point, and XLA lowers those to serialized dynamic-slices — measured ~3.3 µs
per row on v5e, 80 ms for a 24k-point batch, dominating the whole matcher.

The TPU-first formulation inverts it: stream *segment blocks* past each
*point chunk* and keep a running top-K of distinct edges in VMEM scratch.
All regular VPU work — no gathers, no data-dependent addressing, nothing
for the compiler to serialize.

Three levels of work avoidance keep it output-sensitive:

1. **Spatial blocks** — build_seg_pack sorts segments by Morton code of
   their midpoint, so each SBLK-column block covers a compact region, and
   records per-block bboxes.
2. **Block culling (scalar prefetch)** — before the kernel, a tiny jnp
   pre-pass intersects each point-chunk's (sub-)bboxes with the block
   bboxes and emits a per-chunk id list with the relevant (hit) blocks
   first. The segment BlockSpec's index_map reads the prefetched list, so
   only relevant blocks are ever DMA'd. The list keeps full nblocks width
   (completeness by construction — no truncation); pad slots repeat the
   previous id, which skips both the re-fetch (equal consecutive indices)
   and, via the in-kernel `fresh` predicate, all the VPU work.
3. **Early-out** — a block whose segments all miss the radius skips the
   top-K selection entirely (`pl.when` on the block-min distance).

Output contract matches ops.candidates.find_candidates_trace: top-K
*distinct* edges per point, each edge represented by its closest
projection (Meili keeps one candidate per edge).
"""

from __future__ import annotations

import contextlib
import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from reporter_tpu.ops.candidates import CandidateSet

BIG = 1e30  # python float: pallas kernels reject captured jnp constants

try:  # pallas lowers on TPU backends; keep CPU-only envs importable
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
except Exception as _exc:  # pragma: no cover
    pl = None
    pltpu = None
    _PALLAS_IMPORT_ERROR = repr(_exc)
else:
    _PALLAS_IMPORT_ERROR = None

# seg_pack component rows
SP_AX, SP_AY, SP_BX, SP_BY, SP_OFF, SP_LEN, SP_EDGE, SP_SPARE = range(8)
SP_NCOMP = 8

# seg_feat component rows (round 13, the MXU coarse pass): per-column
# quadratic-form coefficients such that, for a point recentered on the
# column's SUB-slice center q = p - c,
#   A*qx^2 + B*qy^2 + C*qx*qy + D*qx + E*qy + F
#     == squared distance from p to the segment's INFINITE line,
# a lower bound on the exact point-to-segment distance (the clamp to the
# endpoints only ever moves the closest point further away). One
# [P, 8] @ [8, subw] dot per surviving slice evaluates the whole slice's
# coarse distances on the MXU. Rows CX/CY carry the slice center the
# coefficients were recentered on — the kernel reads it from HERE, never
# recomputes it, so host/device center drift is impossible by
# construction. Padding columns carry F = BIG (coarse distance BIG →
# never admit a pair on their own).
SF_A, SF_B, SF_C, SF_D, SF_E, SF_F, SF_CX, SF_CY = range(8)
SF_NCOMP = 8

# Conservative margin of the MXU coarse test, RELATIVE to the squared
# clamp-box scale: XLA TPU may serve even an f32-input matmul with
# bf16-multiply passes (precision=DEFAULT), so the margin assumes
# bf16-grade operand rounding (2^-9 relative) for BOTH dtypes — the
# worst-case term-sum bound is ~9*s^2 * 2^-8 ≈ s^2 * 2^-4.8; 2^-4 gives
# ~1.8x headroom over that already-unattainable joint worst case, and
# tests/test_dense_candidates.py fuzzes the bound with emulated bf16
# rounding. An absolute 0.5 m^2 slack covers the tiny-scale regime.
_MXU_REL_MARGIN = 0.0625
_MXU_ABS_MARGIN = 0.5

# interpret mode: run the kernel through the pallas interpreter on any
# backend — slow, for debugging kernel logic without TPU access
# (env_flag = THE boolean env parse, round-14 env-flag lint)
from reporter_tpu.utils.tracing import env_flag as _env_flag

_INTERPRET = _env_flag(os.environ.get("RTPU_PALLAS_INTERPRET"))

_P = 256          # points per chunk: halves the (chunks x blocks) launch
#                   grid vs 128 — measured ~2/5/9% faster on sf/organic/xl
#                   (interleaved A/B, round 4); 512 loses (looser bboxes)
_SBLK = 512       # segment columns per block (small: culling granularity;
#                   512 re-validated post-narrow-grid; results are exact
#                   at any block size since the merge is order-independent)
_SUB = 128
#                   sub-block columns for the IN-KERNEL second culling
#                   level (round 8): each DMA'd _SBLK block is tested per
#                   _SUB-column lane-width slice against the chunk's
#                   actual points (exact point-vs-bbox distance, tighter
#                   than the host pre-pass's chunk-bbox test), and the
#                   pair geometry + top-K selection run only on slices
#                   that can hold an in-radius pair. Must divide _SBLK.
_NSUB = 8         # chunk sub-bboxes — 32 points per sub-bbox, the same
#                   culling tightness as the old 128/4 (results identical)
_NJ_CAP = 128     # narrow-grid width DEFAULT rung: max culled blocks per
#                   chunk before the sweep falls back to the full-width
#                   launch grid (Morton-sorted fleet chunks typically hit
#                   ~6-11 blocks; the cap kills the per-slot launch
#                   overhead that cost bayarea-xl ~45% of its dispatch at
#                   1184 blocks). Round 17: callers may override per
#                   dispatch via find_candidates_dense(nj_cap=...) —
#                   the kernel plan's nj_cap (matcher/autotune.TunedPlan),
#                   restricted to the config.SWEEP_NJ_CAP_RUNGS ladder so
#                   the compiled-shape universe stays manifest-pinned;
#                   this module constant is the default plan's rung.
SPLIT_LEN = 256.0  # long-segment pre-split span (shared with tiles/capacity)


class SegPack(NamedTuple):
    """Device-side dense segment table (spatially blocked)."""

    pack: np.ndarray   # f32 [8, S_pad] component rows, Morton-sorted columns
    bbox: np.ndarray   # f32 [nblocks, 4] per-block (xmin, ymin, xmax, ymax)
    sub: "np.ndarray | None" = None
    #                  # f32 [nblocks, (SBLK/SUB)*4] per-SUB-slice bboxes
    #                  # (xmin, ymin, xmax, ymax quads; NaN = empty slice)
    #                  # — the in-kernel second culling level; None on
    #                  # packs built before round 8 (kernel falls back to
    #                  # whole-block sweeps)
    feat: "np.ndarray | None" = None
    #                  # f32 [8, S_pad] per-column MXU feature rows (SF_*)
    #                  # — the round-13 matmul-form coarse pass; None on
    #                  # packs built before round 13 (mxu=True then raises
    #                  # rather than silently measuring f32 against itself)


def _morton(x: np.ndarray, y: np.ndarray) -> np.ndarray:
    """Interleave 16-bit quantized coords → 32-bit Morton keys."""

    def spread(v):
        v = v.astype(np.uint64)
        v = (v | (v << 16)) & np.uint64(0x0000FFFF0000FFFF)
        v = (v | (v << 8)) & np.uint64(0x00FF00FF00FF00FF)
        v = (v | (v << 4)) & np.uint64(0x0F0F0F0F0F0F0F0F)
        v = (v | (v << 2)) & np.uint64(0x3333333333333333)
        v = (v | (v << 1)) & np.uint64(0x5555555555555555)
        return v

    return spread(x) | (spread(y) << np.uint64(1))


def _split_long_segments(seg_a, seg_b, seg_edge, seg_off, seg_len,
                         lmax: float):
    """Tile segments longer than ``lmax`` into collinear sub-spans.

    A 2 km rural edge is ONE line segment; its bbox inflates whichever
    Morton block it lands in until half the metro's chunks "hit" that
    block (organic/xl tiles carry many such edges — grid tiles none).
    Sub-spans tile the segment exactly: min distance over pieces equals
    distance to the whole segment, and offabs composes via the piece's
    off0, so candidates are unchanged (to f32 rounding at the seams) —
    only the culling gets tighter."""
    long_i = np.nonzero(seg_len > lmax)[0]
    if not len(long_i):
        return seg_a, seg_b, seg_edge, seg_off, seg_len
    keep = np.ones(len(seg_len), bool)
    keep[long_i] = False
    # grouped formulation (xl-scale tiles can carry tens of thousands of
    # long rural edges; a per-edge Python loop is real time on one core):
    # piece r of parent i spans fractions [r/n_i, (r+1)/n_i]
    n = np.ceil(seg_len[long_i] / lmax).astype(np.int64)
    parent = np.repeat(long_i, n)                      # [N] parent index
    r = np.arange(len(parent)) - np.repeat(np.cumsum(n) - n, n)
    nn = np.repeat(n, n).astype(np.float64)
    f0 = (r / nn)[:, None]
    f1 = ((r + 1) / nn)[:, None]
    d = seg_b[parent] - seg_a[parent]
    pb_long = seg_a[parent] + d * f1
    # the final piece ends at the ORIGINAL endpoint bit-for-bit: junction
    # nodes are segment endpoints, and an a+(b-a)*1.0 ulp there would
    # break the exact d=0 ties the cross-backend tie-break relies on
    last = (r + 1) == nn.astype(np.int64)
    pb_long[last] = seg_b[parent[last]]
    return (np.concatenate([seg_a[keep],
                            seg_a[parent] + d * f0]).astype(np.float32),
            np.concatenate([seg_b[keep], pb_long]).astype(np.float32),
            np.concatenate([seg_edge[keep], seg_edge[parent]]),
            np.concatenate([seg_off[keep], seg_off[parent]
                            + seg_len[parent] * f0[:, 0]]).astype(np.float32),
            np.concatenate([seg_len[keep], seg_len[parent]
                            * (f1 - f0)[:, 0]]).astype(np.float32))


def packed_columns(seg_len: np.ndarray, block: int = _SBLK,
                   split_len: float = SPLIT_LEN) -> int:
    """Post-split padded column count of build_seg_pack's layout — the
    shape math tiles/capacity needs WITHOUT rebuilding the Morton pack
    (~seconds at 0.6M segments on one core). Must mirror
    _split_long_segments' piece count exactly."""
    s = len(seg_len)
    if split_len and s:
        long = seg_len > split_len
        s = int(s - long.sum()
                + np.ceil(seg_len[long] / split_len).sum())
    return max(block, -(-s // block) * block)


def build_seg_pack(seg_a: np.ndarray, seg_b: np.ndarray, seg_edge: np.ndarray,
                   seg_off: np.ndarray, seg_len: np.ndarray,
                   block: int = _SBLK, split_len: float = SPLIT_LEN) -> SegPack:
    """Morton-sort segments, pack [8, S_pad] f32 component rows (edge ids
    bitcast into a row), record per-block bboxes. Padding columns carry
    edge = -1 → permanently invalid; padding blocks carry NaN bboxes →
    never selected by the culling pre-pass. Segments longer than
    ``split_len`` are tiled into sub-spans first so no block bbox is
    inflated by one long edge (_split_long_segments)."""
    if split_len and len(seg_len):
        seg_a, seg_b, seg_edge, seg_off, seg_len = _split_long_segments(
            seg_a, seg_b, seg_edge, seg_off, seg_len, split_len)
    s = len(seg_edge)
    spad = max(block, ((s + block - 1) // block) * block)

    mid = (seg_a + seg_b) * 0.5 if s else np.zeros((0, 2))
    if s:
        lo = mid.min(0)
        span = np.maximum(mid.max(0) - lo, 1e-6)
        q = np.minimum((mid - lo) / span * 65535.0, 65535.0).astype(np.uint32)
        order = np.argsort(_morton(q[:, 0], q[:, 1]), kind="stable")
    else:
        order = np.arange(0)
    a, b = seg_a[order], seg_b[order]

    pack = np.zeros((SP_NCOMP, spad), np.float32)
    pack[SP_AX, :s] = a[:, 0]
    pack[SP_AY, :s] = a[:, 1]
    pack[SP_BX, :s] = b[:, 0]
    pack[SP_BY, :s] = b[:, 1]
    pack[SP_OFF, :s] = seg_off[order]
    pack[SP_LEN, :s] = seg_len[order]
    edge = np.full(spad, -1, np.int32)
    edge[:s] = seg_edge[order]
    pack[SP_EDGE] = edge.view(np.float32)

    nblocks = spad // block
    bbox = np.full((nblocks, 4), np.nan, np.float32)
    for blk in range(nblocks):
        sl = slice(blk * block, min((blk + 1) * block, s))
        if sl.start >= s:
            break
        xs = np.concatenate([a[sl, 0], b[sl, 0]])
        ys = np.concatenate([a[sl, 1], b[sl, 1]])
        bbox[blk] = (xs.min(), ys.min(), xs.max(), ys.max())

    # Per-SUB-slice bboxes (round 8, the in-kernel second culling level).
    # Padding columns (>= s) are excluded; slices with no real column get
    # NaN quads, which every comparison in the kernel rejects. Vectorized:
    # the padding tail is contiguous, so per-column extrema with +-inf
    # sentinels reduce correctly and the all-pad slices are masked after.
    nsub = block // _SUB if _SUB and block % _SUB == 0 else 1
    subw = block // nsub
    real = np.arange(spad) < s
    big = np.float32(np.inf)
    cxmin = np.where(real, np.minimum(pack[SP_AX], pack[SP_BX]), big)
    cymin = np.where(real, np.minimum(pack[SP_AY], pack[SP_BY]), big)
    cxmax = np.where(real, np.maximum(pack[SP_AX], pack[SP_BX]), -big)
    cymax = np.where(real, np.maximum(pack[SP_AY], pack[SP_BY]), -big)
    quads = np.stack([cxmin.reshape(-1, subw).min(1),
                      cymin.reshape(-1, subw).min(1),
                      cxmax.reshape(-1, subw).max(1),
                      cymax.reshape(-1, subw).max(1)], axis=1)
    quads[~real.reshape(-1, subw).any(1)] = np.nan
    quads = quads.astype(np.float32)
    sub = quads.reshape(nblocks, nsub * 4)

    # Per-column MXU feature rows (round 13): quadratic expansion of the
    # point-to-LINE squared distance, recentered on each column's slice
    # center. Coefficients are computed in f64 and stored f32 (host
    # rounding ≪ the kernel's bf16-grade margin); the CENTER itself rides
    # rows SF_CX/SF_CY so the kernel and the builder can never disagree
    # on it. Padding columns get zero coefficients + F = BIG → their
    # coarse distance is BIG and can never keep a slice alive by itself.
    centers = np.stack([(quads[:, 0] + quads[:, 2]) * np.float32(0.5),
                        (quads[:, 1] + quads[:, 3]) * np.float32(0.5)],
                       axis=1)                         # f32 [nslices, 2]
    c64 = np.repeat(centers, subw, axis=0).astype(np.float64)  # [spad, 2]
    a64 = np.stack([pack[SP_AX], pack[SP_AY]], 1).astype(np.float64)
    b64 = np.stack([pack[SP_BX], pack[SP_BY]], 1).astype(np.float64)
    d64 = b64 - a64
    w = 1.0 / np.maximum((d64 * d64).sum(1), 1e-12)    # same eps as the
    #                                                    exact geometry
    e64 = a64 - c64
    g = e64[:, 0] * d64[:, 1] - e64[:, 1] * d64[:, 0]  # e x d
    feat = np.zeros((SF_NCOMP, spad), np.float32)
    feat[SF_A] = np.where(real, d64[:, 1] ** 2 * w, 0.0)
    feat[SF_B] = np.where(real, d64[:, 0] ** 2 * w, 0.0)
    feat[SF_C] = np.where(real, -2.0 * d64[:, 0] * d64[:, 1] * w, 0.0)
    feat[SF_D] = np.where(real, -2.0 * g * d64[:, 1] * w, 0.0)
    feat[SF_E] = np.where(real, 2.0 * g * d64[:, 0] * w, 0.0)
    feat[SF_F] = np.where(real, g * g * w, BIG)
    feat[SF_CX] = c64[:, 0]
    feat[SF_CY] = c64[:, 1]
    return SegPack(pack=pack, bbox=bbox, sub=sub, feat=feat)


def cull_radius(radius: float) -> float:
    """The sub-slice cull's statically dilated radius: absorbs f32
    rounding of the point-to-bbox lower bound so the in-kernel cull can
    never drop a pair the exact r2 test would keep. ONE definition —
    bench's host-side culling-stats replication imports it, so the
    reported pair counts stay exactly what the kernel computes even if
    this is retuned."""
    return float(radius) * 1.0005 + 0.01


def _block_geometry(px, py, seg):
    """Distances/offsets of a [P,1] point column against a [8,SBLK] segment
    block. Returns (d2 [P,SBLK], edge [P,SBLK] i32, offabs [P,SBLK]).
    Shared by the pallas kernel and the jnp fallback."""
    ax = seg[SP_AX:SP_AX + 1, :]
    ay = seg[SP_AY:SP_AY + 1, :]
    bx = seg[SP_BX:SP_BX + 1, :]
    by = seg[SP_BY:SP_BY + 1, :]
    off0 = seg[SP_OFF:SP_OFF + 1, :]
    slen = seg[SP_LEN:SP_LEN + 1, :]
    edge = jax.lax.bitcast_convert_type(seg[SP_EDGE:SP_EDGE + 1, :], jnp.int32)

    abx = bx - ax
    aby = by - ay
    denom = jnp.maximum(abx * abx + aby * aby, 1e-12)
    t = jnp.clip(((px - ax) * abx + (py - ay) * aby) / denom, 0.0, 1.0)
    dx = px - (ax + t * abx)
    dy = py - (ay + t * aby)
    d2 = dx * dx + dy * dy
    offabs = off0 + t * slen
    return d2, jnp.broadcast_to(edge, d2.shape), offabs


def _select_topk(d2, edge, offabs, k: int):
    """K passes of (pick global min lane, extract fields, kill same-edge).

    d2 [P, C] (BIG = invalid), edge i32 [P, C], offabs [P, C] →
    (d2 [P, K], edge [P, K], offabs [P, K]); scans C columns K times, all
    lane-parallel VPU work. Same algorithm as candidates._topk_distinct_edges
    but extraction by masked reduction instead of argmin+gather (in-kernel
    gathers would reintroduce the serialization this kernel removes).

    Distance TIES break toward the smallest edge id — the same order the
    grid backend (cell rows in segment-index order, argmin keeps the
    first) and the CPU oracle (stable sort over the segment arrays)
    resolve them. Morton sorting permutes this kernel's scan order, so a
    first-lane tie-break would pick a DIFFERENT tied candidate than the
    other two backends; at organic degree-5/6 junctions several edges
    tie at exactly the node distance and K fills up, which made the
    divergence visible as ~2% phantom oracle disagreement (round 4).
    Edge-id ties also make the block-merge order-independent.
    """
    P, C = d2.shape
    big_e = jnp.int32(2 ** 31 - 1)
    outs_d, outs_e, outs_o = [], [], []
    for _ in range(k):
        m = jnp.min(d2, axis=1, keepdims=True)                     # [P,1]
        tied = d2 == m
        pick_e = jnp.min(jnp.where(tied, edge, big_e), axis=1)     # [P]
        # the picked edge IS the reduction result — no lane extraction
        # pass needed; offset = the edge's lowest tied projection (same
        # as the oracle's stable first-segment pick: segment order is
        # increasing offset within an edge). Three column reductions per
        # step vs the old first-lane scheme's four.
        sel = tied & (edge == pick_e[:, None])
        o_k = jnp.min(jnp.where(sel, offabs, BIG), axis=1)
        ok = m[:, 0] < BIG
        outs_d.append(m[:, 0])
        outs_e.append(jnp.where(ok, pick_e, -1))
        outs_o.append(jnp.where(ok, o_k, 0.0))
        d2 = jnp.where((edge == pick_e[:, None]) & ok[:, None], BIG, d2)
    return (jnp.stack(outs_d, 1), jnp.stack(outs_e, 1), jnp.stack(outs_o, 1))


def _sweep_kernel(ids_ref, pts_ref, seg_ref, edge_out, off_out, dist_out,
                  d2_s, edge_s, off_s, *, r2: float, k: int, nj: int):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        d2_s[:] = jnp.full_like(d2_s, BIG)
        edge_s[:] = jnp.full_like(edge_s, -1)
        off_s[:] = jnp.zeros_like(off_s)

    # Padded id-list slots repeat the previous id: the pipeline skips the
    # re-DMA on equal consecutive block indices, and `fresh` skips ALL the
    # VPU work, so non-hit grid steps cost only the program launch.
    fresh = (j == 0) | (ids_ref[i, j] != ids_ref[i, jnp.maximum(j - 1, 0)])

    @pl.when(fresh)
    def _():
        d2, edge, offabs = _block_geometry(pts_ref[:, 0:1], pts_ref[:, 1:2],
                                           seg_ref[:])
        d2 = jnp.where((edge >= 0) & (d2 <= r2), d2, BIG)

        # bbox culling is conservative — blocks with zero in-radius hits
        # still skip the (much heavier) top-K selection machinery
        @pl.when(jnp.min(d2) < BIG)
        def _():
            bd, be, bo = _select_topk(d2, edge, offabs, k)         # [P,K]
            md, me, mo = _select_topk(
                jnp.concatenate([d2_s[:], bd], axis=1),
                jnp.concatenate([edge_s[:], be], axis=1),
                jnp.concatenate([off_s[:], bo], axis=1), k)
            d2_s[:] = md
            edge_s[:] = me
            off_s[:] = mo

    @pl.when(j == nj - 1)
    def _():
        md = d2_s[:]
        edge_out[:] = edge_s[:]
        off_out[:] = off_s[:]
        dist_out[:] = jnp.where(md < BIG,
                                jnp.sqrt(jnp.maximum(md, 0.0)), BIG)


def _sweep_kernel_sub(ids_ref, pts_ref, seg_ref, sub_ref, *rest,
                      r2: float, rc2: float, radius: float, k: int, nj: int,
                      nsub: int, subw: int, lowp: str, mxu: bool = False):
    """Two-level sweep (round 8). Per ``subw``-column slice of the DMA'd
    block: (1) an exact point-vs-slice-bbox distance test (min over the
    chunk's actual points — tighter than the host pre-pass's chunk-bbox
    overlap) gates all pair work; (2) the top-K update is ONE fused
    _select_topk over the [P, subw + k] concat of the slice's distances
    with the running scratch. The old shape selected over the full
    _SBLK-wide block and then merged [P, 2k] — ~4x the selection
    reductions when a single slice holds every in-radius pair, and the
    roofline says selection roughly doubles effective sweep cost.

    ``mxu=True`` (round 13) inserts a matmul-form coarse pair pass per
    surviving slice: point features [P, 8] (quadratic expansion of the
    recentered, clamp-boxed point coordinates) against the staged
    per-column coefficient rows [8, subw] — ONE dot on the MXU whose
    output is each pair's squared point-to-LINE distance, a lower bound
    on the exact point-to-segment distance. Exact f32 geometry +
    selection run only when some coarse distance admits an in-radius
    pair within a conservative margin (bf16-grade operand rounding is
    assumed for BOTH matmul dtypes — see _MXU_REL_MARGIN), so results
    stay bit-identical to every other kernel arm by construction.
    ``lowp`` selects the matmul operand dtype ("bf16" = native MXU
    width, "off" = f32 operands).

    ``lowp="bf16"`` WITHOUT mxu keeps the round-8 VPU filter: a
    recentered bf16 coarse pair pass per surviving slice (a 16-ulp bound
    on the recentered coordinate magnitude plus 0.5 m slack), same
    conservative-refinement contract.

    Exactness of the culling: slice bboxes are built from the same f32
    endpoint values the geometry reads, the point-to-bbox distance is a
    lower bound on every point-to-segment distance in the slice, and
    ``rc2`` carries a small static dilation over ``r2`` to absorb f32
    rounding of the bound itself — so no in-radius pair is ever skipped.
    """
    if mxu:
        (feat_ref, edge_out, off_out, dist_out, d2_s, edge_s, off_s) = rest
    else:
        feat_ref = None
        (edge_out, off_out, dist_out, d2_s, edge_s, off_s) = rest
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        d2_s[:] = jnp.full_like(d2_s, BIG)
        edge_s[:] = jnp.full_like(edge_s, -1)
        off_s[:] = jnp.zeros_like(off_s)

    # same launch-skip discipline as _sweep_kernel: padded id slots repeat
    # the previous id, so non-hit grid steps cost only the program launch
    fresh = (j == 0) | (ids_ref[i, j] != ids_ref[i, jnp.maximum(j - 1, 0)])

    @pl.when(fresh)
    def _():
        px = pts_ref[:, 0:1]
        py = pts_ref[:, 1:2]
        sb = sub_ref[:]                                    # [1, nsub*4]
        for s in range(nsub):                              # static unroll
            lox = sb[0:1, 4 * s + 0:4 * s + 1]             # [1, 1] each
            loy = sb[0:1, 4 * s + 1:4 * s + 2]
            hix = sb[0:1, 4 * s + 2:4 * s + 3]
            hiy = sb[0:1, 4 * s + 3:4 * s + 4]
            dx = jnp.maximum(jnp.maximum(lox - px, px - hix), 0.0)
            dy = jnp.maximum(jnp.maximum(loy - py, py - hiy), 0.0)
            bb2 = dx * dx + dy * dy                        # [P, 1]

            # NaN quads (all-padding slices) compare False -> skipped
            @pl.when(jnp.min(bb2) <= rc2)
            def _(s=s, lox=lox, loy=loy, hix=hix, hiy=hiy):
                seg = seg_ref[:, s * subw:(s + 1) * subw]

                def exact():
                    d2, edge, offabs = _block_geometry(px, py, seg)
                    d2 = jnp.where((edge >= 0) & (d2 <= r2), d2, BIG)

                    @pl.when(jnp.min(d2) < BIG)
                    def _():
                        md, me, mo = _select_topk(
                            jnp.concatenate([d2_s[:], d2], axis=1),
                            jnp.concatenate([edge_s[:], edge], axis=1),
                            jnp.concatenate([off_s[:], offabs], axis=1), k)
                        d2_s[:] = md
                        edge_s[:] = me
                        off_s[:] = mo

                if mxu:
                    # MXU coarse pass: evaluate every pair's squared
                    # point-to-LINE distance as one [P, 8] x [8, subw]
                    # dot over the staged quadratic coefficients. The
                    # point is recentered on the SAME center the
                    # coefficients were built with (read from the
                    # feature rows — never recomputed) and clamped into
                    # the slice bbox dilated by ~radius: the box contains
                    # every segment of the slice, so projecting the
                    # point into it never increases its distance to
                    # them, and the clamp bounds every matmul operand by
                    # the slice extent instead of the chunk's spread
                    # (the r8 bf16-filter argument, verbatim).
                    feat = feat_ref[:, s * subw:(s + 1) * subw]
                    cx = feat[SF_CX:SF_CX + 1, 0:1]    # [1, 1] each
                    cy = feat[SF_CY:SF_CY + 1, 0:1]
                    mx = jnp.float32(radius) * 1.001 + 0.5
                    exm = (hix - lox) * 0.5 + mx
                    eym = (hiy - loy) * 0.5 + mx
                    qx = jnp.clip(px - cx, -exm, exm)  # [P, 1]
                    qy = jnp.clip(py - cy, -eym, eym)
                    one = jnp.ones_like(qx)
                    zero = jnp.zeros_like(qx)
                    pf = jnp.concatenate(
                        [qx * qx, qy * qy, qx * qy, qx, qy, one,
                         zero, zero], axis=1)          # [P, 8]
                    # rows SF_CX/SF_CY multiply the two zero point
                    # features — exactly 0 contribution at any rounding
                    if lowp == "bf16":
                        lhs = pf.astype(jnp.bfloat16)
                        rhs = feat.astype(jnp.bfloat16)
                    else:
                        lhs, rhs = pf, feat
                    d2m = jax.lax.dot_general(
                        lhs, rhs, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)  # [P, subw]
                    scale = jnp.maximum(exm, eym)
                    thr = (jnp.float32(r2)
                           + scale * scale * jnp.float32(_MXU_REL_MARGIN)
                           + jnp.float32(_MXU_ABS_MARGIN))

                    @pl.when(jnp.min(d2m) <= jnp.min(thr))
                    def _():
                        exact()
                elif lowp != "bf16":
                    exact()
                else:
                    # recenter on the slice bbox AND clamp every operand
                    # into the bbox dilated by ~radius: the slice's real
                    # endpoints already lie inside (unchanged); far-away
                    # chunk points and zero-padding columns clamp to the
                    # boundary. Projection onto a convex set containing
                    # the slice's segments never increases the distance
                    # to them, so the coarse test stays conservative —
                    # and the bf16 error scale is bounded by the SLICE
                    # extent + radius instead of the whole chunk's
                    # spread (unclamped, a 2 km trace chunk inflated the
                    # margin until the filter stopped culling anything)
                    mx = jnp.float32(radius) * 1.001 + 0.5
                    cx = (lox + hix) * 0.5
                    cy = (loy + hiy) * 0.5
                    ex = (hix - lox) * 0.5 + mx            # [1, 1]
                    ey = (hiy - loy) * 0.5 + mx
                    pxc = jnp.clip(px - cx, -ex, ex)
                    pyc = jnp.clip(py - cy, -ey, ey)
                    axc = jnp.clip(seg[SP_AX:SP_AX + 1, :] - cx, -ex, ex)
                    ayc = jnp.clip(seg[SP_AY:SP_AY + 1, :] - cy, -ey, ey)
                    bxc = jnp.clip(seg[SP_BX:SP_BX + 1, :] - cx, -ex, ex)
                    byc = jnp.clip(seg[SP_BY:SP_BY + 1, :] - cy, -ey, ey)
                    scale = jnp.maximum(ex, ey)            # |coord| bound
                    bf = jnp.bfloat16
                    pxl, pyl = pxc.astype(bf), pyc.astype(bf)
                    axl, ayl = axc.astype(bf), ayc.astype(bf)
                    abx = bxc.astype(bf) - axl
                    aby = byc.astype(bf) - ayl
                    den = jnp.maximum(abx * abx + aby * aby,
                                      jnp.asarray(1e-12, bf))
                    t = jnp.clip(((pxl - axl) * abx + (pyl - ayl) * aby)
                                 / den,
                                 jnp.asarray(0.0, bf), jnp.asarray(1.0, bf))
                    dxl = pxl - (axl + t * abx)
                    dyl = pyl - (ayl + t * aby)
                    d2c = (dxl * dxl + dyl * dyl).astype(jnp.float32)
                    # conservative inflation: bf16 rounds each operand to
                    # <= scale * 2^-9 absolute error and the ~10-op chain
                    # accumulates a few ulps more — scale * 2^-4 (6.25%)
                    # is ~16x that bound, plus a 0.5 m absolute slack for
                    # the tiny-coordinate regime
                    rl = (jnp.float32(radius) + scale * jnp.float32(0.0625)
                          + jnp.float32(0.5))              # [1, 1]

                    @pl.when(jnp.min(d2c) <= jnp.min(rl * rl))
                    def _():
                        exact()

    @pl.when(j == nj - 1)
    def _():
        md = d2_s[:]
        edge_out[:] = edge_s[:]
        off_out[:] = off_s[:]
        dist_out[:] = jnp.where(md < BIG,
                                jnp.sqrt(jnp.maximum(md, 0.0)), BIG)


def _chunk_block_ids(pts, valid, bbox, radius: float, nchunks: int):
    """Culling pre-pass: ([nchunks, nblocks] i32 block ids to visit,
    [nchunks] i32 hit counts).

    pts f32 [nchunks*P, 2] (already padded), valid bool [nchunks*P].
    Each chunk is split into _NSUB consecutive sub-ranges; a block is a hit
    if its (radius-dilated) bbox overlaps any sub-range's bbox. Hits are
    listed first (ascending id); the tail repeats the last hit so the
    kernel skips both the DMA and all compute for those slots.
    """
    sub = pts.reshape(nchunks * _NSUB, _P // _NSUB, 2)
    v = valid.reshape(nchunks * _NSUB, _P // _NSUB, 1)
    big = jnp.float32(BIG)
    lo = jnp.min(jnp.where(v, sub, big), axis=1)        # [nc*NSUB, 2]
    hi = jnp.max(jnp.where(v, sub, -big), axis=1)
    lo = lo - radius
    hi = hi + radius

    bxmin, bymin, bxmax, bymax = (bbox[:, 0], bbox[:, 1], bbox[:, 2],
                                  bbox[:, 3])
    hit = ((bxmin[None, :] <= hi[:, 0:1]) & (bxmax[None, :] >= lo[:, 0:1]) &
           (bymin[None, :] <= hi[:, 1:2]) & (bymax[None, :] >= lo[:, 1:2]))
    hit = hit.reshape(nchunks, _NSUB, -1).any(axis=1)   # [nchunks, nblocks]

    nblocks = hit.shape[1]
    ids = jnp.arange(nblocks, dtype=jnp.int32)[None, :]
    key = jnp.where(hit, ids, nblocks + ids)            # hits sort first
    order = jnp.sort(key, axis=1)                       # [nchunks, nblocks]
    is_hit = order < nblocks
    hit_id = jnp.where(is_hit, order, 0)
    # pad slots ← running last hit (cummax works since ids ascend); the
    # list keeps FULL width nblocks, so no hit is ever dropped — sparsity
    # is recovered by the narrow-grid truncation in _dense_pallas (exact
    # whenever hits fit _NJ_CAP — the counts returned here prove it) and
    # in-kernel by the `fresh` skip
    padded = jax.lax.cummax(jnp.where(is_hit, hit_id, -1), axis=1)
    # dtype pinned: a bool jnp.sum accumulates in the DEFAULT int width,
    # which under x64 silently widens to i64 (device-contract x64 audit)
    return (jnp.maximum(padded, 0).astype(jnp.int32),
            jnp.sum(hit, axis=1, dtype=jnp.int32))


def _dense_pallas(points, valid, seg_pack: "SegPack | tuple", radius: float,
                  k: int, subcull: bool = True, lowp: str = "off",
                  mxu: bool = False, nj_cap: "int | None" = None):
    # resolved at CALL time so the interpret-parity tests' module-global
    # monkeypatch keeps working; params-driven callers pass the rung
    nj_cap = _NJ_CAP if nj_cap is None else int(nj_cap)
    pack, bbox = seg_pack[0], seg_pack[1]
    sub = seg_pack[2] if len(seg_pack) > 2 else None
    feat = seg_pack[3] if len(seg_pack) > 3 else None
    use_sub = bool(subcull) and sub is not None
    if lowp == "bf16" and not use_sub and not mxu:
        # only the two-level kernel implements the low-precision pass;
        # silently running plain f32 would let an A/B "bf16 arm" measure
        # f32 against itself (the config layer raises the same way)
        raise ValueError(
            "lowp='bf16' requires the two-level kernel: subcull=True and "
            "a seg_pack built with sub quads")
    use_mxu = bool(mxu)
    if use_mxu and (not use_sub or feat is None):
        # same discipline: an "mxu arm" that silently fell back to the
        # plain two-level kernel would A/B-measure an arm against itself
        raise ValueError(
            "mxu=True requires the two-level kernel (subcull=True) and a "
            "seg_pack built with feat rows (round 13 build_seg_pack)")
    n = points.shape[0]
    spad = pack.shape[1]
    nchunks = max(1, (n + _P - 1) // _P)
    npad = nchunks * _P
    pts = jnp.pad(points, ((0, npad - n), (0, 0)))
    val = jnp.pad(valid, (0, npad - n))
    # neutralize invalid points (zeros would drag chunk bboxes to origin):
    # replace with the chunk's masked mean so they cull like their chunk
    chunks = pts.reshape(nchunks, _P, 2)
    vc = val.reshape(nchunks, _P, 1)
    # dtype pinned (see _chunk_block_ids): the default-int bool sum would
    # also drag the mean's division up to f64 under x64
    cnt = jnp.maximum(jnp.sum(vc, axis=1, dtype=jnp.int32), 1)
    mean = jnp.sum(jnp.where(vc, chunks, 0.0), axis=1) / cnt
    pts = jnp.where(vc, chunks, mean[:, None, :]).reshape(npad, 2)

    ids, nhits = _chunk_block_ids(pts, val, bbox, radius, nchunks)

    if use_sub:
        nsub4 = int(sub.shape[1])
        nsub = nsub4 // 4
        subw = _SBLK // nsub
        rc = cull_radius(radius)
    r2 = float(radius) * float(radius)

    def call(ids_g, pts_g, nj):
        nc = ids_g.shape[0]
        in_specs = [
            pl.BlockSpec((_P, 2), lambda i, j, ids: (i, 0)),
            pl.BlockSpec((SP_NCOMP, _SBLK),
                         lambda i, j, ids: (0, ids[i, j])),
        ]
        inputs = [ids_g, pts_g, pack]
        if use_sub:
            # quads ride as [nblocks, 1, nsub4] so the block's last two
            # dims EQUAL the array's: a (1, nsub4) block of the 2-D table
            # violates the TPU's (8, 128) tiling rule (interpret mode
            # accepts it; the Mosaic lowering refuses it)
            in_specs.append(
                pl.BlockSpec((None, 1, nsub4),
                             lambda i, j, ids: (ids[i, j], 0, 0)))
            inputs.append(sub.reshape(sub.shape[0], 1, nsub4))
            if use_mxu:
                # feature rows ride the same per-block DMA discipline as
                # the segment pack (equal consecutive ids skip the fetch)
                in_specs.append(
                    pl.BlockSpec((SF_NCOMP, _SBLK),
                                 lambda i, j, ids: (0, ids[i, j])))
                inputs.append(feat)
            kern = functools.partial(
                _sweep_kernel_sub, r2=r2, rc2=rc * rc, radius=float(radius),
                k=k, nj=nj, nsub=nsub, subw=subw, lowp=lowp, mxu=use_mxu)
        else:
            kern = functools.partial(_sweep_kernel, r2=r2, k=k, nj=nj)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nc, nj),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((_P, k), lambda i, j, ids: (i, 0)),
                pl.BlockSpec((_P, k), lambda i, j, ids: (i, 0)),
                pl.BlockSpec((_P, k), lambda i, j, ids: (i, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((_P, k), jnp.float32),
                pltpu.VMEM((_P, k), jnp.int32),
                pltpu.VMEM((_P, k), jnp.float32),
            ],
        )
        return pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((nc * _P, k), jnp.int32),
                jax.ShapeDtypeStruct((nc * _P, k), jnp.float32),
                jax.ShapeDtypeStruct((nc * _P, k), jnp.float32),
            ],
            interpret=_INTERPRET,
            name="candidate_sweep",
        )(*inputs)

    def sweep(ids_w):
        """Full sweep at one static id-list width. The grid dim must
        equal the id-list width or the kernel reads the scalar ref out of
        bounds. The prefetched id list lives in SMEM (~1 MB), lane-padded
        to 128 columns — cap chunks per pallas_call and sequence groups
        (XLA pipelines consecutive custom calls)."""
        nj = ids_w.shape[1]
        _, maxc = prefetch_group_cap(nj)
        if nchunks <= maxc:
            # tuple(): the narrow/full cond branches can take different
            # chunking paths here, and lax.cond requires identical output
            # containers — don't rely on pallas_call's own return type
            return tuple(call(ids_w, pts, nj))
        parts = []
        for lo in range(0, nchunks, maxc):
            hi = min(nchunks, lo + maxc)
            parts.append(call(ids_w[lo:hi], pts[lo * _P:hi * _P], nj))
        return tuple(jnp.concatenate(xs, axis=0) for xs in zip(*parts))

    # Narrow-grid launch (round-5 xl attribution): the full-width grid
    # runs nblocks steps per chunk and big metros pay megasteps of empty
    # launches — bayarea-xl's 1184-block table spent ~45% of its dispatch
    # on culled slots (~85 ns each). Hits sort first, so truncating the
    # id list to nj_cap columns is EXACT whenever every chunk hits at
    # most nj_cap blocks (typical max is tens; the culling stats prove
    # it per dispatch) — one traced cond falls back to the full-width
    # sweep for the rare spread-out batch.
    if ids.shape[1] > nj_cap:
        edge, off, dist = jax.lax.cond(
            jnp.max(nhits) <= nj_cap,
            lambda: sweep(ids[:, :nj_cap]),
            lambda: sweep(ids))
    else:
        edge, off, dist = sweep(ids)
    return edge[:n], off[:n], dist[:n]


def _dense_jnp(points, seg_pack, radius: float, k: int):
    """Reference path (CPU tests, multichip dry-runs, interpret debugging):
    full sweep, no culling — identical output, blocked over points to bound
    the [P, S] temporary."""
    pack = seg_pack[0] if isinstance(seg_pack, (tuple, SegPack)) else seg_pack
    n = points.shape[0]
    # own chunk size, decoupled from the pallas launch-grid tuning (_P):
    # this path's [P, S] f32 temporary is ~P*606k*4 B at xl scale on the
    # one-core CPU host, so keep P at the memory-bounding 128
    P = 128
    nchunks = max(1, (n + P - 1) // P)
    npad = nchunks * P
    pts = jnp.pad(points, ((0, npad - n), (0, 0))).reshape(nchunks, P, 2)
    r2 = radius * radius

    def chunk(p):
        d2, edge, offabs = _block_geometry(p[:, 0:1], p[:, 1:2], pack)
        d2 = jnp.where((edge >= 0) & (d2 <= r2), d2, BIG)
        return _select_topk(d2, edge, offabs, k)

    d2c, ec, oc = jax.lax.map(chunk, pts)
    d2c = d2c.reshape(npad, k)[:n]
    dist = jnp.where(d2c < BIG, jnp.sqrt(jnp.maximum(d2c, 0.0)), BIG)
    return ec.reshape(npad, k)[:n], oc.reshape(npad, k)[:n], dist


# SMEM budget of one pallas_call's scalar-prefetch id list: the whole
# [nc, nj] i32 array is prefetched, lane-padded to 128 columns, and SMEM
# is ~1 MB per core — the 512 KB self-cap leaves headroom for the grid
# indices and compiler-managed scalars. ONE definition: the launcher's
# chunk grouping below and the static device-contract audit
# (analysis/device_contract.py) must bound the same bytes.
SMEM_PREFETCH_BUDGET = 512 * 1024
SMEM_LANE_PAD = 128


def prefetch_group_cap(nj: int) -> "tuple[int, int]":
    """(lane-padded id-list columns, max chunks per pallas_call) for an
    id list ``nj`` wide — the shape math that keeps every grouped
    scalar-prefetch launch inside ``SMEM_PREFETCH_BUDGET``."""
    padded_cols = ((nj + SMEM_LANE_PAD - 1) // SMEM_LANE_PAD) * SMEM_LANE_PAD
    return padded_cols, max(1, SMEM_PREFETCH_BUDGET // (padded_cols * 4))


def prefetch_smem_bytes(nchunks: int, nj: int) -> int:
    """Static SMEM footprint bound of the id list for ONE grouped launch
    over ``nchunks`` point chunks at id-list width ``nj`` (the audit's
    closed form; the launcher never exceeds it by construction)."""
    padded_cols, maxc = prefetch_group_cap(nj)
    return min(nchunks, maxc) * padded_cols * 4


_FORCE_PALLAS_TRACE = 0


@contextlib.contextmanager
def pallas_trace_override():
    """Audit hook (analysis/device_contract.py): make ``_use_pallas()``
    answer True on a CPU host so ``jax.make_jaxpr`` traces the ACTUAL
    kernel program — abstract eval only, nothing is lowered or run."""
    global _FORCE_PALLAS_TRACE
    _FORCE_PALLAS_TRACE += 1
    try:
        yield
    finally:
        _FORCE_PALLAS_TRACE -= 1


def _use_pallas() -> bool:
    if _INTERPRET or _FORCE_PALLAS_TRACE:
        return pl is not None
    if jax.default_backend() == "cpu":
        return False
    if pl is None:
        # an accelerator without the kernel must fail loudly: quietly
        # serving the full-sweep jnp reference would hide the device path
        raise RuntimeError(
            f"Pallas failed to import on backend {jax.default_backend()!r}"
            f" ({_PALLAS_IMPORT_ERROR}); the dense sweep has no fallback")
    return True


def find_candidates_dense(points, seg_pack, radius: float,
                          max_candidates: int,
                          valid=None, subcull: bool = True,
                          lowp: str = "off",
                          mxu: bool = False,
                          nj_cap: "int | None" = None) -> CandidateSet:
    """points f32 [N, 2] → CandidateSet with [N, K] fields (flat batch).

    seg_pack: a SegPack (or (pack, bbox[, sub[, feat]]) tuple of
    arrays). valid (bool [N], optional) marks padding points — they
    still produce (ignored) rows but are excluded from the culling
    bboxes. Uses the pallas sweep on accelerators (raises there if
    Pallas is missing), the jnp full sweep on CPU backends.

    subcull enables the in-kernel sub-block culling + fused narrow top-K
    (round 8; needs the pack's ``sub`` quads — silently falls back to the
    whole-block kernel without them). mxu=True (round 13) runs the
    matmul-form coarse pair pass on the MXU per surviving slice (needs
    the pack's ``feat`` rows — raises without them); lowp="bf16" then
    selects bf16 matmul operands. lowp="bf16" without mxu keeps the
    round-8 VPU coarse pair filter. Every combination is bit-identical
    to the whole-block kernel and the jnp reference by construction
    (interpret-mode test-asserted): coarse passes only ever SKIP
    provably-out-of-radius work, refinement is exact f32.

    nj_cap (round 17): the narrow-grid launch width rung (the kernel
    plan's nj_cap; None = this module's _NJ_CAP default).
    Exact at any width — the lax.cond full-width fallback is unchanged —
    so the per-metro autotuner may select it freely.
    """
    if valid is None:
        valid = jnp.ones(points.shape[0], bool)
    if _use_pallas():
        edge, off, dist = _dense_pallas(points, valid, seg_pack, radius,
                                        max_candidates, subcull=subcull,
                                        lowp=lowp, mxu=mxu, nj_cap=nj_cap)
    else:
        edge, off, dist = _dense_jnp(points, seg_pack, radius, max_candidates)
    return CandidateSet(edge=edge, offset=off, dist=dist, valid=edge >= 0)
