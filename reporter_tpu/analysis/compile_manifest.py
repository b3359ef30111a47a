"""Pinned compiled-shape universe + static SMEM/HBM budgets.

Every jit trace is ~150 ms of host time on this one-core box and is NOT
covered by the persistent compile cache (the r12 SpeedHistogram lesson:
a shape-varying scatter dropped fresh trace cost into whichever measured
wave first hit a new cap, and the attribution noise cost a round). The
executable population is therefore a deliberately SMALL, FIXED universe
— scheduler trace-count rungs × matcher point buckets × three wire
entries × two accuracy variants, one histogram scatter shape, one dense
sweep geometry — and this module pins it: ``compute_manifest()`` derives
the universe from the live constants, ``GOLDEN`` is the committed copy,
and any drift (a new rung, a changed bucket, a resized kernel block, a
bumped staged-table layout) is a CI failure instead of r12-style bench
noise. Intentional changes regenerate the golden block with::

    python -m reporter_tpu.analysis --update-manifest

(the fixtures/regen.py workflow: regenerate ONLY for intentional
compile-universe changes, and let the diff say what moved).

The same module carries the static device-memory bounds:

- ``smem_findings()`` — every grouped ``dense_candidates``
  scalar-prefetch launch (lane-padded ×128, the ~1 MB SMEM ceiling)
  stays within budget at every id-list width the envelope allows, using
  the launcher's OWN grouping math (ops.dense_candidates
  prefetch_smem_bytes — one spelling, checked not duplicated);
- ``hbm_findings(ts)`` — tiles/capacity.py's staged-byte shape math
  equals the bytes ``host_tables`` actually builds (cross-checked on a
  real tiny tileset), and the envelope metro's staged layout fits the
  committed HBM budget.
"""

from __future__ import annotations

from typing import Any

__all__ = ["compute_manifest", "GOLDEN", "check", "diff",
           "smem_findings", "hbm_findings", "update_golden"]

# The size envelope the static budget checks bound: generous multiples
# of the largest benched metro (bayarea-xl: 606k line segments / 485k
# directed edges), far under the continental scale where capacity.py
# already mandates sharding. Grow these when a bigger metro lands.
ENVELOPE = {
    "line_segments": 2_000_000,
    "directed_edges": 1_000_000,
    "nodes": 500_000,
    "reach_max": 128,
}

# static SMEM ceiling asserted per grouped prefetch launch (the hardware
# gives ~1 MB/core; dense_candidates self-caps its id lists at 512 KB)
SMEM_BOUND_BYTES = 1024 * 1024


def compute_manifest() -> "dict[str, Any]":
    """The compiled-shape universe, derived from the live constants."""
    from reporter_tpu.backfill import aggregate as bagg
    from reporter_tpu.config import (SWEEP_NJ_CAP_RUNGS, MatcherParams,
                                     ServiceConfig)
    from reporter_tpu.matcher import api, autotune
    from reporter_tpu.ops import aggregate as agg
    from reporter_tpu.ops import dense_candidates as dc
    from reporter_tpu.ops import match
    from reporter_tpu.service import scheduler
    from reporter_tpu.streaming.histogram import SpeedHistogram
    from reporter_tpu.tiles import capacity, tileset

    rungs = list(scheduler._TRACE_RUNGS)
    buckets = list(api._BUCKETS)
    nsub = dc._SBLK // dc._SUB if dc._SUB and dc._SBLK % dc._SUB == 0 else 1
    cap_rungs = list(SWEEP_NJ_CAP_RUNGS)
    arms = [autotune.TunedPlan(arm=a, lowp=l).label.split("@")[0]
            for a, l in autotune.CANDIDATE_ARMS]
    return {
        "manifest_version": 1,
        "scheduler": {
            "trace_count_rungs": rungs,
            "max_batch_traces_default": ServiceConfig().max_batch_traces,
        },
        "matcher": {
            "point_buckets": buckets,
            "max_device_batch_default": MatcherParams().max_device_batch,
            "max_dispatch_rows": api._MAX_DISPATCH_ROWS,
            "wire_entries": ["f32", "q16", "q8"],
            "acc_scale_variants": 2,
            # the [B, T] executable-shape bound per tile per layout: every
            # serving dispatch shape is (rung | max_device_batch slice,
            # bucket) — an executable outside this grid is a NEW COMPILE
            "wire_executables_per_tile_bound":
                len(rungs) * len(buckets) * 3 * 2,
        },
        "wire_formats": {
            "compact_max_edges": match._COMPACT_WIRE_EDGES,
            "offset_quantum_m": match.OFFSET_QUANTUM,
            # layout → [wire dtype, lane count] (unpack_wire dispatches
            # on exactly this)
            "layouts": {"compact": ["uint16", 2], "full": ["uint16", 3],
                        "packed": ["uint32", 1]},
            "infeed_dtypes": {"f32": "float32", "q16": "int16",
                              "q8": "int8"},
        },
        "dense_sweep": {
            "point_chunk": dc._P,
            "seg_block": dc._SBLK,
            "sub_slice": dc._SUB,
            "nsub_per_block": nsub,
            "chunk_sub_bboxes": dc._NSUB,
            "narrow_grid_cap": dc._NJ_CAP,
            # round 17: the cap is plan-selectable from this fixed
            # ladder only (config.SWEEP_NJ_CAP_RUNGS) — the compiled-
            # shape universe stays finite; exact at any rung
            "nj_cap_rungs": cap_rungs,
            "split_len_m": dc.SPLIT_LEN,
            "pack_rows": dc.SP_NCOMP,
            "feat_rows": dc.SF_NCOMP,
            "smem_prefetch_budget_bytes": dc.SMEM_PREFETCH_BUDGET,
            "smem_lane_pad": dc.SMEM_LANE_PAD,
            "smem_bound_bytes": SMEM_BOUND_BYTES,
        },
        "histogram_scatter": {
            "cap_rows": SpeedHistogram._CAP,
        },
        # round 20: the backfill aggregates' shared flat scatter — ONE
        # update-batch shape and a fixed set of grids per tile, so an
        # open-loop run adds exactly two scatter executables to the
        # universe (ops/aggregate.py; grids in backfill/aggregate.py)
        "backfill_scatter": {
            "cap_rows": agg._CAP,
            "grids": ["speed_tod", "turns"],
            "tod_bins_default": bagg.DEFAULT_TOD_BINS,
            "turn_slots_default": bagg.DEFAULT_TURN_SLOTS,
            # r21: the mesh arm keeps per-device partial grids and
            # scatters cap_rows indices PER SHARD ([ndev, cap] blocks
            # through ONE jit(shard_map) program per mesh — still two
            # scatter executables per tile per process, mesh or not);
            # partials merge bucket-wise at the one harvest readback
            "mesh": {
                "cap_rows_per_shard": agg._CAP,
                "executables_per_grid": 1,
                "merge": "host i32 bucket sum at snapshot()",
            },
        },
        # round 17: the per-metro self-tuning plan space — the cap-rung
        # × kernel-arm matrix the tuner may pick from, fully enumerated
        # so per-metro tuning can never grow the executable population
        # past this block (matcher/autotune.py)
        "autotune": {
            "plan_version": autotune.PLAN_VERSION,
            "arms": arms,
            "nj_cap_rungs": cap_rungs,
            "plans_bound": len(arms) * len(cap_rungs),
            "cal_dispatches": autotune.CAL_DISPATCHES,
            "cal_batch_shape": list(autotune.CAL_BATCH_SHAPE),
            # two-phase calibration: every arm at the default rung +
            # the winner across the remaining rungs — the per-tile
            # compile cost of measuring, bounded
            "calibration_executables_per_tile_bound":
                len(arms) + len(cap_rungs) - 1,
            "staged_member": "tuned_plan",
            "nj_cap_default": autotune.TunedPlan().nj_cap,
        },
        "staged_tables": {
            "layout_version": tileset.STAGED_LAYOUT_VERSION,
            "dense_layout_keys": list(tileset._DENSE_LAYOUT_KEYS),
            "hbm_budget_bytes": capacity.DEFAULT_HBM_BUDGET,
        },
        "envelope": dict(ENVELOPE),
    }


# --- BEGIN GOLDEN MANIFEST (generated; do not hand-edit — run
#     `python -m reporter_tpu.analysis --update-manifest`) ---
GOLDEN: "dict[str, Any]" = \
{'autotune': {'arms': ['subcull',
                       'subcull+bf16',
                       'block',
                       'mxu',
                       'mxu+bf16'],
              'cal_batch_shape': [128, 64],
              'cal_dispatches': 4,
              'calibration_executables_per_tile_bound': 7,
              'nj_cap_default': 128,
              'nj_cap_rungs': [64, 128, 256],
              'plan_version': 1,
              'plans_bound': 15,
              'staged_member': 'tuned_plan'},
 'backfill_scatter': {'cap_rows': 4096,
                      'grids': ['speed_tod', 'turns'],
                      'mesh': {'cap_rows_per_shard': 4096,
                               'executables_per_grid': 1,
                               'merge': 'host i32 bucket sum at '
                                        'snapshot()'},
                      'tod_bins_default': 24,
                      'turn_slots_default': 8},
 'dense_sweep': {'chunk_sub_bboxes': 8,
                 'feat_rows': 8,
                 'narrow_grid_cap': 128,
                 'nj_cap_rungs': [64, 128, 256],
                 'nsub_per_block': 4,
                 'pack_rows': 8,
                 'point_chunk': 256,
                 'seg_block': 512,
                 'smem_bound_bytes': 1048576,
                 'smem_lane_pad': 128,
                 'smem_prefetch_budget_bytes': 524288,
                 'split_len_m': 256.0,
                 'sub_slice': 128},
 'envelope': {'directed_edges': 1000000,
              'line_segments': 2000000,
              'nodes': 500000,
              'reach_max': 128},
 'histogram_scatter': {'cap_rows': 4096},
 'manifest_version': 1,
 'matcher': {'acc_scale_variants': 2,
             'max_device_batch_default': 2048,
             'max_dispatch_rows': 4096,
             'point_buckets': [16, 32, 64, 128, 256, 512, 1024],
             'wire_entries': ['f32', 'q16', 'q8'],
             'wire_executables_per_tile_bound': 546},
 'scheduler': {'max_batch_traces_default': 256,
               'trace_count_rungs': [1,
                                     2,
                                     4,
                                     8,
                                     16,
                                     32,
                                     64,
                                     128,
                                     256,
                                     512,
                                     1024,
                                     2048,
                                     4096]},
 'staged_tables': {'dense_layout_keys': ['seg_pack',
                                         'seg_bbox',
                                         'seg_sub',
                                         'seg_feat'],
                   'hbm_budget_bytes': 12884901888,
                   'layout_version': 3},
 'wire_formats': {'compact_max_edges': 16384,
                  'infeed_dtypes': {'f32': 'float32',
                                    'q16': 'int16',
                                    'q8': 'int8'},
                  'layouts': {'compact': ['uint16', 2],
                              'full': ['uint16', 3],
                              'packed': ['uint32', 1]},
                  'offset_quantum_m': 0.25}}
# --- END GOLDEN MANIFEST ---


def diff(golden: "dict | Any", computed: "dict | Any",
         path: str = "") -> "list[str]":
    """Flat list of drift descriptions (empty = pinned). Dropped keys and
    changed values both count — the manifest is extend-don't-drop."""
    out: "list[str]" = []
    if isinstance(golden, dict) and isinstance(computed, dict):
        for k in sorted(set(golden) | set(computed)):
            p = f"{path}.{k}" if path else str(k)
            if k not in computed:
                out.append(f"{p}: dropped from the computed universe "
                           f"(golden: {golden[k]!r})")
            elif k not in golden:
                out.append(f"{p}: new in the computed universe "
                           f"({computed[k]!r}) — not in the golden "
                           "manifest")
            else:
                out.extend(diff(golden[k], computed[k], p))
        return out
    if golden != computed:
        out.append(f"{path}: golden {golden!r} != computed {computed!r}")
    return out


def check() -> "list[str]":
    """Manifest drift + static budget findings, one string each — the
    full gate: shape-universe drift, the SMEM bound, AND the HBM
    cross-check (on a freshly compiled tiny tileset; the compile is
    ~20 ms and byte-exactness on ANY tileset pins the formula)."""
    from reporter_tpu.analysis.device_contract import _tiny_tileset

    out = diff(GOLDEN, compute_manifest())
    out.extend(smem_findings())
    out.extend(hbm_findings(_tiny_tileset()))
    return out


# ---------------------------------------------------------------------------
# static SMEM bound

def _envelope_blocks() -> int:
    from reporter_tpu.ops import dense_candidates as dc

    s = ENVELOPE["line_segments"]
    spad = max(dc._SBLK, -(-s // dc._SBLK) * dc._SBLK)
    return spad // dc._SBLK


def smem_findings() -> "list[str]":
    """Assert every grouped scalar-prefetch launch's id list fits the
    SMEM budget at every id-list width reachable inside the envelope:
    EVERY narrow-grid ladder rung (round 17 — the tuner may select any
    of them per metro), the envelope metro's full block count, and the
    degenerate single-block tile."""
    from reporter_tpu.config import SWEEP_NJ_CAP_RUNGS
    from reporter_tpu.ops import dense_candidates as dc

    out: "list[str]" = []
    nblocks = _envelope_blocks()
    huge_chunks = -(-ENVELOPE["directed_edges"] // dc._P) * 4  # any cap
    cases = [(f"rung-{r}", min(nblocks, r)) for r in SWEEP_NJ_CAP_RUNGS]
    cases += [("default-cap", min(nblocks, dc._NJ_CAP)),
              ("full-envelope", nblocks),
              ("single-block", 1)]
    for label, nj in cases:
        bytes_ = dc.prefetch_smem_bytes(huge_chunks, nj)
        if bytes_ > SMEM_BOUND_BYTES:
            out.append(
                f"smem: {label} launch (nj={nj}) prefetches {bytes_} B "
                f"of SMEM ids > bound {SMEM_BOUND_BYTES} B — shrink the "
                "per-call chunk cap (ops.dense_candidates."
                "prefetch_group_cap)")
        if bytes_ > dc.SMEM_PREFETCH_BUDGET:
            out.append(
                f"smem: {label} launch (nj={nj}) exceeds the launcher's "
                f"own {dc.SMEM_PREFETCH_BUDGET} B self-cap ({bytes_} B) "
                "— prefetch_group_cap and prefetch_smem_bytes disagree")
    return out


# ---------------------------------------------------------------------------
# static HBM bound

def hbm_findings(ts) -> "list[str]":
    """Cross-check capacity.py's staged-byte shape math against the
    bytes ``host_tables`` ACTUALLY builds (a formula that drifts from
    the layout under-plans silently), then bound the envelope metro."""
    import numpy as np

    from reporter_tpu.ops import dense_candidates as dc
    from reporter_tpu.tiles import capacity

    out: "list[str]" = []
    shardable, fixed = capacity.dense_staged_bytes(ts)
    host = ts.host_tables("dense")
    actual_shardable = sum(int(host[k].nbytes) for k in
                           ("seg_pack", "seg_bbox", "seg_sub", "seg_feat"))
    actual_fixed = sum(int(host[k].nbytes) for k in
                       ("edge_len", "reach_row", "edge_osmlr",
                        "reach_to", "reach_dist"))
    if shardable != actual_shardable:
        out.append(
            f"hbm: capacity.dense_staged_bytes shardable formula "
            f"({shardable} B) != bytes host_tables stages "
            f"({actual_shardable} B) for {ts.name!r} — the shape math "
            "drifted from build_seg_pack's layout")
    if fixed != actual_fixed:
        out.append(
            f"hbm: capacity.dense_staged_bytes fixed formula ({fixed} B) "
            f"!= staged per-edge/reach bytes ({actual_fixed} B) for "
            f"{ts.name!r}")

    # envelope metro, analytically (mirrors dense_staged_bytes; the
    # cross-check above is what licenses the mirror)
    env = ENVELOPE
    seg_len = np.full(env["line_segments"], 50.0, np.float32)
    spad = dc.packed_columns(seg_len)
    nsub = dc._SBLK // dc._SUB if dc._SUB and dc._SBLK % dc._SUB == 0 else 1
    env_shardable = ((dc.SP_NCOMP + dc.SF_NCOMP) * spad
                     + (spad // dc._SBLK) * 4 * (1 + nsub)) * 4
    env_fixed = (env["directed_edges"] * (4 + 4 + 4)
                 + env["nodes"] * env["reach_max"] * (4 + 4))
    total = env_shardable + env_fixed
    if total > capacity.DEFAULT_HBM_BUDGET:
        out.append(
            f"hbm: envelope metro stages {total} B replicated > budget "
            f"{capacity.DEFAULT_HBM_BUDGET} B — grow the budget, shard, "
            "or shrink the envelope with a dated note")
    return out


# ---------------------------------------------------------------------------
# regen (the fixtures/regen.py workflow)

_BEGIN = ("# --- BEGIN GOLDEN MANIFEST (generated; do not hand-edit — run\n"
          "#     `python -m reporter_tpu.analysis --update-manifest`) ---")
_END = "# --- END GOLDEN MANIFEST ---"


def update_golden(path: "str | None" = None) -> str:
    """Rewrite this module's GOLDEN block from the live constants."""
    import pprint

    if path is None:
        path = __file__.rstrip("c")      # .pyc safety, pragma-free
    with open(path) as f:
        src = f.read()
    lo = src.index(_BEGIN)
    hi = src.index(_END) + len(_END)
    body = pprint.pformat(compute_manifest(), width=72, sort_dicts=True)
    block = (f"{_BEGIN}\nGOLDEN: \"dict[str, Any]\" = \\\n{body}\n{_END}")
    with open(path, "w") as f:
        f.write(src[:lo] + block + src[hi:])
    return path
