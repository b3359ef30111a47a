"""Segment-sharded candidate search — the framework's TP analog.

SURVEY.md §2.3 marks tensor parallelism "not needed; optional sharded kNN
reduce over ICI if a metro's edge set exceeds one core's HBM". This is
that option: the Morton-blocked segment table (seg_pack + seg_feat
columns + their bboxes) is sharded over a mesh axis, every device sweeps
its shard of the map against the FULL point batch, and the per-shard top-K candidate lists
are all-gathered over ICI and merged with the same distinct-edge K-merge
the dense kernel uses per block. Viterbi then runs data-parallel on the
merged candidates (reach tables replicated — node-keyed [N, M] and small
relative to shape data).

Segments of one edge may straddle a shard boundary; the merge dedupes by
edge id keeping the closer projection with the dense kernel's own
distance-tie resolution (``_select_topk``), so results are bit-identical
to the unsharded sweep — including at exact ties (test-asserted).

Collective traffic per batch: one all-gather of [shards, B·T, K] candidate
triples over ICI — bytes ≈ shards × points × K × 12, tiny next to the
sharded HBM win (each device holds 1/shards of the map).
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from reporter_tpu.config import MatcherParams
from reporter_tpu.ops.candidates import CandidateSet
from reporter_tpu.parallel.compat import shard_map
from reporter_tpu.ops.dense_candidates import (
    _SBLK,
    _select_topk,
    build_seg_pack,
    find_candidates_dense,
)
from reporter_tpu.ops.hmm import viterbi_decode_batched
from reporter_tpu.ops.match import MatchOutput
from reporter_tpu.tiles.tileset import TileSet


class ShardedTables(NamedTuple):
    seg_pack: jnp.ndarray    # [8, S_pad] — sharded over columns
    seg_bbox: jnp.ndarray    # [nblocks, 4] — sharded over rows
    seg_sub: jnp.ndarray     # [nblocks, nsub*4] — sharded over rows
    seg_feat: jnp.ndarray    # [8, S_pad] MXU feature rows — sharded over
    #                          columns in lockstep with seg_pack
    edge_len: jnp.ndarray    # replicated
    reach_row: jnp.ndarray   # replicated (edge → governing reach row)
    reach_to: jnp.ndarray
    reach_dist: jnp.ndarray


def shard_tables(mesh: Mesh, ts: TileSet, axis: str = "tile",
                 ) -> ShardedTables:
    """Pad the segment table to shards × block multiples and device_put with
    the column dimension sharded over ``axis``."""
    n = mesh.shape[axis]
    sp = build_seg_pack(ts.seg_a, ts.seg_b, ts.seg_edge, ts.seg_off,
                        ts.seg_len)
    spad = sp.pack.shape[1]
    per = -(-spad // (n * _SBLK)) * _SBLK          # columns per shard
    total = per * n
    pack = np.full((sp.pack.shape[0], total), np.int32(-1).view(np.float32),
                   np.float32)
    pack[:, :spad] = sp.pack
    bbox = np.full((total // _SBLK, 4), np.nan, np.float32)
    bbox[:sp.bbox.shape[0]] = sp.bbox
    sub = np.full((total // _SBLK, sp.sub.shape[1]), np.nan, np.float32)
    sub[:sp.sub.shape[0]] = sp.sub
    # feature rows pad in whole blocks whose NaN sub quads gate them off
    # before the matmul — BIG fill keeps a stray read conservative
    feat = np.full((sp.feat.shape[0], total), np.float32(1e30), np.float32)
    feat[:, :spad] = sp.feat

    return ShardedTables(
        seg_pack=jax.device_put(jnp.asarray(pack),
                                NamedSharding(mesh, P(None, axis))),
        seg_bbox=jax.device_put(jnp.asarray(bbox),
                                NamedSharding(mesh, P(axis))),
        seg_sub=jax.device_put(jnp.asarray(sub),
                               NamedSharding(mesh, P(axis))),
        seg_feat=jax.device_put(jnp.asarray(feat),
                                NamedSharding(mesh, P(None, axis))),
        edge_len=jax.device_put(jnp.asarray(ts.edge_len),
                                NamedSharding(mesh, P())),
        reach_row=jax.device_put(jnp.asarray(ts.edge_reach_row),
                                 NamedSharding(mesh, P())),
        reach_to=jax.device_put(jnp.asarray(ts.reach_to),
                                NamedSharding(mesh, P())),
        reach_dist=jax.device_put(jnp.asarray(ts.reach_dist),
                                  NamedSharding(mesh, P())),
    )


def _merge_topk(edge, dist, off, k: int):
    """Merge gathered per-shard K-lists: fields [shards, N, K] → [N, K].
    Delegates to the dense kernel's ``_select_topk`` so the distinct-edge
    K-merge and its distance-tie resolution (smallest tied edge id, then
    its lowest tied offset) are ONE implementation: exact node-distance
    ties at high-degree junctions can span shard boundaries, and any
    drift here would let the sharded path pick a different tied edge than
    the single-device sweep (test-asserted bit-identical)."""
    s, n, kk = edge.shape
    e = jnp.moveaxis(edge, 0, 1).reshape(n, s * kk)
    d = jnp.moveaxis(dist, 0, 1).reshape(n, s * kk)
    o = jnp.moveaxis(off, 0, 1).reshape(n, s * kk)
    d = jnp.where(e >= 0, d, jnp.float32(1e30))
    md, me, mo = _select_topk(d, e, o, k)
    return me, md, mo


def make_sharded_matcher(mesh: Mesh, ts: TileSet, params: MatcherParams,
                         axis: str = "tile"):
    """fn(points [B,T,2], valid [B,T]) → MatchOutput with the segment table
    sharded over ``axis`` (map-capacity scaling) and the batch replicated
    on that axis. Compose with batch sharding over the other mesh axes
    externally if desired."""
    tables = shard_tables(mesh, ts, axis)
    radius, k = params.search_radius, params.max_candidates

    def local(points, valid, seg_pack, seg_bbox, seg_sub, seg_feat,
              edge_len, reach_row, reach_to, reach_dist):
        B, T = points.shape[:2]
        flat = find_candidates_dense(
            points.reshape(B * T, 2),
            (seg_pack, seg_bbox, seg_sub, seg_feat),
            radius, k, valid=valid.reshape(B * T))
        # all-gather each shard's K-list over ICI, then K-merge
        ge = jax.lax.all_gather(flat.edge, axis)        # [shards, N, K]
        gd = jax.lax.all_gather(flat.dist, axis)
        go = jax.lax.all_gather(flat.offset, axis)
        me, md, mo = _merge_topk(ge, gd, go, k)
        cands = CandidateSet(edge=me.reshape(B, T, k),
                             offset=mo.reshape(B, T, k),
                             dist=md.reshape(B, T, k),
                             valid=(me >= 0).reshape(B, T, k))
        vit = viterbi_decode_batched(
            cands, points, valid,
            {"edge_len": edge_len, "reach_row": reach_row,
             "reach_to": reach_to, "reach_dist": reach_dist},
            params.sigma_z, params.beta, params.max_route_distance_factor,
            params.breakage_distance, params.backward_slack,
            params.interpolation_distance)
        return MatchOutput(edge=vit.edge, offset=vit.offset,
                           chain_start=vit.chain_start, matched=vit.matched)

    other = [a for a in mesh.axis_names if a != axis]
    sharded = shard_map(
        local, mesh=mesh,
        in_specs=(P(*other) if other else P(), P(*other) if other else P(),
                  P(None, axis), P(axis), P(axis), P(None, axis),
                  P(), P(), P(), P()),
        out_specs=P(*other) if other else P(),
        check_vma=False,
    )

    @jax.jit
    def step(points, valid) -> MatchOutput:
        return sharded(points, valid, tables.seg_pack, tables.seg_bbox,
                       tables.seg_sub, tables.seg_feat, tables.edge_len,
                       tables.reach_row, tables.reach_to, tables.reach_dist)

    return step
