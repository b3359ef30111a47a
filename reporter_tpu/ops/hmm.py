"""HMM emission/transition costs and Viterbi decode as a `lax.scan`.

Replaces Meili's ViterbiSearch + per-pair Dijkstra routing (SURVEY.md §2.2
"HMM Viterbi decode" / "Inter-candidate routing", valhalla/meili — UNVERIFIED
paths): the data-dependent label-set Dijkstra of the reference's hot loop is
replaced by a gather into offline reach tables (tiles/reach.py), so one
Viterbi time-step is pure dense arithmetic over a [K, K] transition block —
scan-friendly, vmappable over a batch of traces, no host round-trips.

Cost model (negative log-likelihood up to constants, matching Meili's):
  emission(c)      = dist(point, c)^2 / (2 * sigma_z^2)
  transition(c→c') = |route_dist(c, c') − gc_dist| / beta
with transitions disallowed when no route exists within the reach radius or
the route detour exceeds ``max_route_distance_factor``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from reporter_tpu.ops.candidates import BIG, CandidateSet


class ViterbiResult(NamedTuple):
    choice: jnp.ndarray       # i32 [T] chosen candidate slot per point, -1 unmatched
    edge: jnp.ndarray         # i32 [T] chosen edge id, -1 unmatched
    offset: jnp.ndarray       # f32 [T] offset along chosen edge (m)
    chain_start: jnp.ndarray  # bool [T] True where a new HMM chain begins
    matched: jnp.ndarray      # bool [T]


def route_distance(e1, off1, e2, off2, tables, backward_slack: float = 10.0):
    """Network distance from candidate (e1, off1) to candidate (e2, off2).

    Broadcasts over leading dims. Uses the reach tables: end-of-e1→start-of-e2
    plus the remainders on both end edges; the same-edge forward case is a
    plain offset difference. Same-edge projections that move *backwards* by
    less than ``backward_slack`` (GPS jitter between close samples) count as
    zero forward progress instead of a full graph loop. BIG when unreachable
    within the reach radius.
    """
    edge_len = tables["edge_len"]
    reach_row = tables["reach_row"]    # i32 [E] — edge → reach row (node
                                       # rows; private rows for restricted
                                       # from-edges, tiles/reach.py)
    reach_to = tables["reach_to"]      # [R, M]
    reach_dist = tables["reach_dist"]  # [R, M]

    e1s = jnp.maximum(e1, 0)
    e2s = jnp.maximum(e2, 0)
    n1 = reach_row[e1s]
    row_to = reach_to[n1]              # [..., M]
    row_d = reach_dist[n1]
    hit = row_to == e2s[..., None]
    gap = jnp.min(jnp.where(hit, row_d, BIG), axis=-1)
    cross = (edge_len[e1s] - off1) + gap + off2

    same = (e1 == e2) & (off2 >= off1 - backward_slack)
    direct = jnp.maximum(off2 - off1, 0.0)
    route = jnp.where(same, jnp.minimum(direct, cross), cross)
    return jnp.where((e1 >= 0) & (e2 >= 0), route, BIG)


def transition_costs(cands_t: CandidateSet, cands_u: CandidateSet, gc, tables,
                     beta: float, max_route_factor: float,
                     backward_slack: float = 10.0):
    """[K, K] transition cost block from point t's candidates to point u's.

    gc: scalar straight-line distance between the two measurements.
    """
    e1, o1 = cands_t.edge, cands_t.offset
    e2, o2 = cands_u.edge, cands_u.offset
    route = route_distance(e1[:, None], o1[:, None], e2[None, :], o2[None, :],
                           tables, backward_slack)
    cost = jnp.abs(route - gc) / beta
    # Detour guard: route much longer than the crow flies ⇒ disallowed
    # (Meili's max_route_distance_factor). The +10 m floor keeps near-zero gc
    # pairs (stopped vehicle) from disallowing everything.
    allowed = (route < BIG) & (route <= max_route_factor * gc + 10.0)
    allowed &= cands_t.valid[:, None] & cands_u.valid[None, :]
    return jnp.where(allowed, cost, BIG)


def emission_costs(cands: CandidateSet, sigma_z: float):
    """[T, K] emission cost; BIG for invalid candidates."""
    c = cands.dist ** 2 / (2.0 * sigma_z ** 2)
    return jnp.where(cands.valid, c, BIG)


ATTR_EDGES = 64    # edges per row of the per-edge attribute table


def edge_attr_table(reach_row, edge_len):
    """i32 [ceil(E / 64), 128]: the per-edge attributes the Viterbi scan
    looks up, 64 edges a row. Lanes 0-63 of row r hold ``reach_row`` of
    edges 64r .. 64r+63, lanes 64-127 the bit patterns of their
    ``edge_len``; the edge count is zero-padded up to a multiple of 64.

    One row gather from this table replaces two per-element gathers from
    the [E] tables: on a TPU v5e a per-element gather pays ~7 ns an
    index, a row gather from VMEM ~1.5 ns a 512-byte row."""
    pad = -reach_row.shape[0] % ATTR_EDGES
    rows = jnp.pad(reach_row, (0, pad))
    lens = jnp.pad(jax.lax.bitcast_convert_type(edge_len, jnp.int32),
                   (0, pad))
    return jnp.concatenate([rows.reshape(-1, ATTR_EDGES),
                            lens.reshape(-1, ATTR_EDGES)], axis=1)


def edge_attr_lookup(attr, e):
    """(reach_row[max(e, 0)], edge_len[max(e, 0)]) from ``edge_attr_table``:
    one row gather, then two lane selects. Each select sums a row in
    which one lane is nonzero, so both values are the tables' exact bits."""
    e = jnp.maximum(e, 0)
    row = attr[e >> 6]                                  # row e // 64
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, row.ndim - 1)
    j = (e & (ATTR_EDGES - 1))[..., None]
    # dtype pinned: integer jnp.sum accumulates in the default int width
    n1 = jnp.sum(jnp.where(lane == j, row, 0), axis=-1, dtype=jnp.int32)
    bits = jnp.sum(jnp.where(lane == j + ATTR_EDGES, row, 0), axis=-1,
                   dtype=jnp.int32)
    return n1, jax.lax.bitcast_convert_type(bits, jnp.float32)


def _keep_mask_batched(pts, vp, interp_distance: float):
    """Batch-last keep mask: pts [T, 2, B], vp [T, B] → bool [T, B]."""
    if interp_distance <= 0.0:
        return vp
    d2_min = jnp.float32(interp_distance) ** 2

    def step(carry, x):
        last_pt, any_kept = carry
        pt, v = x
        d2 = jnp.sum((pt - last_pt) ** 2, axis=0)       # [B]
        keep = v & (~any_kept | (d2 >= d2_min))
        return (jnp.where(keep[None, :], pt, last_pt), any_kept | keep), keep

    B = vp.shape[1]
    (_, _), keep = jax.lax.scan(
        step, (pts[0], jnp.zeros((B,), bool)), (pts, vp))
    return keep


@jax.named_scope("viterbi")
def viterbi_decode_batched(cands: CandidateSet, points, valid_pt, tables,
                           sigma_z: float, beta: float,
                           max_route_factor: float, breakage_distance: float,
                           backward_slack: float = 10.0,
                           interpolation_distance: float = 0.0,
                           ) -> ViterbiResult:
    """Whole-batch Viterbi: cands fields [B, T, K], points [B, T, 2],
    valid_pt [B, T] → ViterbiResult fields [B, T].

    Semantically identical to vmap(viterbi_decode) (tests assert bit
    equality) but laid out **batch-last** internally: the scan carries
    [K, B] tensors and each step's K×K transition block is [K, K, B], so
    the batch rides the TPU lane dimension at full width. The vmapped form
    puts K (=8) on lanes — 8/128 occupancy — and measured ~3 ms per scan
    step of almost no arithmetic; batch-last recovers the width. Each
    step fetches the previous candidates' reach rows and edge lengths by
    one row gather from ``edge_attr_table``, built once per call.

    Runs under the named scope ``viterbi`` (op metadata only), by which
    the device trace attributes the scan's ops.
    """
    B, T, K = cands.edge.shape
    ce = jnp.moveaxis(cands.edge, 0, -1)                # [T, K, B]
    co = jnp.moveaxis(cands.offset, 0, -1)
    cd = jnp.moveaxis(cands.dist, 0, -1)
    cv = jnp.moveaxis(cands.valid, 0, -1)
    pts = jnp.moveaxis(points, 0, -1)                   # [T, 2, B]
    vp = valid_pt.T                                     # [T, B]

    em = jnp.where(cv, cd ** 2 / (2.0 * sigma_z ** 2), BIG)   # [T, K, B]
    keep = _keep_mask_batched(pts, vp, interpolation_distance)
    active = keep & jnp.any(cv, axis=1)                 # [T, B]
    identity_bp = jnp.broadcast_to(
        jnp.arange(K, dtype=jnp.int32)[:, None], (K, B))
    k_iota = jnp.arange(K, dtype=jnp.int32)

    attr = edge_attr_table(tables["reach_row"], tables["edge_len"])
    reach_to = tables["reach_to"]
    reach_dist = tables["reach_dist"]

    def trans_block(pe, po, pv, e, o, v, gc):
        """[K, K, B] transition costs (mirror of transition_costs)."""
        e2 = jnp.maximum(e, 0)
        n1, len1 = edge_attr_lookup(attr, pe)           # [K, B] each
        rows_to = reach_to[n1]                          # [K, B, M]
        rows_d = reach_dist[n1]
        hit = rows_to[:, None] == e2[None, :, :, None]  # [K, K, B, M]
        gap = jnp.min(jnp.where(hit, rows_d[:, None], BIG), axis=-1)
        cross = (len1 - po)[:, None] + gap + o[None, :]
        same = ((pe[:, None] == e[None, :])
                & (o[None, :] >= po[:, None] - backward_slack))
        direct = jnp.maximum(o[None, :] - po[:, None], 0.0)
        route = jnp.where(same, jnp.minimum(direct, cross), cross)
        route = jnp.where((pe[:, None] >= 0) & (e[None, :] >= 0), route, BIG)
        cost = jnp.abs(route - gc) / beta
        allowed = (route < BIG) & (route <= max_route_factor * gc + 10.0)
        allowed &= pv[:, None] & v[None, :]
        return jnp.where(allowed, cost, BIG)

    def step(carry, inp):
        score, prev_pt, prev_any, pe, po, pv = carry
        em_t, pt, act_t, e, o, v = inp

        gc = jnp.sqrt(jnp.sum((pt - prev_pt) ** 2, axis=0))     # [B]
        trans = trans_block(pe, po, pv, e, o, v, gc)            # [K, K, B]
        trans = jnp.where(gc <= breakage_distance, trans, BIG)

        via = score[:, None] + trans
        # index dtype pinned: jnp.argmin indexes in the DEFAULT int width
        # (i64 under x64) — lax.argmin with an explicit index_dtype is
        # the same op with the width pinned (device-contract x64 audit)
        best_prev = jax.lax.argmin(via, 0, jnp.int32)           # [K, B]
        best_cost = jnp.min(via, axis=0)
        connected = best_cost < BIG

        broken = ~jnp.any(connected, axis=0) | ~prev_any        # [B]
        new_score = jnp.where(broken[None, :], em_t,
                              jnp.where(connected, best_cost + em_t, BIG))
        backptr = jnp.where(broken[None, :] | ~connected, -1, best_prev)

        act = act_t[None, :]
        score_out = jnp.where(act, new_score, score)
        new_carry = (score_out,
                     jnp.where(act, pt, prev_pt),
                     act_t | prev_any,
                     jnp.where(act, e, pe),
                     jnp.where(act, o, po),
                     jnp.where(act, v, pv))
        emit = (score_out,
                jnp.where(act, backptr, identity_bp),
                act_t & broken)
        return new_carry, emit

    init = (jnp.full((K, B), BIG, jnp.float32), pts[0],
            jnp.zeros((B,), bool),
            jnp.full((K, B), -1, jnp.int32),
            jnp.zeros((K, B), jnp.float32),
            jnp.zeros((K, B), bool))
    xs = (em, pts, active, ce, co, cv)
    _, (scores, backptrs, started) = jax.lax.scan(step, init, xs)

    # ---- backtrack (reverse scan; see viterbi_decode for the invariant) --
    def back(carry, inp):
        nxt_choice, nxt_started = carry                 # [B]
        score_t, bp_next, act_t, started_t = inp
        sel = k_iota[:, None] == jnp.maximum(nxt_choice, 0)[None, :]
        # dtype pinned: integer jnp.sum accumulates in the DEFAULT int
        # width, which under x64 silently widens the scan carry to i64
        # (the device-contract x64 audit traces exactly this)
        prop = jnp.sum(jnp.where(sel, bp_next, 0), axis=0, dtype=jnp.int32)
        prop = jnp.where(nxt_choice >= 0, prop, -1)
        own = jax.lax.argmin(score_t, 0, jnp.int32)   # index dtype pinned
        own = jnp.where(jnp.min(score_t, axis=0) < BIG, own, -1)
        terminal = nxt_started | (nxt_choice < 0)
        choice_t = jnp.where(terminal, own, prop)
        out = jnp.where(act_t, choice_t, -1)
        return (choice_t, started_t), out

    bp_above = jnp.concatenate(
        [backptrs[1:], jnp.full((1, K, B), -1, jnp.int32)])
    rev = (scores[::-1], bp_above[::-1], active[::-1], started[::-1])
    _, choices_rev = jax.lax.scan(
        back, (jnp.full((B,), -1, jnp.int32), jnp.ones((B,), bool)), rev)
    choice = choices_rev[::-1]                          # [T, B]

    safe = jnp.maximum(choice, 0)
    matched = choice >= 0
    sel = k_iota[None, :, None] == safe[:, None, :]     # [T, K, B]
    edge = jnp.where(matched,
                     jnp.sum(jnp.where(sel, ce, 0), axis=1, dtype=jnp.int32),
                     -1)
    offset = jnp.where(matched, jnp.sum(jnp.where(sel, co, 0.0), axis=1), 0.0)

    # interpolated points ride the matched path (see viterbi_decode)
    interp = vp & ~keep

    def fill(carry, x):
        pe_, po_, pok = carry                           # [B]
        e, o, m, ip = x
        use = ip & pok & ~m
        e2 = jnp.where(use, pe_, e)
        o2 = jnp.where(use, po_, o)
        new = (jnp.where(m, e, pe_), jnp.where(m, o, po_), pok | m)
        return new, (e2, o2, m | use)

    _, (edge, offset, matched) = jax.lax.scan(
        fill, (jnp.full((B,), -1, jnp.int32), jnp.zeros((B,), jnp.float32),
               jnp.zeros((B,), bool)),
        (edge, offset, matched, interp))

    return ViterbiResult(
        choice=choice.T.astype(jnp.int32),
        edge=edge.T.astype(jnp.int32),
        offset=offset.T,
        chain_start=started.T,
        matched=matched.T,
    )


def interpolation_keep_mask(points, valid_pt, interp_distance: float):
    """bool [T]: False for points within ``interp_distance`` of the last
    kept point — Meili's input interpolation (such points ride the matched
    path instead of voting in the HMM; SURVEY.md §2.2 map-matcher row).
    Sequential by definition (distance to the last KEPT point), so a small
    lax.scan over T; vmap over traces upstream."""
    if interp_distance <= 0.0:
        return valid_pt
    d2_min = jnp.float32(interp_distance) ** 2

    def step(carry, x):
        last_pt, any_kept = carry
        pt, v = x
        d2 = jnp.sum((pt - last_pt) ** 2)
        keep = v & (~any_kept | (d2 >= d2_min))
        return (jnp.where(keep, pt, last_pt), any_kept | keep), keep

    (_, _), keep = jax.lax.scan(
        step, (points[0], jnp.bool_(False)), (points, valid_pt))
    return keep


def _forward_lattice(cands: CandidateSet, points, valid_pt, keep, tables,
                     sigma_z: float, beta: float, max_route_factor: float,
                     breakage_distance: float, backward_slack: float):
    """Forward Viterbi pass of ONE trace → (scores [T,K], backptrs [T,K],
    started [T], active [T]). Shared by viterbi_decode (best path) and
    viterbi_topk_paths (K-best terminal completions)."""
    T, K = cands.edge.shape
    em = emission_costs(cands, sigma_z)                     # [T, K]
    active = keep & jnp.any(cands.valid, axis=1)            # [T]
    identity_bp = jnp.arange(K, dtype=jnp.int32)

    def slot_view(t_idx):
        return CandidateSet(edge=cands.edge[t_idx], offset=cands.offset[t_idx],
                            dist=cands.dist[t_idx], valid=cands.valid[t_idx])

    def step(carry, inp):
        score, prev_pt, prev_any, prev_idx = carry
        em_t, pt, act_t, t_idx = inp

        gc = jnp.sqrt(jnp.sum((pt - prev_pt) ** 2))
        trans = transition_costs(slot_view(prev_idx), slot_view(t_idx), gc,
                                 tables, beta, max_route_factor,
                                 backward_slack)                   # [K, K]
        trans = jnp.where(gc <= breakage_distance, trans, BIG)

        via = score[:, None] + trans
        best_prev = jnp.argmin(via, axis=0).astype(jnp.int32)       # [K]
        best_cost = jnp.min(via, axis=0)
        connected = best_cost < BIG

        broken = ~jnp.any(connected) | ~prev_any
        new_score = jnp.where(broken, em_t,
                              jnp.where(connected, best_cost + em_t, BIG))
        backptr = jnp.where(broken | ~connected, -1, best_prev)

        score_out = jnp.where(act_t, new_score, score)
        new_carry = (score_out,
                     jnp.where(act_t, pt, prev_pt),
                     act_t | prev_any,
                     jnp.where(act_t, t_idx, prev_idx))
        emit = (score_out,
                jnp.where(act_t, backptr, identity_bp),
                act_t & broken)
        return new_carry, emit

    init = (jnp.full((K,), BIG, jnp.float32), points[0], jnp.bool_(False),
            jnp.int32(0))
    xs = (em, points, active, jnp.arange(T, dtype=jnp.int32))
    _, (scores, backptrs, started) = jax.lax.scan(step, init, xs)
    return scores, backptrs, started, active


def viterbi_decode(cands: CandidateSet, points, valid_pt, tables,
                   sigma_z: float, beta: float, max_route_factor: float,
                   breakage_distance: float,
                   backward_slack: float = 10.0,
                   interpolation_distance: float = 0.0) -> ViterbiResult:
    """Viterbi over the candidate lattice of ONE trace.

    points: f32 [T, 2] (for gc distances); valid_pt: bool [T] padding mask.
    Chain breakage: when consecutive points are farther apart than
    ``breakage_distance`` or no transition is allowed, the chain restarts at
    the new point, mirroring Meili's broken-path behavior. Inactive points
    (padding, interpolated, or no candidate in radius) pass the carry
    through untouched with identity backpointers, so chains connect across
    them.
    """
    T, K = cands.edge.shape
    keep = interpolation_keep_mask(points, valid_pt, interpolation_distance)
    scores, backptrs, started, active = _forward_lattice(
        cands, points, valid_pt, keep, tables, sigma_z, beta,
        max_route_factor, breakage_distance, backward_slack)

    # ---- backtrack (reverse scan) ---------------------------------------
    # carry = (slot chosen at the level just above, propagated down through
    # identity backpointers at inactive levels; started flag of that level).
    # A level is a chain terminal when the level above started a new chain
    # (or there is no level above): re-seed from its own score argmin — at
    # inactive levels the passed-through score is exactly the final score of
    # the last active point below, so re-seeding there is correct too.
    def back(carry, inp):
        nxt_choice, nxt_started = carry
        score_t, bp_next, act_t, started_t = inp
        prop = jnp.where(nxt_choice >= 0,
                         bp_next[jnp.maximum(nxt_choice, 0)], -1)
        own = jnp.argmin(score_t).astype(jnp.int32)
        own = jnp.where(score_t[own] < BIG, own, -1)
        terminal = nxt_started | (nxt_choice < 0)
        choice_t = jnp.where(terminal, own, prop)
        out = jnp.where(act_t, choice_t, -1)
        return (choice_t, started_t), out

    bp_above = jnp.concatenate([backptrs[1:], jnp.full((1, K), -1, jnp.int32)])
    rev = (scores[::-1], bp_above[::-1], active[::-1], started[::-1])
    _, choices_rev = jax.lax.scan(back, (jnp.int32(-1), jnp.bool_(True)), rev)
    choice = choices_rev[::-1]

    safe = jnp.maximum(choice, 0)
    matched = choice >= 0
    t_ar = jnp.arange(T)
    edge = jnp.where(matched, cands.edge[t_ar, safe], -1).astype(jnp.int32)
    offset = jnp.where(matched, cands.offset[t_ar, safe], 0.0)

    # Interpolated points (valid but not voting) ride the matched path:
    # inherit the last matched point's (edge, offset), as Meili interpolates
    # skipped input points onto the route. Padding stays unmatched.
    interp = valid_pt & ~keep

    def fill(carry, x):
        pe, po, pok = carry
        e, o, m, ip = x
        use = ip & pok & ~m
        e2 = jnp.where(use, pe, e)
        o2 = jnp.where(use, po, o)
        m2 = m | use
        new = (jnp.where(m, e, pe), jnp.where(m, o, po), pok | m)
        return new, (e2, o2, m2)

    _, (edge, offset, matched) = jax.lax.scan(
        fill, (jnp.int32(-1), jnp.float32(0.0), jnp.bool_(False)),
        (edge, offset, matched, interp))

    return ViterbiResult(
        choice=choice.astype(jnp.int32),
        edge=edge,
        offset=offset,
        chain_start=started,
        matched=matched,
    )


def viterbi_topk_paths(cands: CandidateSet, points, valid_pt, tables,
                       sigma_z: float, beta: float, max_route_factor: float,
                       breakage_distance: float,
                       backward_slack: float = 10.0,
                       interpolation_distance: float = 0.0):
    """K-best path interpretations of ONE trace (Meili's TopKSearch analog,
    SURVEY.md §2.2 HMM row).

    Ranks the final chain's K terminal candidates by accumulated cost and
    backtracks each one; earlier chains keep their best path. (Meili
    enumerates alternates by penalized re-search over the whole lattice;
    terminal completion is the standard single-pass K-best Viterbi
    approximation — alternates differ in the suffix, which for map matching
    is where the ambiguity that TopK serves lives: parallel roads at the
    trace's end.) tests/test_topk_oracle.py pins this contract against an
    exact list-Viterbi: rank 0 is the global optimum, every alternate is
    the exact optimal completion for its terminal, and true K-best
    dominates the returned ranking element-wise.

    Returns (choice [K, T] i32 candidate slots (-1 unmatched), score [K]
    f32 accumulated cost, valid [K] bool), ranked best-first.
    """
    T, K = cands.edge.shape
    keep = interpolation_keep_mask(points, valid_pt, interpolation_distance)
    scores, backptrs, started, active = _forward_lattice(
        cands, points, valid_pt, keep, tables, sigma_z, beta,
        max_route_factor, breakage_distance, backward_slack)

    final = scores[-1]                                   # [K]
    order = jnp.argsort(final).astype(jnp.int32)         # best-first slots
    rank_score = final[order]
    rank_valid = rank_score < BIG

    def back_one(slot):
        # Same reverse scan as viterbi_decode, but the level above T-1 is
        # pinned to `slot`: bp row of all-slot + non-terminal carry makes
        # the last level choose `slot`, propagated down through inactive
        # levels by the identity backpointers.
        def back(carry, inp):
            nxt_choice, nxt_started = carry
            score_t, bp_next, act_t, started_t = inp
            prop = jnp.where(nxt_choice >= 0,
                             bp_next[jnp.maximum(nxt_choice, 0)], -1)
            own = jnp.argmin(score_t).astype(jnp.int32)
            own = jnp.where(score_t[own] < BIG, own, -1)
            terminal = nxt_started | (nxt_choice < 0)
            choice_t = jnp.where(terminal, own, prop)
            out = jnp.where(act_t, choice_t, -1)
            return (choice_t, started_t), out

        bp_above = jnp.concatenate(
            [backptrs[1:], jnp.broadcast_to(slot, (1, K)).astype(jnp.int32)])
        rev = (scores[::-1], bp_above[::-1], active[::-1], started[::-1])
        _, choices_rev = jax.lax.scan(
            back, (slot.astype(jnp.int32), jnp.bool_(False)), rev)
        return choices_rev[::-1]

    choices = jax.vmap(back_one)(order)                  # [K, T]
    choices = jnp.where(rank_valid[:, None], choices, -1)
    return choices, rank_score, rank_valid


def viterbi_kbest_paths(cands: CandidateSet, points, valid_pt, tables,
                        sigma_z: float, beta: float, max_route_factor: float,
                        breakage_distance: float,
                        backward_slack: float = 10.0,
                        interpolation_distance: float = 0.0,
                        num_paths: int = 4):
    """EXACT K-best paths of ONE trace's final chain (list Viterbi).

    Where viterbi_topk_paths returns the optimal completion per terminal
    candidate (alternates can only differ in the suffix), this carries the
    top ``num_paths`` path costs PER LATTICE STATE through the scan — the
    textbook list-Viterbi / parallel-list decoder, which on TPU is just
    one more vectorized axis: the carry is [K, R] instead of [K], the
    per-step reduction a lax.top_k over the (prev candidate × rank)
    axis. Exactness (scores AND paths, against an independent numpy
    list-Viterbi oracle) is asserted by tests/test_topk_oracle.py.

    Alternate ranks share the convention of viterbi_topk_paths: earlier
    chains keep their single best path; ranks enumerate the final chain's
    K globally-best paths, not per-terminal completions.

    Returns (choice [R, T] i32 candidate slots (-1 unmatched), score [R]
    f32, valid [R] bool), ranked best-first.
    """
    T, K = cands.edge.shape
    R = int(num_paths)
    keep = interpolation_keep_mask(points, valid_pt, interpolation_distance)
    em = emission_costs(cands, sigma_z)                     # [T, K]
    active = keep & jnp.any(cands.valid, axis=1)            # [T]
    # flat (candidate, rank) coding: state s = c * R + r
    identity_bp = jnp.arange(K * R, dtype=jnp.int32).reshape(K, R)

    def slot_view(t_idx):
        return CandidateSet(edge=cands.edge[t_idx], offset=cands.offset[t_idx],
                            dist=cands.dist[t_idx], valid=cands.valid[t_idx])

    def step(carry, inp):
        score, prev_pt, prev_any, prev_idx = carry          # score [K, R]
        em_t, pt, act_t, t_idx = inp

        gc = jnp.sqrt(jnp.sum((pt - prev_pt) ** 2))
        trans = transition_costs(slot_view(prev_idx), slot_view(t_idx), gc,
                                 tables, beta, max_route_factor,
                                 backward_slack)             # [K, K]
        trans = jnp.where(gc <= breakage_distance, trans, BIG)

        # via[(cp, r), c] = score[cp, r] + trans[cp, c]; top-R smallest per
        # c. Ties resolve by ascending flat index — the same (cp, r)
        # enumeration order the numpy oracle's stable sort uses.
        via = (score[:, :, None] + trans[:, None, :]).reshape(K * R, K)
        vals, idxs = jax.lax.top_k(-via.T, R)                # [K(c), R]
        best_cost = -vals                                    # ascending
        connected = best_cost < BIG
        broken = ~jnp.any(connected) | ~prev_any

        restart = jnp.concatenate(
            [em_t[:, None], jnp.full((K, R - 1), BIG, em_t.dtype)], axis=1)
        new_score = jnp.where(broken, restart,
                              jnp.where(connected,
                                        best_cost + em_t[:, None], BIG))
        backptr = jnp.where(broken | ~connected, -1, idxs.astype(jnp.int32))

        score_out = jnp.where(act_t, new_score, score)
        new_carry = (score_out,
                     jnp.where(act_t, pt, prev_pt),
                     act_t | prev_any,
                     jnp.where(act_t, t_idx, prev_idx))
        emit = (score_out,
                jnp.where(act_t, backptr, identity_bp),
                act_t & broken)
        return new_carry, emit

    init = (jnp.full((K, R), BIG, jnp.float32), points[0], jnp.bool_(False),
            jnp.int32(0))
    xs = (em, points, active, jnp.arange(T, dtype=jnp.int32))
    _, (scores, backptrs, started) = jax.lax.scan(step, init, xs)
    # scores [T, K, R], backptrs [T, K, R] (flat-coded), started [T]

    final = scores[-1].reshape(K * R)
    order = jnp.argsort(final)[:R].astype(jnp.int32)         # best R states
    rank_score = final[order]
    rank_valid = rank_score < BIG

    def back_one(state):                                     # flat (c, r)
        def back(carry, inp):
            nxt_state, nxt_started = carry
            score_t, bp_next, act_t, started_t = inp
            safe = jnp.maximum(nxt_state, 0)
            prop = jnp.where(nxt_state >= 0,
                             bp_next.reshape(K * R)[safe], -1)
            # chain boundary: earlier chains keep their single best path
            own = jnp.argmin(score_t.reshape(K * R)).astype(jnp.int32)
            own = jnp.where(score_t.reshape(K * R)[own] < BIG, own, -1)
            terminal = nxt_started | (nxt_state < 0)
            state_t = jnp.where(terminal, own, prop)
            out = jnp.where(act_t, state_t, -1)
            return (state_t, started_t), out

        bp_above = jnp.concatenate(
            [backptrs[1:],
             jnp.broadcast_to(state, (1, K, R)).astype(jnp.int32)])
        rev = (scores[::-1], bp_above[::-1], active[::-1], started[::-1])
        _, states_rev = jax.lax.scan(
            back, (state.astype(jnp.int32), jnp.bool_(False)), rev)
        states = states_rev[::-1]
        return jnp.where(states >= 0, states // R, -1)       # slot per point

    choices = jax.vmap(back_one)(order)                      # [R, T]
    choices = jnp.where(rank_valid[:, None], choices, -1)
    return choices, rank_score, rank_valid
