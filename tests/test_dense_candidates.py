"""Dense (sweep) candidate search vs the grid-gather path and vs numpy.

The dense backend must agree with the grid backend wherever the grid's
dilation guarantees coverage (search_radius <= index_radius): same distinct
top-K edges, same distances, same offsets.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from reporter_tpu.config import CompilerParams, MatcherParams
from reporter_tpu.netgen.synthetic import generate_city
from reporter_tpu.netgen.traces import synthesize_fleet
from reporter_tpu.ops.candidates import find_candidates_trace
from reporter_tpu.ops.dense_candidates import (build_seg_pack,
                                               find_candidates_dense)
from reporter_tpu.ops.match import match_batch
from reporter_tpu.tiles.compiler import compile_network


@pytest.fixture(scope="module")
def ts():
    return compile_network(generate_city("tiny", seed=11), CompilerParams())


@pytest.fixture(scope="module")
def tables(ts):
    return ts.device_tables()


def _fleet_points(ts, b, t, seed=5):
    fleet = synthesize_fleet(ts, b, num_points=t, seed=seed)
    return np.stack([p.xy for p in fleet]).astype(np.float32)


def test_seg_pack_roundtrip(ts):
    sp = build_seg_pack(ts.seg_a, ts.seg_b, ts.seg_edge, ts.seg_off,
                        ts.seg_len)
    from reporter_tpu.ops.dense_candidates import _SBLK

    s = len(ts.seg_edge)
    assert sp.pack.shape[1] % _SBLK == 0
    edges = sp.pack[6].view(np.int32)
    # Morton sort permutes columns; same multiset of edges, -1 padding tail
    np.testing.assert_array_equal(np.sort(edges[:s]), np.sort(ts.seg_edge))
    assert (edges[s:] == -1).all()
    # every real column lies inside its block's bbox
    nblocks = sp.pack.shape[1] // _SBLK
    for blk in range(nblocks):
        cols = slice(blk * _SBLK, (blk + 1) * _SBLK)
        e = edges[cols]
        if (e < 0).all():
            assert np.isnan(sp.bbox[blk]).all()
            continue
        real = e >= 0
        xs = np.concatenate([sp.pack[0, cols][real], sp.pack[2, cols][real]])
        ys = np.concatenate([sp.pack[1, cols][real], sp.pack[3, cols][real]])
        assert xs.min() >= sp.bbox[blk, 0] - 1e-3
        assert ys.min() >= sp.bbox[blk, 1] - 1e-3
        assert xs.max() <= sp.bbox[blk, 2] + 1e-3
        assert ys.max() <= sp.bbox[blk, 3] + 1e-3


def test_dense_matches_grid(ts, tables):
    pts = _fleet_points(ts, 4, 40).reshape(-1, 2)
    radius, k = 50.0, 8

    dense = find_candidates_dense(
        jnp.asarray(pts), (tables["seg_pack"], tables["seg_bbox"]), radius, k)
    grid = find_candidates_trace(jnp.asarray(pts), tables, ts.meta, radius, k)

    d_edge = np.asarray(dense.edge)
    g_edge = np.asarray(grid.edge)
    d_dist = np.asarray(dense.dist)
    g_dist = np.asarray(grid.dist)
    for i in range(len(pts)):
        dv, gv = d_edge[i] >= 0, g_edge[i] >= 0
        assert dv.sum() == gv.sum(), f"point {i}: candidate count differs"
        dd = np.sort(d_dist[i][dv])
        gd = np.sort(g_dist[i][gv])
        # same distance multiset always
        np.testing.assert_allclose(dd, gd, rtol=1e-5, atol=1e-3,
                                   err_msg=f"point {i}")
        # exact edge-set agreement, ties included: both backends break
        # distance ties toward the smallest edge id (the Morton reorder
        # used to legally swap equidistant edges at the K-th cut; that
        # divergence is designed out now)
        assert (set(d_edge[i][dv].tolist()) == set(g_edge[i][gv].tolist())
                ), f"point {i}"


def test_long_segment_split():
    """Tiles with multi-km edges (organic/xl): build_seg_pack tiles them
    into sub-spans for tighter block bboxes. Candidates must be the SAME
    as an unsplit pack and as the grid backend; node-endpoint ties stay
    exact (the final piece pins the original endpoint bit-for-bit); and
    capacity's shape math must match the actually-built pack."""
    from reporter_tpu.geometry import xy_to_lonlat
    from reporter_tpu.netgen.network import RoadNetwork, Way
    from reporter_tpu.ops.dense_candidates import packed_columns

    # a 2 km spine meeting short streets at both ends
    xy = np.array([[-1000.0, 0.0], [1000.0, 0.0], [1000.0, 150.0],
                   [-1000.0, -150.0], [0.0, 140.0]])
    ll = xy_to_lonlat(xy, np.array([-122.3, 37.8]))
    net = RoadNetwork(node_lonlat=ll, ways=[
        Way(way_id=1, nodes=[0, 1], speed_mps=29.0),      # 2 km edge
        Way(way_id=2, nodes=[1, 2]),
        Way(way_id=3, nodes=[0, 3]),
        Way(way_id=4, nodes=[4, 1]),                      # long diagonal
    ])
    lts = compile_network(net, CompilerParams(reach_radius=400.0))
    assert float(lts.seg_len.max()) > 1000.0

    split = build_seg_pack(lts.seg_a, lts.seg_b, lts.seg_edge,
                           lts.seg_off, lts.seg_len)
    unsplit = build_seg_pack(lts.seg_a, lts.seg_b, lts.seg_edge,
                             lts.seg_off, lts.seg_len, split_len=0.0)
    assert split.pack.shape[1] == packed_columns(lts.seg_len)
    n_pieces = (split.pack[6].view(np.int32) >= 0).sum()
    assert n_pieces > len(lts.seg_edge)        # the long edges DID split

    tab = lts.device_tables()
    rng = np.random.default_rng(2)
    pts = np.vstack([
        rng.uniform([-1100, -250], [1100, 250], (200, 2)),
        lts.node_xy[[0, 1]],                   # exactly at the junctions
    ]).astype(np.float32)
    k = 8
    cs = find_candidates_dense(jnp.asarray(pts),
                               (jnp.asarray(split.pack),
                                jnp.asarray(split.bbox)), 50.0, k)
    cu = find_candidates_dense(jnp.asarray(pts),
                               (jnp.asarray(unsplit.pack),
                                jnp.asarray(unsplit.bbox)), 50.0, k)
    cg = find_candidates_trace(jnp.asarray(pts), tab, lts.meta, 50.0, k)
    es, eu, eg = (np.asarray(c.edge) for c in (cs, cu, cg))
    for i in range(len(pts)):
        # sub-ulp seam rounding may flip the ORDER of near-ties; the edge
        # SET must be identical across all three packs
        s_set = set(es[i][es[i] >= 0].tolist())
        assert s_set == set(eu[i][eu[i] >= 0].tolist()), i
        assert s_set == set(eg[i][eg[i] >= 0].tolist()), i
    # at the junction nodes the ties are EXACT (endpoints bit-preserved),
    # so even the order must survive the split
    np.testing.assert_array_equal(es[-2:], eg[-2:])
    # offsets compare per (row, edge) — column order differs at near-ties
    os_, ou = np.asarray(cs.offset), np.asarray(cu.offset)
    for i in range(len(pts)):
        got = {int(e): float(o) for e, o in zip(es[i], os_[i]) if e >= 0}
        want = {int(e): float(o) for e, o in zip(eu[i], ou[i]) if e >= 0}
        for e, o in want.items():              # seam rounding ≤ ~0.5 m
            assert abs(got[e] - o) < 0.51, (i, e, got[e], o)
    np.testing.assert_allclose(np.sort(np.asarray(cs.dist), 1),
                               np.sort(np.asarray(cg.dist), 1), atol=1e-3)


def test_tie_break_at_star_junction():
    """12 ways meeting at one node: a query at the node ties every
    incident edge at distance ~0, overflowing K — all three candidate
    paths (dense sweep, grid gather, CPU oracle) must keep the SAME
    smallest-edge-id subset (the organic 2.7% phantom-disagreement bug,
    round 4)."""
    from reporter_tpu.geometry import xy_to_lonlat
    from reporter_tpu.matcher.cpu_reference import find_candidates_cpu
    from reporter_tpu.netgen.network import RoadNetwork, Way
    from reporter_tpu.config import MatcherParams

    n_spokes = 12
    ang = np.linspace(0, 2 * np.pi, n_spokes, endpoint=False)
    xy = np.vstack([[0.0, 0.0],
                    np.stack([np.cos(ang), np.sin(ang)], 1) * 200.0])
    ll = xy_to_lonlat(xy, np.array([-122.4, 37.75]))
    ways = [Way(way_id=i + 1, nodes=[0, i + 1]) for i in range(n_spokes)]
    sts = compile_network(RoadNetwork(node_lonlat=ll, ways=ways,
                                      name="star"),
                          CompilerParams(cell_size=64.0))
    tab = sts.device_tables()
    k = 8
    # exactly the node's stored coordinate: every incident edge ties at
    # d == 0.0 bit-for-bit (an off-node point gives sub-mm NEAR-ties,
    # where f32 d-vs-d2 comparison order may legitimately differ)
    pt = sts.node_xy[0:1].astype(np.float32)
    dense = find_candidates_dense(
        jnp.asarray(pt), (tab["seg_pack"], tab["seg_bbox"]), 50.0, k)
    grid = find_candidates_trace(jnp.asarray(pt), tab, sts.meta, 50.0, k)
    cpu = find_candidates_cpu(sts, pt[0].astype(np.float64),
                              MatcherParams())
    d_e = [int(e) for e in np.asarray(dense.edge)[0] if e >= 0]
    g_e = [int(e) for e in np.asarray(grid.edge)[0] if e >= 0]
    c_e = [c.edge for c in cpu]
    assert len(d_e) == k                 # ties overflow K: all slots full
    assert d_e == g_e == c_e, (d_e, g_e, c_e)
    # and the kept subset is exactly the K smallest edge ids of the tie
    assert d_e == sorted(d_e)


def test_dense_against_numpy_bruteforce(ts, tables):
    rng = np.random.default_rng(3)
    lo = ts.node_xy.min(0) - 30.0
    hi = ts.node_xy.max(0) + 30.0
    pts = rng.uniform(lo, hi, size=(64, 2)).astype(np.float32)
    radius, k = 50.0, 8

    dense = find_candidates_dense(
        jnp.asarray(pts), (tables["seg_pack"], tables["seg_bbox"]), radius, k)
    a, b = ts.seg_a, ts.seg_b
    ab = b - a
    denom = np.maximum((ab * ab).sum(1), 1e-12)
    for i, p in enumerate(pts):
        t = np.clip(((p - a) * ab).sum(1) / denom, 0, 1)
        proj = a + t[:, None] * ab
        d = np.linalg.norm(p - proj, axis=1)
        best: dict[int, float] = {}
        for e, dd in zip(ts.seg_edge, d):
            if dd <= radius and (e not in best or dd < best[e]):
                best[int(e)] = float(dd)
        want = sorted(best.items(), key=lambda kv: kv[1])[:k]
        got_e = [int(e) for e in np.asarray(dense.edge[i]) if e >= 0]
        got_d = [float(x) for x, e in
                 zip(np.asarray(dense.dist[i]), np.asarray(dense.edge[i]))
                 if e >= 0]
        assert len(got_e) == len(want), f"point {i}"
        np.testing.assert_allclose(
            got_d, [w[1] for w in want], rtol=1e-4, atol=1e-2)
        # edge identity can swap only between equal distances
        for (we, wd), ge, gd in zip(want, got_e, got_d):
            assert we == ge or abs(wd - gd) < 1e-2


def test_match_batch_dense_vs_grid(ts, tables):
    pts = _fleet_points(ts, 6, 48)
    valid = np.ones(pts.shape[:2], bool)
    p_dense = MatcherParams(candidate_backend="dense")
    p_grid = MatcherParams(candidate_backend="grid")
    out_d = match_batch(jnp.asarray(pts), jnp.asarray(valid), tables,
                        ts.meta, p_dense)
    out_g = match_batch(jnp.asarray(pts), jnp.asarray(valid), tables,
                        ts.meta, p_grid)
    # candidate-order ties (e.g. the two directed edges of a two-way street
    # at identical distance) legally resolve differently between backends
    agree = (np.asarray(out_d.edge) == np.asarray(out_g.edge)).mean()
    assert agree > 0.95, f"dense vs grid match agreement {agree:.3f}"
    np.testing.assert_array_equal(np.asarray(out_d.matched),
                                  np.asarray(out_g.matched))


def test_seg_pack_sub_quads(ts):
    """The per-sub-block quads (round 8, the kernel's second culling
    level): every real column's endpoints sit inside its own slice's
    quad, all-padding slices carry NaN, and the quads never exceed the
    whole block's bbox."""
    from reporter_tpu.ops.dense_candidates import _SBLK, _SUB

    sp = build_seg_pack(ts.seg_a, ts.seg_b, ts.seg_edge, ts.seg_off,
                        ts.seg_len)
    nsub = _SBLK // _SUB if _SBLK % _SUB == 0 else 1
    subw = _SBLK // nsub
    assert sp.sub.shape == (sp.bbox.shape[0], nsub * 4)
    edges = sp.pack[6].view(np.int32)
    for blk in range(sp.bbox.shape[0]):
        for s in range(nsub):
            cols = slice(blk * _SBLK + s * subw, blk * _SBLK + (s + 1) * subw)
            real = edges[cols] >= 0
            quad = sp.sub[blk, 4 * s:4 * s + 4]
            if not real.any():
                assert np.isnan(quad).all()
                continue
            xs = np.concatenate([sp.pack[0, cols][real],
                                 sp.pack[2, cols][real]])
            ys = np.concatenate([sp.pack[1, cols][real],
                                 sp.pack[3, cols][real]])
            assert xs.min() >= quad[0] - 1e-3 and xs.max() <= quad[2] + 1e-3
            assert ys.min() >= quad[1] - 1e-3 and ys.max() <= quad[3] + 1e-3
            if not np.isnan(sp.bbox[blk]).any():
                assert quad[0] >= sp.bbox[blk, 0] - 1e-3
                assert quad[2] <= sp.bbox[blk, 2] + 1e-3


def test_pallas_kernels_interpret_parity(ts, monkeypatch):
    """EVERY pallas sweep kernel through the interpreter vs the jnp
    reference — the bit-identity gate for kernel logic without TPU
    access. One in-process test replaces the old per-case subprocesses:
    ``_INTERPRET`` / ``_SBLK`` / ``_SUB`` / ``_NJ_CAP`` are module
    globals read at CALL time, so monkeypatch flips them, and interpret
    pallas costs seconds PER CALL (the narrow-grid cond traces BOTH
    sweeps each call), so coverage is folded into a handful of calls
    over one shared batch shape:

      1. round-8 two-level kernel, narrow launch EXECUTING (_NJ_CAP=1,
         spatially tight batch) — junction-node d=0 ties included;
      2. same kernel, full-width fallback executing (spread batch with
         48-52 m radius-boundary points: the in/out decision rides the
         exact r2 test);
      3. bf16 coarse-filter variant (cond lifted — one trace), same
         spread batch: conservative-refinement exactness incl. ties;
      4. the retained r7 whole-block kernel (subcull=False), cond
         live — the bench A/B arm stays pinned too;
      5-7. the round-13 MXU arm: narrow branch executing on the tight
         batch (d=0 ties through the matmul coarse pass), full-width
         fallback executing on the spread batch (radius-boundary
         points), and the bf16-operand matmul (cond lifted) — the three
         adversarial regimes the r8 arms pinned, now pinned for the
         matmul-form coarse pass too.

    _SBLK forced to 128 / _SUB to 64 so even the tiny tile spans
    multiple blocks x 2 sub-slices per block (multi-block merge + the
    `fresh` skip + both cond branches all exercise)."""
    import jax.numpy as jnp

    import reporter_tpu.ops.dense_candidates as dc

    monkeypatch.setattr(dc, "_INTERPRET", True)
    monkeypatch.setattr(dc, "_SBLK", 128)
    monkeypatch.setattr(dc, "_SUB", 64)

    sp = build_seg_pack(ts.seg_a, ts.seg_b, ts.seg_edge, ts.seg_off,
                        ts.seg_len, block=128)
    assert sp.bbox.shape[0] >= 2 and sp.sub.shape[1] == 8
    packs = (jnp.asarray(sp.pack), jnp.asarray(sp.bbox),
             jnp.asarray(sp.sub), jnp.asarray(sp.feat))

    rng = np.random.default_rng(7)
    lo = ts.node_xy.min(0)
    hi = ts.node_xy.max(0)
    N = 96                       # ONE shape: jnp reference compiles once

    def pad(p):
        p = np.asarray(p, np.float32)
        return np.tile(p, (-(-N // len(p)), 1))[:N]

    local = pad(np.concatenate([      # corner cluster + exact node ties
        lo + rng.uniform(0, 40.0, (64, 2)).astype(np.float32),
        ts.node_xy[:32].astype(np.float32)]))
    mid = ((ts.seg_a + ts.seg_b) * 0.5)[:48]
    ang = rng.uniform(0, 2 * np.pi, len(mid))
    r_off = rng.uniform(48.0, 52.0, len(mid))[:, None]
    spread = pad(np.concatenate([     # tile-wide + boundary + node ties
        rng.uniform(lo - 30, hi + 30, (32, 2)),
        ts.node_xy[:16],
        mid + np.stack([np.cos(ang), np.sin(ang)], 1) * r_off]))

    refs = {}

    def check(pts, name, cap, **kw):
        monkeypatch.setattr(dc, "_NJ_CAP", cap)
        pj = jnp.asarray(pts)
        if name not in refs:
            refs[name] = dc._dense_jnp(pj, (packs[0], None), 50.0, 8)
        e, o, d = refs[name]
        c = dc.find_candidates_dense(pj, packs, 50.0, 8, **kw)
        tag = (name, cap, kw)
        assert (np.asarray(c.edge) == np.asarray(e)).all(), tag
        assert np.allclose(np.asarray(c.dist), np.asarray(d),
                           rtol=1e-5, atol=1e-2), tag
        assert np.allclose(np.asarray(c.offset), np.asarray(o),
                           rtol=1e-5, atol=1e-2), tag

    check(local, "local", cap=1)                    # narrow executes
    check(spread, "spread", cap=1)                  # fallback executes
    check(spread, "spread", cap=8, lowp="bf16")     # no cond: one trace
    check(spread, "spread", cap=1, subcull=False)   # r7 whole-block arm
    check(local, "local", cap=1, mxu=True)          # mxu: narrow + ties
    check(spread, "spread", cap=1, mxu=True)        # mxu: fallback + 48-52m
    check(spread, "spread", cap=8, mxu=True,        # mxu: bf16 operands,
          lowp="bf16")                              # no cond: one trace

    # documented 2-tuple fallback: a pack WITHOUT sub quads silently
    # runs the whole-block kernel even with subcull requested (pre-r8
    # packs / external callers) — no cond (cap high): one trace
    monkeypatch.setattr(dc, "_NJ_CAP", 8)
    c = dc.find_candidates_dense(jnp.asarray(spread), packs[:2], 50.0, 8)
    e, o, d = refs["spread"]
    assert (np.asarray(c.edge) == np.asarray(e)).all()
    assert np.allclose(np.asarray(c.dist), np.asarray(d),
                       rtol=1e-5, atol=1e-2)

    # mxu=True on a pack WITHOUT feat rows must raise, not silently run
    # the plain two-level kernel (an A/B arm measuring itself)
    with pytest.raises(ValueError, match="feat"):
        dc.find_candidates_dense(jnp.asarray(spread), packs[:3], 50.0, 8,
                                 mxu=True)


def test_seg_pack_feat_quadratic(ts):
    """The round-13 MXU feature rows: for every real column, the staged
    quadratic form evaluated at a recentered point equals the squared
    point-to-LINE distance (f64 reference), which lower-bounds the exact
    point-to-segment distance; padding columns carry F = BIG so they can
    never keep a slice alive on their own."""
    from reporter_tpu.ops import dense_candidates as dc

    sp = build_seg_pack(ts.seg_a, ts.seg_b, ts.seg_edge, ts.seg_off,
                        ts.seg_len)
    edges = sp.pack[dc.SP_EDGE].view(np.int32)
    real = edges >= 0
    assert sp.feat.shape == sp.pack.shape
    assert (sp.feat[dc.SF_F][~real] == dc.BIG).all()
    assert (sp.feat[dc.SF_A:dc.SF_F][:, ~real] == 0.0).all()

    f = sp.feat.astype(np.float64)
    a = np.stack([sp.pack[dc.SP_AX], sp.pack[dc.SP_AY]], 1)[real].astype(
        np.float64)
    b = np.stack([sp.pack[dc.SP_BX], sp.pack[dc.SP_BY]], 1)[real].astype(
        np.float64)
    d = b - a
    denom = np.maximum((d * d).sum(1), 1e-12)
    rng = np.random.default_rng(9)
    pts = rng.uniform(ts.node_xy.min(0) - 80, ts.node_xy.max(0) + 80,
                      (40, 2))
    for p in pts:
        qx = p[0] - f[dc.SF_CX][real]
        qy = p[1] - f[dc.SF_CY][real]
        form = (f[dc.SF_A][real] * qx * qx + f[dc.SF_B][real] * qy * qy
                + f[dc.SF_C][real] * qx * qy + f[dc.SF_D][real] * qx
                + f[dc.SF_E][real] * qy + f[dc.SF_F][real])
        cross = (p[0] - a[:, 0]) * d[:, 1] - (p[1] - a[:, 1]) * d[:, 0]
        dline2 = cross * cross / denom
        np.testing.assert_allclose(form, dline2, rtol=1e-3, atol=0.05)
        # lower bound on the exact segment distance (clamped projection)
        t = np.clip(((p - a) * d).sum(1) / denom, 0.0, 1.0)
        proj = a + t[:, None] * d
        dseg2 = ((p - proj) ** 2).sum(1)
        assert (form <= dseg2 + 0.06).all()


def test_mxu_coarse_filter_is_conservative_under_bf16():
    """Fuzz the margin constants (_MXU_REL_MARGIN/_MXU_ABS_MARGIN): a
    host replication of the kernel's coarse pass — recenter, clamp into
    the dilated slice box, build the [.., 8] features, round EVERY matmul
    operand to bf16 (harsher than the MXU's exact-product/f32-accumulate
    pipeline) — must never score an in-radius pair above the slice
    threshold. The clamp-projection argument (the box contains the
    slice's segments, so projecting the point into it never increases
    its distance to them) plus the margin must absorb every rounding
    source, or the kernel could silently drop candidates on chip."""
    import ml_dtypes

    from reporter_tpu.ops import dense_candidates as dc

    rng = np.random.default_rng(17)
    n = 400
    radius = 50.0
    a = rng.uniform(0, 3000.0, (n, 2)).astype(np.float32)
    # mixed lengths incl. >256 m (exercises the pre-split inside
    # build_seg_pack) and near-degenerate segments
    span = rng.uniform(0.01, 600.0, (n, 1)).astype(np.float32)
    ang = rng.uniform(0, 2 * np.pi, (n, 1))
    b = (a + span * np.concatenate(
        [np.cos(ang), np.sin(ang)], 1)).astype(np.float32)
    seg_len = np.linalg.norm(b - a, axis=1).astype(np.float32)
    sp = build_seg_pack(a, b, np.arange(n, dtype=np.int32),
                        np.zeros(n, np.float32), seg_len)
    # points: near segments, at endpoints (d=0 ties), at the radius
    # boundary, and far away (the clamp regime)
    pts = np.concatenate([
        a[:80] + rng.uniform(-60, 60, (80, 2)).astype(np.float32),
        a[:40],
        rng.uniform(-5000, 8000, (40, 2)).astype(np.float32),
    ]).astype(np.float32)

    pack, feat, sub = sp.pack, sp.feat, sp.sub
    edges = pack[dc.SP_EDGE].view(np.int32)
    nsub = sub.shape[1] // 4
    subw = pack.shape[1] // (sub.shape[0] * nsub)
    mx = np.float32(radius * 1.001 + 0.5)
    bf = ml_dtypes.bfloat16
    # exact segment distances (f64) for the conservativeness reference
    a64 = np.stack([pack[dc.SP_AX], pack[dc.SP_AY]], 1).astype(np.float64)
    b64 = np.stack([pack[dc.SP_BX], pack[dc.SP_BY]], 1).astype(np.float64)
    d64 = b64 - a64
    denom = np.maximum((d64 * d64).sum(1), 1e-12)
    checked = 0
    for blk in range(sub.shape[0]):
        for s in range(nsub):
            quad = sub[blk, 4 * s:4 * s + 4]
            if np.isnan(quad).any():
                continue
            cols = slice(blk * subw * nsub + s * subw,
                         blk * subw * nsub + (s + 1) * subw)
            fcols = feat[:, cols]
            cx, cy = fcols[dc.SF_CX, 0], fcols[dc.SF_CY, 0]
            exm = (quad[2] - quad[0]) * np.float32(0.5) + mx
            eym = (quad[3] - quad[1]) * np.float32(0.5) + mx
            qx = np.clip(pts[:, 0] - cx, -exm, exm).astype(np.float32)
            qy = np.clip(pts[:, 1] - cy, -eym, eym).astype(np.float32)
            pf = np.stack([qx * qx, qy * qy, qx * qy, qx, qy,
                           np.ones_like(qx), np.zeros_like(qx),
                           np.zeros_like(qx)], 1)           # [P, 8]
            lhs = pf.astype(bf).astype(np.float32)
            rhs = fcols.astype(bf).astype(np.float32)
            coarse = lhs @ rhs                              # [P, subw]
            scale = np.float32(max(exm, eym))
            thr = (np.float32(radius * radius)
                   + scale * scale * np.float32(dc._MXU_REL_MARGIN)
                   + np.float32(dc._MXU_ABS_MARGIN))
            # exact pair distances for this slice's real columns
            real = edges[cols] >= 0
            if not real.any():
                continue
            ai = a64[cols][real]
            di = d64[cols][real]
            den = denom[cols][real]
            t = np.clip(((pts[:, None, :] - ai[None]) * di[None]).sum(-1)
                        / den[None], 0.0, 1.0)
            proj = ai[None] + t[..., None] * di[None]
            dseg2 = ((pts[:, None, :] - proj) ** 2).sum(-1)  # [P, nreal]
            in_radius = dseg2 <= radius * radius
            if in_radius.any():
                assert (coarse[:, :len(den)][in_radius] <= thr).all(), (
                    blk, s)
                checked += int(in_radius.sum())
    assert checked > 300    # the fuzz actually exercised in-radius pairs


def test_mxu_coarse_gate_actually_culls():
    """The gate's OTHER edge: an always-admit defect (flipped
    comparison, runaway threshold) would pass every parity and
    conservativeness test — the coarse pass only ever ADDS exact work —
    and ship as pure matmul overhead. Pin the skip case with a host
    replica under bf16 rounding: points INSIDE a sparse slice's bbox
    (so the r8 sub-bbox cull admits them) but far from its actual lines
    must score above the slice threshold, i.e. the matmul gate would
    skip the slice."""
    import ml_dtypes

    from reporter_tpu.ops import dense_candidates as dc

    radius = 50.0
    # parallel diagonals: their joint bbox is the whole square, but the
    # lower-right corner is hundreds of meters from every line — the
    # bbox-inflated sparse-slice shape the matmul pass exists to cull
    n = 4
    a = np.stack([np.arange(n) * 12.0, np.zeros(n)], 1).astype(np.float32)
    b = (a + np.float32(400.0)).astype(np.float32)
    seg_len = np.linalg.norm(b - a, axis=1).astype(np.float32)
    sp = build_seg_pack(a, b, np.arange(n, dtype=np.int32),
                        np.zeros(n, np.float32), seg_len,
                        split_len=0.0)         # keep ONE slice of lines
    quad = sp.sub[0, 0:4]
    assert not np.isnan(quad).any()
    feat = sp.feat[:, :dc._SUB]
    mx = np.float32(radius * 1.001 + 0.5)
    exm = (quad[2] - quad[0]) * np.float32(0.5) + mx
    eym = (quad[3] - quad[1]) * np.float32(0.5) + mx
    scale = np.float32(max(exm, eym))
    thr = (np.float32(radius * radius)
           + scale * scale * np.float32(dc._MXU_REL_MARGIN)
           + np.float32(dc._MXU_ABS_MARGIN))
    # in-bbox points far from the diagonals (>= ~240 m to every line,
    # well past the margin-widened threshold radius)
    pts = np.array([[380.0, 20.0], [410.0, 40.0], [350.0, 5.0]],
                   np.float32)
    bf = ml_dtypes.bfloat16
    cx, cy = feat[dc.SF_CX, 0], feat[dc.SF_CY, 0]
    qx = np.clip(pts[:, 0] - cx, -exm, exm).astype(np.float32)
    qy = np.clip(pts[:, 1] - cy, -eym, eym).astype(np.float32)
    pf = np.stack([qx * qx, qy * qy, qx * qy, qx, qy,
                   np.ones_like(qx), np.zeros_like(qx),
                   np.zeros_like(qx)], 1)
    coarse = pf.astype(bf).astype(np.float32) @ feat.astype(bf).astype(
        np.float32)
    # min over the chunk's points × the slice's columns is the kernel's
    # gate operand: it must EXCEED the threshold → the slice is skipped
    assert coarse.min() > thr, (float(coarse.min()), float(thr))


def test_mxu_interpret_parity_split_tile(monkeypatch):
    """MXU arm on a tile with >256 m edges (the long-segment pre-split):
    collinear sub-span seams + endpoint-pinned ties must survive the
    matmul coarse pass bit-identically — one interpret call, jnp
    reference (the satellite's fourth adversarial regime; the other
    three ride the shared-fixture calls in the main parity test)."""
    import jax.numpy as jnp

    import reporter_tpu.ops.dense_candidates as dc
    from reporter_tpu.geometry import xy_to_lonlat
    from reporter_tpu.netgen.network import RoadNetwork, Way

    monkeypatch.setattr(dc, "_INTERPRET", True)
    monkeypatch.setattr(dc, "_SBLK", 128)
    monkeypatch.setattr(dc, "_SUB", 64)
    monkeypatch.setattr(dc, "_NJ_CAP", 8)       # cond lifted: one trace

    xy = np.array([[-1000.0, 0.0], [1000.0, 0.0], [1000.0, 150.0],
                   [-1000.0, -150.0], [0.0, 140.0]])
    ll = xy_to_lonlat(xy, np.array([-122.3, 37.8]))
    net = RoadNetwork(node_lonlat=ll, ways=[
        Way(way_id=1, nodes=[0, 1], speed_mps=29.0),      # 2 km edge
        Way(way_id=2, nodes=[1, 2]),
        Way(way_id=3, nodes=[0, 3]),
        Way(way_id=4, nodes=[4, 1]),
    ])
    lts = compile_network(net, CompilerParams(reach_radius=400.0))
    assert float(lts.seg_len.max()) > 1000.0
    sp = build_seg_pack(lts.seg_a, lts.seg_b, lts.seg_edge, lts.seg_off,
                        lts.seg_len, block=128)
    packs = (jnp.asarray(sp.pack), jnp.asarray(sp.bbox),
             jnp.asarray(sp.sub), jnp.asarray(sp.feat))
    rng = np.random.default_rng(2)
    pts = np.vstack([
        rng.uniform([-1100, -250], [1100, 250], (90, 2)),
        lts.node_xy[[0, 1]],                   # exactly at the junctions
        np.stack([np.linspace(-950, 950, 4), np.full(4, 50.0)], 1),
    ]).astype(np.float32)
    ref = dc._dense_jnp(jnp.asarray(pts), (packs[0], None), 50.0, 8)
    c = dc.find_candidates_dense(jnp.asarray(pts), packs, 50.0, 8,
                                 mxu=True)
    assert (np.asarray(c.edge) == np.asarray(ref[0])).all()
    assert np.allclose(np.asarray(c.dist), np.asarray(ref[2]),
                       rtol=1e-5, atol=1e-2)
    assert np.allclose(np.asarray(c.offset), np.asarray(ref[1]),
                       rtol=1e-5, atol=1e-2)


def test_missing_pallas_raises_on_accelerator(monkeypatch):
    """No hidden fallback: on a non-CPU backend a failed Pallas import
    raises instead of quietly serving the jnp full sweep; CPU keeps the
    jnp path."""
    import jax

    from reporter_tpu.ops import dense_candidates as dc

    monkeypatch.setattr(dc, "pl", None)
    monkeypatch.setattr(dc, "_INTERPRET", False)
    assert dc._use_pallas() is False           # CPU: the jnp reference
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="no fallback"):
        dc._use_pallas()
