"""Kernel unit tests vs NumPy oracles (SURVEY.md §4: "kernel unit tests: kNN
and Viterbi vs NumPy oracles on synthetic geometry")."""

import numpy as np
import jax.numpy as jnp
import pytest

from reporter_tpu.config import MatcherParams
from reporter_tpu.geometry import point_segment_project
from reporter_tpu.matcher.cpu_reference import find_candidates_cpu
from reporter_tpu.netgen.traces import synthesize_probe
from reporter_tpu.ops.candidates import BIG, find_candidates
from reporter_tpu.ops.hmm import route_distance
from reporter_tpu.ops.match import match_batch
from reporter_tpu.tiles.reach import reach_lookup

RADIUS = 50.0
K = 8


def oracle_candidates(ts, pt):
    """CPU-oracle candidates (cpu_reference is the single source of truth)."""
    cands = find_candidates_cpu(
        ts, pt, MatcherParams(search_radius=RADIUS, max_candidates=K))
    return {c.edge: (c.dist, c.offset) for c in cands}


class TestCandidates:
    def test_vs_oracle(self, tiny_tiles, rng):
        ts = tiny_tiles
        tables = ts.device_tables()
        lo = ts.node_xy.min(axis=0)
        hi = ts.node_xy.max(axis=0)
        pts = rng.uniform(lo, hi, size=(50, 2)).astype(np.float32)
        for pt in pts:
            got = find_candidates(jnp.asarray(pt), tables, ts.meta, RADIUS, K)
            want = oracle_candidates(ts, pt.astype(np.float64))
            got_edges = {int(e) for e, v in zip(got.edge, got.valid) if bool(v)}
            # The K-th-nearest cutoff is tie-prone (f32 kernel vs f64 oracle):
            # demand exact agreement only below the cutoff, and distance
            # near the cutoff for any disputed edge.
            cutoff = max(dv[0] for dv in want.values()) if want else 0.0
            sure = {e for e, dv in want.items() if dv[0] < cutoff - 0.01}
            assert sure <= got_edges
            d_all, t_all, _ = point_segment_project(
                pt[None, :].astype(np.float64), ts.seg_a, ts.seg_b)
            for e, d, off, v in zip(got.edge, got.dist, got.offset, got.valid):
                if not bool(v):
                    continue
                e = int(e)
                if e in want:
                    wd, woff = want[e]
                    assert abs(float(d) - wd) < 0.01
                    assert abs(float(off) - woff) < 0.1
                else:  # tie at the cutoff: must still be a genuine nearby edge
                    wd = d_all[ts.seg_edge == e].min()
                    assert abs(float(d) - wd) < 0.01
                    assert wd <= cutoff + 0.01

    def test_no_candidates_far_away(self, tiny_tiles):
        ts = tiny_tiles
        got = find_candidates(
            jnp.asarray(np.array([1e6, 1e6], np.float32)),
            ts.device_tables(), ts.meta, RADIUS, K)
        assert not bool(got.valid.any())


class TestRouteDistance:
    def test_vs_reach_tables(self, tiny_tiles, rng):
        ts = tiny_tiles
        tables = ts.device_tables()
        for _ in range(200):
            e1 = int(rng.integers(ts.num_edges))
            e2 = int(rng.integers(ts.num_edges))
            o1 = float(rng.uniform(0, ts.edge_len[e1]))
            o2 = float(rng.uniform(0, ts.edge_len[e2]))
            got = float(route_distance(
                jnp.int32(e1), jnp.float32(o1), jnp.int32(e2), jnp.float32(o2),
                tables, backward_slack=0.0))
            gap = reach_lookup(ts.reach_to, ts.reach_dist, ts.edge_reach_row, e1, e2)
            cross = (float(ts.edge_len[e1]) - o1) + gap + o2
            want = min(o2 - o1, cross) if (e1 == e2 and o2 >= o1) else cross
            if want == np.inf:
                assert got >= float(BIG)
            else:
                assert got == pytest.approx(want, abs=0.5)

    def test_consecutive_edges_gap_zero(self, tiny_tiles):
        ts = tiny_tiles
        tables = ts.device_tables()
        # any edge and a direct successor: route end→start must be ~0
        for e1 in range(0, ts.num_edges, 7):
            u = int(ts.edge_dst[e1])
            succ = [int(x) for x in ts.node_out[u] if x >= 0]
            if not succ:
                continue
            e2 = succ[0]
            got = float(route_distance(
                jnp.int32(e1), jnp.float32(ts.edge_len[e1]), jnp.int32(e2),
                jnp.float32(0.0), tables))
            assert got == pytest.approx(0.0, abs=1e-3)


class TestMatchAccuracy:
    def test_ground_truth_agreement(self, tiny_tiles):
        """Point-level edge agreement vs synthetic ground truth ≥ 90%
        (observed ~96%; the residual is node-boundary ambiguity)."""
        ts = tiny_tiles
        tables = ts.device_tables()
        agree = total = 0
        for seed in range(6):
            p = synthesize_probe(ts, seed=seed, num_points=60)
            out = match_batch(
                jnp.asarray(p.xy[None].astype(np.float32)),
                jnp.ones((1, 60), bool), tables, ts.meta, MatcherParams())
            edge = np.array(out.edge[0])
            assert np.array(out.matched[0]).all()
            ok = (edge == p.true_edges) | (edge == ts.edge_opp[p.true_edges])
            agree += int(ok.sum())
            total += 60
        assert agree / total >= 0.90

    def test_padding_invariance(self, tiny_tiles):
        """Padded tail must not change the matched prefix."""
        ts = tiny_tiles
        tables = ts.device_tables()
        p = synthesize_probe(ts, seed=11, num_points=40)
        pts40 = p.xy.astype(np.float32)
        out40 = match_batch(jnp.asarray(pts40[None]), jnp.ones((1, 40), bool),
                            tables, ts.meta, MatcherParams())
        pts64 = np.zeros((64, 2), np.float32)
        pts64[:40] = pts40
        valid = np.zeros((1, 64), bool)
        valid[0, :40] = True
        out64 = match_batch(jnp.asarray(pts64[None]), jnp.asarray(valid),
                            tables, ts.meta, MatcherParams())
        np.testing.assert_array_equal(
            np.array(out40.edge[0]), np.array(out64.edge[0, :40]))
        assert not np.array(out64.matched[0, 40:]).any()

    def test_determinism(self, tiny_tiles):
        """Same batch → bit-identical output under jit (SURVEY.md §5 race
        detection analog)."""
        ts = tiny_tiles
        tables = ts.device_tables()
        p = synthesize_probe(ts, seed=5, num_points=60)
        pts = jnp.asarray(p.xy[None].astype(np.float32))
        v = jnp.ones((1, 60), bool)
        a = match_batch(pts, v, tables, ts.meta, MatcherParams())
        b = match_batch(pts, v, tables, ts.meta, MatcherParams())
        np.testing.assert_array_equal(np.array(a.edge), np.array(b.edge))
        np.testing.assert_array_equal(np.array(a.offset), np.array(b.offset))

    def test_breakage_restarts_chain(self, tiny_tiles):
        """A huge jump mid-trace must start a new chain, not a bogus route."""
        ts = tiny_tiles
        tables = ts.device_tables()
        pa = synthesize_probe(ts, seed=2, num_points=20)
        pb = synthesize_probe(ts, seed=9, num_points=20)
        # Shift pb far away in time/space order: just concatenate positions —
        # the two walks are in different parts of the grid with a jump.
        pts = np.concatenate([pa.xy[:20], pb.xy[:20]]).astype(np.float32)
        out = match_batch(jnp.asarray(pts[None]), jnp.ones((1, 40), bool),
                          tables, ts.meta,
                          MatcherParams(breakage_distance=100.0))
        starts = np.array(out.chain_start[0])
        assert starts[0]
        # At least one restart somewhere in the concatenation neighborhood
        # (the jump may be < breakage if the walks happen to end nearby; seed
        # pair chosen so they don't).
        assert starts[1:].any()


class TestInterpolationMask:
    def test_keep_mask_matches_naive(self, tiny_tiles):
        import jax.numpy as jnp

        from reporter_tpu.ops.hmm import interpolation_keep_mask

        rng = np.random.default_rng(5)
        # random walk with some stationary clusters
        steps = rng.normal(0, 8, size=(40, 2))
        steps[10:15] = 0.1   # stopped vehicle
        pts = np.cumsum(steps, axis=0).astype(np.float32)
        valid = np.ones(40, bool)
        valid[35:] = False

        got = np.asarray(interpolation_keep_mask(
            jnp.asarray(pts), jnp.asarray(valid), 10.0))

        want = np.zeros(40, bool)
        last = None
        for t in range(40):
            if not valid[t]:
                continue
            if last is None or np.linalg.norm(pts[t] - pts[last]) >= 10.0:
                want[t] = True
                last = t
        np.testing.assert_array_equal(got, want)

    def test_disabled_keeps_all(self, tiny_tiles):
        import jax.numpy as jnp

        from reporter_tpu.ops.hmm import interpolation_keep_mask

        pts = jnp.zeros((8, 2), jnp.float32)
        valid = jnp.ones(8, bool)
        got = np.asarray(interpolation_keep_mask(pts, valid, 0.0))
        assert got.all()

    def test_stationary_cluster_interpolated_both_backends(self, tiny_tiles):
        """A stopped vehicle's noise cloud must not fragment the match, and
        jax/cpu backends must agree on which points vote."""
        from reporter_tpu.config import Config, MatcherParams
        from reporter_tpu.matcher.api import SegmentMatcher, Trace
        from reporter_tpu.netgen.traces import synthesize_probe

        ts = tiny_tiles
        probe = synthesize_probe(ts, seed=13, num_points=50, gps_sigma=3.0)
        xy = probe.xy.copy()
        xy[20:30] = xy[20] + np.random.default_rng(0).normal(
            0, 2.0, size=(10, 2))          # 10 samples while stopped
        times = probe.times
        tr = Trace(uuid="veh", xy=xy.astype(np.float32), times=times)

        recs = {}
        for backend in ("jax", "reference_cpu"):
            # pin the dense candidate path: this test compares interpolation
            # semantics across matcher backends, and grid-vs-dense tie
            # ordering on CPU would add unrelated noise
            from reporter_tpu.config import MatcherParams

            m = SegmentMatcher(ts, Config(
                matcher_backend=backend,
                matcher=MatcherParams(candidate_backend="dense")))
            recs[backend] = m.match_many([tr])[0]
        ids_j = [r.segment_id for r in recs["jax"]]
        ids_c = [r.segment_id for r in recs["reference_cpu"]]
        assert ids_j == ids_c


class TestBatchedViterbi:
    def test_edge_attr_lookup_exact(self, tiny_tiles):
        """The scan's one-row-gather lookup returns exactly reach_row[e]
        and edge_len[e]'s bits for every edge (113 edges: the last row of
        the attribute table is part padding), and edge 0's for e = -1."""
        from reporter_tpu.ops.hmm import edge_attr_lookup, edge_attr_table

        ts = tiny_tiles
        tables = ts.device_tables()
        E = ts.num_edges
        assert E % 64
        attr = edge_attr_table(tables["reach_row"], tables["edge_len"])
        assert attr.shape == (-(-E // 64), 128)
        e = np.concatenate([np.arange(E), [-1, 0, E - 1]]).astype(np.int32)
        n1, len1 = edge_attr_lookup(attr, jnp.asarray(e.reshape(2, -1)))
        want = np.maximum(e, 0)
        np.testing.assert_array_equal(
            np.asarray(n1).ravel(), np.asarray(tables["reach_row"])[want])
        np.testing.assert_array_equal(
            np.asarray(len1).ravel().view(np.int32),
            np.asarray(tables["edge_len"])[want].view(np.int32))

    @pytest.mark.parametrize("tiles", ["tiny_tiles", "sf_tiles"])
    def test_batched_matches_vmapped(self, tiles, request):
        """viterbi_decode_batched must be bit-identical to
        vmap(viterbi_decode) — same lattice, batch-last layout — on tiles
        whose edge counts (113, 5,328) are not multiples of 64."""
        import jax
        import jax.numpy as jnp

        from reporter_tpu.config import MatcherParams
        from reporter_tpu.netgen.traces import synthesize_fleet
        from reporter_tpu.ops.hmm import viterbi_decode, viterbi_decode_batched
        from reporter_tpu.ops.match import batch_candidates

        ts = request.getfixturevalue(tiles)
        assert ts.num_edges % 64
        tables = ts.device_tables()
        params = MatcherParams()
        fleet = synthesize_fleet(ts, 7, num_points=40, seed=17)
        pts = np.stack([p.xy for p in fleet]).astype(np.float32)
        # chain break + padding coverage
        pts[2, 20:] += np.float32(3000.0)
        valid = np.ones(pts.shape[:2], bool)
        valid[5, 30:] = False

        pj, vj = jnp.asarray(pts), jnp.asarray(valid)
        cands = batch_candidates(pj, vj, tables, ts.meta, params)
        args = (tables, params.sigma_z, params.beta,
                params.max_route_distance_factor, params.breakage_distance,
                params.backward_slack, params.interpolation_distance)

        ref = jax.vmap(lambda c, p, v: viterbi_decode(c, p, v, *args))(
            cands, pj, vj)
        got = viterbi_decode_batched(cands, pj, vj, *args)
        for name, a, b in zip(ref._fields, ref, got):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=name)


class TestTopKPaths:
    def test_best_path_matches_viterbi(self, tiny_tiles):
        import jax.numpy as jnp

        from reporter_tpu.config import MatcherParams
        from reporter_tpu.netgen.traces import synthesize_probe
        from reporter_tpu.ops.hmm import viterbi_decode, viterbi_topk_paths
        from reporter_tpu.ops.candidates import find_candidates_trace

        ts = tiny_tiles
        tables = ts.device_tables()
        params = MatcherParams()
        p = synthesize_probe(ts, seed=8, num_points=40, gps_sigma=3.0)
        pts = jnp.asarray(p.xy.astype(np.float32))
        valid = jnp.ones(len(p.xy), bool)
        cands = find_candidates_trace(pts, tables, ts.meta,
                                      params.search_radius,
                                      params.max_candidates)
        args = (tables, params.sigma_z, params.beta,
                params.max_route_distance_factor, params.breakage_distance,
                params.backward_slack, params.interpolation_distance)
        best = viterbi_decode(cands, pts, valid, *args)
        choices, scores, ok = viterbi_topk_paths(cands, pts, valid, *args)

        assert bool(ok[0])
        np.testing.assert_array_equal(np.asarray(choices[0]),
                                      np.asarray(best.choice))
        s = np.asarray(scores)
        v = np.asarray(ok)
        # scores ascend over valid ranks; invalid ranks sort last
        assert (np.diff(s[v]) >= -1e-5).all()
        # every valid alternate's choices point at real candidates
        cv = np.asarray(cands.valid)
        for r in range(len(v)):
            if not v[r]:
                continue
            ch = np.asarray(choices[r])
            for t, c in enumerate(ch):
                if c >= 0:
                    assert cv[t, c], f"rank {r} t {t}"
