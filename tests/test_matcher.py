"""SegmentMatcher tests: backend agreement (the BASELINE "<5% vs Meili"
proxy), output schema parity, and segment association correctness."""

import numpy as np
import pytest

from reporter_tpu.config import CompilerParams, Config
from reporter_tpu.matcher import SegmentMatcher
from reporter_tpu.matcher.api import Trace
from reporter_tpu.netgen.synthetic import generate_city
from reporter_tpu.netgen.traces import synthesize_fleet, synthesize_probe
from reporter_tpu.tiles.compiler import compile_network

SCHEMA_KEYS = {"segment_id", "way_ids", "start_time", "end_time", "length",
               "internal", "queue_length"}


def _edit_distance(a: list, b: list) -> int:
    """Levenshtein over segment-ID sequences (the disagreement unit)."""
    dp = list(range(len(b) + 1))
    for i, x in enumerate(a, 1):
        prev, dp[0] = dp[0], i
        for j, y in enumerate(b, 1):
            prev, dp[j] = dp[j], min(dp[j] + 1, dp[j - 1] + 1,
                                     prev + (x != y))
    return dp[len(b)]


@pytest.fixture(scope="module")
def short_seg_tiles():
    """Short OSMLR segments (250 m) so 60-point traces complete several."""
    return compile_network(
        generate_city("tiny"),
        CompilerParams(reach_radius=500.0, osmlr_max_length=250.0))


@pytest.fixture(scope="module")
def matchers(short_seg_tiles):
    return (SegmentMatcher(short_seg_tiles, Config(matcher_backend="jax")),
            SegmentMatcher(short_seg_tiles,
                           Config(matcher_backend="reference_cpu")))


class TestSchema:
    def test_reference_output_shape(self, matchers, short_seg_tiles):
        mj, _ = matchers
        p = synthesize_probe(short_seg_tiles, seed=1, num_points=60)
        out = mj.match(p.to_report_json())
        assert set(out.keys()) == {"mode", "segments"}
        assert out["segments"], "a 60-point drive must touch some segment"
        for s in out["segments"]:
            assert set(s.keys()) == SCHEMA_KEYS
            assert s["length"] > 0

    def test_empty_trace(self, matchers):
        mj, mc = matchers
        for m in (mj, mc):
            out = m.match({"uuid": "x", "trace": []})
            assert out["segments"] == []


class TestBackendAgreement:
    def test_segment_disagreement_under_5pct(self, matchers, short_seg_tiles):
        """Complete-segment sequences from the jax backend vs the exact-
        Dijkstra CPU oracle; BASELINE target <5% disagreement."""
        mj, mc = matchers
        probes = synthesize_fleet(short_seg_tiles, 20, num_points=60, seed=7)
        traces = [Trace.from_json(p.to_report_json(), short_seg_tiles)
                  for p in probes]
        res_j = mj.match_many(traces)
        res_c = [mc.match_trace(t) for t in traces]
        diff = total = 0
        for rj, rc in zip(res_j, res_c):
            ids_j = [r.segment_id for r in rj if r.complete]
            ids_c = [r.segment_id for r in rc if r.complete]
            total += max(len(ids_j), len(ids_c), 1)
            diff += _edit_distance(ids_j, ids_c)
        assert total > 20, "fleet should produce complete segments"
        assert diff / total < 0.05, f"disagreement {diff}/{total}"

    def test_complete_segments_have_times(self, matchers, short_seg_tiles):
        mj, _ = matchers
        p = synthesize_probe(short_seg_tiles, seed=4, num_points=120)
        recs = mj.match_trace(Trace.from_json(p.to_report_json(),
                                              short_seg_tiles))
        complete = [r for r in recs if r.complete]
        assert complete
        for r in complete:
            assert 0 <= r.start_time < r.end_time
            assert r.length == pytest.approx(
                float(short_seg_tiles.osmlr_len[
                    np.nonzero(short_seg_tiles.osmlr_id == r.segment_id)[0][0]]),
                abs=2.0)

    def test_true_path_segments_recovered(self, matchers, short_seg_tiles):
        """Complete segments reported must be on the ground-truth drive."""
        mj, _ = matchers
        ts = short_seg_tiles
        for seed in (2, 5, 8):
            p = synthesize_probe(ts, seed=seed, num_points=120)
            recs = mj.match_trace(Trace.from_json(p.to_report_json(), ts))
            true_rows = set(int(r) for r in ts.edge_osmlr[p.true_edges])
            true_rows |= {int(ts.edge_osmlr[ts.edge_opp[e]])
                          for e in p.true_edges if ts.edge_opp[e] >= 0}
            true_ids = {int(ts.osmlr_id[r]) for r in true_rows if r >= 0}
            got = [r.segment_id for r in recs if r.complete]
            on_path = sum(g in true_ids for g in got)
            assert on_path >= 0.9 * len(got)


class TestLongTraces:
    def test_chunked_decode_no_data_loss(self, short_seg_tiles, monkeypatch):
        """Traces beyond the largest bucket decode in chunks, not truncate."""
        import reporter_tpu.matcher.api as api_mod
        monkeypatch.setattr(api_mod, "_BUCKETS", (16, 32))
        ts = short_seg_tiles
        m = SegmentMatcher(ts, Config(matcher_backend="jax"))
        p = synthesize_probe(ts, seed=3, num_points=70)
        tr = Trace.from_json(p.to_report_json(), ts)
        edges, offs, starts = m._decode_many([tr])[0]
        assert len(edges) == 70
        assert (edges >= 0).mean() > 0.9  # matched across all chunks
        recs = m.match_trace(tr)
        assert recs


class TestTimes:
    def test_times_monotone_and_in_span(self, matchers, short_seg_tiles):
        mj, _ = matchers
        p = synthesize_probe(short_seg_tiles, seed=6, num_points=90)
        recs = mj.match_trace(Trace.from_json(p.to_report_json(),
                                              short_seg_tiles))
        t_lo, t_hi = p.times[0], p.times[-1]
        last_end = -1.0
        for r in recs:
            if not r.complete:
                continue
            assert t_lo <= r.start_time <= t_hi
            assert t_lo <= r.end_time <= t_hi
            assert r.start_time >= last_end - 1.0  # drive order
            last_end = r.end_time


class TestQuantizedInfeed:
    def test_long_span_trace_falls_back_to_f32(self, short_seg_tiles):
        """A trace spanning beyond i16 fixed-point range must take the f32
        wire path and still decode correctly (same records as a nearby
        normal trace run)."""
        import numpy as np

        from reporter_tpu.config import Config
        from reporter_tpu.matcher.api import SegmentMatcher, Trace
        from reporter_tpu.netgen.traces import synthesize_probe

        ts = short_seg_tiles
        m = SegmentMatcher(ts, Config(matcher_backend="jax"))
        p = synthesize_probe(ts, seed=3, num_points=50, gps_sigma=3.0)
        normal = Trace(uuid="n", xy=p.xy.astype(np.float32), times=p.times)

        # same geometry, but prepend a far-away point to blow the span past
        # +/-8.19km from the trace origin (forces the f32 fallback for the
        # whole slice)
        far = np.concatenate([[p.xy[0] + 9000.0], p.xy]).astype(np.float32)
        times = np.concatenate([[p.times[0] - 1000.0], p.times])
        spanning = Trace(uuid="s", xy=far, times=times)

        r_norm = m.match_many([normal])[0]
        r_both = m.match_many([spanning, normal])
        ids_solo = [r.segment_id for r in r_norm]
        ids_in_batch = [r.segment_id for r in r_both[1]]
        assert ids_solo == ids_in_batch
        # the spanning trace's tail (the real geometry) still matches
        assert [r.segment_id for r in r_both[0] if r.segment_id >= 0]


class TestDeltaInfeed:
    def test_q8_bit_identical_to_q16_and_dispatch(self, short_seg_tiles):
        """The i8-delta infeed must reconstruct the i16 absolutes exactly
        (integer cumsum of integer diffs), so the wire outputs are
        bit-identical; a trace with a >31.75 m step must fall back to
        i16 and still decode the same records."""
        import jax.numpy as jnp
        import numpy as np

        from reporter_tpu.config import Config, MatcherParams
        from reporter_tpu.matcher.api import SegmentMatcher, Trace
        from reporter_tpu.netgen.traces import synthesize_probe
        from reporter_tpu.ops.match import (OFFSET_QUANTUM,
                                            match_batch_wire_q,
                                            match_batch_wire_q8)

        ts = short_seg_tiles
        tab = ts.device_tables()
        params = MatcherParams()
        probes = [synthesize_probe(ts, seed=s, num_points=40,
                                   gps_sigma=3.0) for s in (1, 2, 3)]
        B, T = len(probes), 40
        pts = np.stack([p.xy for p in probes]).astype(np.float32)
        lens = np.full(B, T, np.int32)
        origins = pts[:, 0, :].copy()
        dqi = np.round((pts - origins[:, None, :])
                       / OFFSET_QUANTUM).astype(np.int32)
        d8 = np.diff(dqi, axis=1, prepend=dqi[:, :1] * 0)
        assert np.abs(d8).max() < 128     # 1 Hz fleet steps fit i8
        w16 = np.asarray(match_batch_wire_q(
            jnp.asarray(dqi.astype(np.int16)), jnp.asarray(origins),
            jnp.asarray(lens), tab, ts.meta, params))
        w8 = np.asarray(match_batch_wire_q8(
            jnp.asarray(d8.astype(np.int8)), jnp.asarray(origins),
            jnp.asarray(lens), tab, ts.meta, params))
        np.testing.assert_array_equal(w16, w8)

        # dispatch: a 50 m jump mid-trace overflows i8 — the matcher must
        # still produce the same records as matching the jumpy trace alone
        m = SegmentMatcher(ts, Config(matcher_backend="jax"))
        jump = pts[0].copy()
        jump[20:] += 50.0
        tj = Trace(uuid="j", xy=jump, times=probes[0].times)
        solo = [r.segment_id for r in m.match_many([tj])[0]]
        t_norm = Trace(uuid="n", xy=pts[1], times=probes[1].times)
        both = m.match_many([tj, t_norm])
        assert [r.segment_id for r in both[0]] == solo


class TestPackedU32Wire:
    def test_u32_wire_matches_3lane_on_big_metro(self):
        """Metros past the compact-u16 range: the packed-u32 single-lane
        wire must unpack to EXACTLY the 3-lane result (the offset
        quantum stays 0.25 m whenever the bit budget allows, which it
        does for every synthetic tile) at 2/3 the bytes."""
        import jax.numpy as jnp

        from reporter_tpu.config import MatcherParams
        from reporter_tpu.netgen.synthetic import generate_city
        from reporter_tpu.netgen.traces import synthesize_fleet
        from reporter_tpu.ops.match import (OFFSET_QUANTUM,
                                            match_batch_wire, unpack_wire,
                                            wire_spec)
        from reporter_tpu.tiles.compiler import compile_network

        ts = compile_network(generate_city("big", nx=78, ny=78, seed=9))
        assert ts.num_edges > 16384      # 3-lane territory
        spec = wire_spec(ts.num_edges, float(ts.edge_len.max()))
        assert spec is not None and spec[1] == OFFSET_QUANTUM

        params = MatcherParams()
        tab = ts.device_tables()
        fleet = synthesize_fleet(ts, 6, num_points=60, seed=4)
        pts = np.stack([p.xy for p in fleet]).astype(np.float32)
        lens = np.full(len(fleet), 60, np.int32)
        w3 = np.asarray(match_batch_wire(
            jnp.asarray(pts), jnp.asarray(lens), tab, ts.meta, params))
        w1 = np.asarray(match_batch_wire(
            jnp.asarray(pts), jnp.asarray(lens), tab, ts.meta, params,
            spec=spec))
        assert w3.dtype == np.uint16 and w3.shape[1] == 3
        assert w1.dtype == np.uint32 and w1.shape[1] == 1
        assert w1.nbytes * 3 == w3.nbytes * 2
        e3, o3, s3 = unpack_wire(w3)
        e1, o1, s1 = unpack_wire(w1, spec)
        np.testing.assert_array_equal(e3, e1)
        np.testing.assert_array_equal(o3, o1)
        np.testing.assert_array_equal(s3, s1)

    def test_u32_wire_without_spec_is_actionable(self):
        """A u32 wire can't be unpacked without the spec it was packed
        with — misuse must name wire_spec, not die on NoneType unpack."""
        from reporter_tpu.ops.match import unpack_wire

        wire = np.zeros((2, 1, 8), np.uint32)
        with pytest.raises(ValueError, match="wire_spec"):
            unpack_wire(wire)

    def test_wire_spec_boundaries(self):
        from reporter_tpu.ops.match import wire_spec

        assert wire_spec(5000, 500.0) is None          # compact handles it
        assert wire_spec(60000, 2200.0) is not None    # organic-scale
        ob, q = wire_spec(500000, 500.0)               # xl-scale: 19-bit id
        assert q == 0.25 and ob == 11
        assert wire_spec(500000, 5000.0) is None       # q would be 2.4 m


class TestMatchTopK:
    def test_topk_best_matches_primary(self, short_seg_tiles):
        import numpy as np

        from reporter_tpu.config import Config
        from reporter_tpu.matcher.api import SegmentMatcher, Trace
        from reporter_tpu.netgen.traces import synthesize_probe

        ts = short_seg_tiles
        m = SegmentMatcher(ts, Config(matcher_backend="jax"))
        p = synthesize_probe(ts, seed=15, num_points=50, gps_sigma=3.0)
        tr = Trace(uuid="k", xy=p.xy.astype(np.float32), times=p.times)

        ranked = m.match_topk(tr)
        assert ranked, "no valid alternates"
        scores = [s for s, _ in ranked]
        assert scores == sorted(scores)
        best = {mp.edge for mp in ranked[0][1] if mp.edge >= 0}
        primary = {mp.edge for mp in m.matched_points(tr) if mp.edge >= 0}
        # primary decode adds interpolation fill and 0.25m offset wire
        # quantization; topk reports raw lattice choices — the best
        # alternate's edges must all appear in the primary decode
        assert best <= primary

    def test_match_topk_rejects_over_bucket_traces(self, short_seg_tiles):
        """Ranked alternates do not compose across chunks, so traces past
        the max bucket are an explicit error, not a silent truncation
        (VERDICT r2 weak 4)."""
        from reporter_tpu.config import Config
        from reporter_tpu.matcher.api import _BUCKETS, SegmentMatcher, Trace

        m = SegmentMatcher(short_seg_tiles, Config(matcher_backend="jax"))
        n = _BUCKETS[-1] + 1
        tr = Trace(uuid="long", xy=np.zeros((n, 2), np.float32),
                   times=np.arange(n, dtype=np.float64))
        with pytest.raises(ValueError, match="match_topk"):
            m.match_topk(tr)


class TestQueueLength:
    """Dwell-at-the-stop-line queue model (reference schema queue_length)."""

    @staticmethod
    def _profile_probe(ts, path, speeds_and_spans, uuid, sigma=0.5):
        """Sample a drive whose speed varies along the path.

        speeds_and_spans: list of (speed m/s, span meters) phases; samples at
        dt=1s with small GPS noise so the matched offsets track ground truth.
        """
        from reporter_tpu.geometry import xy_to_lonlat
        from reporter_tpu.netgen.traces import _EdgeShapeCache

        cum = np.concatenate(
            [[0.0], np.cumsum(ts.edge_len[path].astype(np.float64))])
        cache = _EdgeShapeCache(ts)
        rng = np.random.default_rng(99)
        d, dists = 0.0, [0.0]
        for speed, span in speeds_and_spans:
            end = min(d + span, float(cum[-1]) - 1e-3)
            while d < end:
                d = min(d + speed, end)
                dists.append(d)
        xs = []
        for s in dists:
            k = int(np.searchsorted(cum, s, side="right") - 1)
            k = max(0, min(k, len(path) - 1))
            xs.append(cache.point_at(path[k], s - cum[k]))
        xy = np.asarray(xs, np.float64) + rng.normal(0.0, sigma, (len(xs), 2))
        times = np.arange(len(dists), dtype=np.float64)
        lonlat = xy_to_lonlat(xy, np.asarray(ts.meta.origin_lonlat))
        return {"uuid": uuid,
                "trace": [{"lat": float(la), "lon": float(lo),
                           "time": float(t)}
                          for (lo, la), t in zip(lonlat, times)]}

    @staticmethod
    def _tail_boundary(ts, path):
        """(d_tail, segment_id, seg_len) of the first OSMLR segment whose
        tail falls mid-path (far enough in for a fast approach phase)."""
        cum = np.concatenate(
            [[0.0], np.cumsum(ts.edge_len[path].astype(np.float64))])
        for k, e in enumerate(path):
            row = int(ts.edge_osmlr[e])
            if row < 0:
                continue
            at_tail = (float(ts.edge_osmlr_off[e]) + float(ts.edge_len[e])
                       >= float(ts.osmlr_len[row]) - 1.0)
            if at_tail and 250.0 <= cum[k + 1] <= cum[-1] - 120.0:
                return float(cum[k + 1]), int(ts.osmlr_id[row]), float(
                    ts.osmlr_len[row])
        return None

    def test_stop_and_go_reports_queue(self, matchers, short_seg_tiles):
        from reporter_tpu.netgen.traces import random_walk_edges

        ts = short_seg_tiles
        mj, mc = matchers
        rng = np.random.default_rng(31)
        for attempt in range(20):
            path = random_walk_edges(ts, rng, 900.0)
            hit = self._tail_boundary(ts, path)
            if hit:
                break
        assert hit, "no usable mid-path segment tail found"
        d_tail, seg_id, seg_len = hit
        crawl = 80.0

        # Fast approach, crawl (1 m/s < QUEUE_SPEED) through the last 80 m
        # before the stop line and a little past it, then fast again.
        jam = self._profile_probe(ts, path, [
            (12.0, d_tail - crawl), (1.0, crawl + 10.0), (12.0, 1e9)], "jam")
        free = self._profile_probe(ts, path, [(12.0, 1e9)], "free")

        expect = min(crawl, seg_len)
        for m in (mj, mc):
            segs = {s["segment_id"]: s for s in m.match(jam)["segments"]}
            assert seg_id in segs, "jam drive must report the tail segment"
            q = segs[seg_id]["queue_length"]
            assert 0.5 * expect <= q <= 1.5 * expect + 5.0, (
                f"queue {q:.1f}m vs expected ~{expect:.0f}m")
            free_segs = {s["segment_id"]: s
                         for s in m.match(free)["segments"]}
            assert free_segs[seg_id]["queue_length"] == 0.0

    def test_queue_clamped_to_segment(self, matchers, short_seg_tiles):
        """A crawl longer than the segment cannot report more queue than
        the segment has length."""
        from reporter_tpu.netgen.traces import random_walk_edges

        ts = short_seg_tiles
        mj, _ = matchers
        rng = np.random.default_rng(77)
        for attempt in range(20):
            path = random_walk_edges(ts, rng, 900.0)
            hit = self._tail_boundary(ts, path)
            if hit:
                break
        assert hit
        d_tail, seg_id, seg_len = hit
        jam = self._profile_probe(ts, path, [(1.5, d_tail + 10.0),
                                             (12.0, 1e9)], "alljam")
        segs = {s["segment_id"]: s for s in mj.match(jam)["segments"]}
        assert seg_id in segs
        assert segs[seg_id]["queue_length"] <= seg_len + 1e-6


class TestAccuracy:
    """Per-point GPS accuracy (the reference schema's optional field):
    emission sigma = max(sigma_z, accuracy), device path via distance
    scaling (ops/match.match_traces), CPU oracle via per-point sigma."""

    def test_accuracy_none_is_noop(self, matchers, short_seg_tiles):
        mj, _ = matchers
        p = synthesize_probe(short_seg_tiles, seed=12, num_points=50)
        base = p.to_report_json()
        with_acc = {"uuid": base["uuid"], "trace": [
            dict(pt, accuracy=1.0) for pt in base["trace"]]}
        a = [s["segment_id"] for s in mj.match(base)["segments"]]
        # accuracy <= sigma_z clamps to sigma_z -> identical decode
        b = [s["segment_id"] for s in mj.match(with_acc)["segments"]]
        assert a == b

    def test_bad_accuracy_point_downweighted(self, short_seg_tiles):
        """Drag one mid-trace point hard sideways. With honest (large)
        reported accuracy the match must ride through on route
        continuity; the same trace claiming pinpoint accuracy is allowed
        to deviate. Checked on both backends."""
        from reporter_tpu.geometry import xy_to_lonlat

        ts = short_seg_tiles
        p = synthesize_probe(ts, seed=22, num_points=50, gps_sigma=1.0)
        xy = p.xy.copy()
        k = 25
        # ~8-sigma outlier, still inside search_radius (50 m) of the true
        # edge: the honest-accuracy decode has the right candidate and
        # must let route continuity outvote the dragged emission
        xy[k] += np.float32(30.0 / np.sqrt(2.0))
        lonlat = xy_to_lonlat(xy.astype(np.float64),
                              np.asarray(ts.meta.origin_lonlat))

        def payload(uuid, acc_k):
            trace = []
            for i, ((lo, la), t) in enumerate(zip(lonlat, p.times)):
                pt = {"lat": float(la), "lon": float(lo), "time": float(t)}
                if i == k:
                    pt["accuracy"] = acc_k
                trace.append(pt)
            return {"uuid": uuid, "trace": trace}

        clean_ids = None
        for backend in ("jax", "reference_cpu"):
            m = SegmentMatcher(short_seg_tiles, Config(matcher_backend=backend))
            honest = m.match(payload(f"h-{backend}", 100.0))["segments"]
            clean = m.match(p.to_report_json())["segments"]
            # with the outlier down-weighted ~25x, the matched segment
            # sequence must equal the clean trace's
            assert ([s["segment_id"] for s in honest]
                    == [s["segment_id"] for s in clean]), backend
            # both backends must agree on the clean sequence too
            if clean_ids is None:
                clean_ids = [s["segment_id"] for s in clean]
            else:
                assert clean_ids == [s["segment_id"] for s in clean]
            # pinpoint claimed accuracy (<= sigma_z) clamps to sigma_z:
            # identical to not reporting accuracy at all, outlier included
            pin = m.match(payload(f"p-{backend}", 1.0))["segments"]
            no_acc = {"uuid": f"n-{backend}", "trace": [
                {k: v for k, v in pt.items() if k != "accuracy"}
                for pt in payload("x", 1.0)["trace"]]}
            bare = m.match(no_acc)["segments"]
            assert ([s["segment_id"] for s in pin]
                    == [s["segment_id"] for s in bare]), backend

    def test_match_topk_honors_accuracy(self, matchers, short_seg_tiles):
        """The ranked-paths surface must apply the same accuracy
        down-weighting as the primary decode: rank 0 on the dragged trace
        with honest accuracy follows the clean route."""
        from reporter_tpu.geometry import xy_to_lonlat  # noqa: F401

        ts = short_seg_tiles
        mj, _ = matchers
        p = synthesize_probe(ts, seed=22, num_points=50, gps_sigma=1.0)
        xy = p.xy.copy()
        k = 25
        xy[k] += np.float32(30.0 / np.sqrt(2.0))
        acc = np.zeros(len(xy), np.float32)
        acc[k] = 100.0
        dragged = Trace(uuid="d", xy=xy.astype(np.float32), times=p.times,
                        accuracy=acc)
        clean = Trace(uuid="c", xy=p.xy.astype(np.float32), times=p.times)
        def route(pts, skip):
            # consecutive-deduped edge sequence, ignoring unmatched slots
            # and the dragged index (its interpolation activity differs
            # between the two traces)
            seq = []
            for i, mp in enumerate(pts):
                if i == skip or mp.edge < 0:
                    continue
                if not seq or seq[-1] != mp.edge:
                    seq.append(mp.edge)
            return seq

        for exact in (False, True):
            best = mj.match_topk(dragged, exact=exact)[0][1]
            want = mj.match_topk(clean, exact=exact)[0][1]
            assert route(best, k) == route(want, k), exact


class TestSweepEnvOverrides:
    """Deployment env overrides (RTPU_SWEEP_AUTOTUNE, RTPU_DISPATCH_*)
    apply at SegmentMatcher construction and are mirrored back into
    self.config — the attributability contract: the config a matcher
    reports is the one that serves."""

    def test_matcher_mirrors_override_into_config(self, tiny_tiles,
                                                  monkeypatch):
        monkeypatch.setenv("RTPU_DISPATCH_TIMEOUT_S", "7.5")
        m = SegmentMatcher(tiny_tiles, Config(matcher_backend="jax"))
        assert m.params.dispatch_timeout_s == 7.5
        assert m.config.matcher.dispatch_timeout_s == 7.5  # no stale view
        # host-only watchdog knobs never reach the wire statics (r9)
        assert m._wire.params.dispatch_timeout_s == 0.0
        monkeypatch.delenv("RTPU_DISPATCH_TIMEOUT_S")
        m2 = SegmentMatcher(tiny_tiles, Config(matcher_backend="jax"))
        assert m2.params.dispatch_timeout_s == 0.0
