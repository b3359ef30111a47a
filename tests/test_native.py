"""Native C++ builder parity vs the pure-Python reference builders.

The analog of the reference's native/C++ test coverage living in Valhalla
(SURVEY.md §4): here the contract is exact output equality, so the Python
builders remain the executable spec.
"""

import os

import numpy as np
import pytest

from reporter_tpu.config import CompilerParams
from reporter_tpu.netgen.synthetic import generate_city
from reporter_tpu.tiles.compiler import _build_grid, compile_network
from reporter_tpu.tiles.native import build_grid_native, build_reach_native
from reporter_tpu.tiles.reach import build_reach_tables

pytestmark = pytest.mark.skipif(
    __import__("reporter_tpu.native", fromlist=["lib"]).lib is None,
    reason="native library unavailable (no g++?)")


@pytest.fixture(scope="module")
def city_tiles():
    # Python builders for ground truth
    return compile_network(
        generate_city("tiny", seed=11),
        CompilerParams(reach_radius=500.0, use_native=False))


class TestReachParity:
    @pytest.mark.parametrize("radius,max_targets", [
        (300.0, 16), (500.0, 32), (800.0, 8)])
    def test_exact_equality(self, city_tiles, radius, max_targets):
        ts = city_tiles
        want = build_reach_tables(ts.node_out, ts.edge_src, ts.edge_dst,
                                  ts.edge_len, radius, max_targets)
        got = build_reach_native(ts.node_out, ts.edge_src, ts.edge_dst,
                                 ts.edge_len, radius, max_targets)
        assert got is not None
        np.testing.assert_array_equal(got[0], want[0])     # reach_to
        np.testing.assert_array_equal(got[1], want[1])     # reach_dist (f32)
        np.testing.assert_array_equal(got[2], want[2])     # reach_next
        assert got[3] == want[3]                           # truncated count

    def test_single_thread_deterministic(self, city_tiles, monkeypatch):
        ts = city_tiles
        a = build_reach_native(ts.node_out, ts.edge_src, ts.edge_dst,
                               ts.edge_len, 500.0, 32)
        monkeypatch.setenv("REPORTER_TPU_NATIVE_THREADS", "1")
        b = build_reach_native(ts.node_out, ts.edge_src, ts.edge_dst,
                               ts.edge_len, 500.0, 32)
        for x, y in zip(a[:3], b[:3]):
            np.testing.assert_array_equal(x, y)


class TestGridParity:
    def test_exact_equality(self, city_tiles):
        ts = city_tiles
        for cell, cap, radius in ((64.0, 64, 50.0), (100.0, 32, 25.0),
                                  (48.0, 8, 0.0)):
            want_grid, dims, lo, want_ovf = _build_grid(
                ts.seg_a, ts.seg_b, cell, cap, radius, use_native=False)
            got = build_grid_native(
                np.minimum(ts.seg_a, ts.seg_b) - radius,
                np.maximum(ts.seg_a, ts.seg_b) + radius,
                lo, cell, dims[0], dims[1], cap)
            assert got is not None
            np.testing.assert_array_equal(got[0], want_grid)
            assert got[1] == want_ovf


class TestCompilerIntegration:
    def test_native_and_python_tilesets_agree(self):
        net = generate_city("tiny", seed=12)
        py = compile_network(net, CompilerParams(use_native=False))
        cc = compile_network(net, CompilerParams(use_native=True))
        np.testing.assert_array_equal(py.reach_to, cc.reach_to)
        np.testing.assert_array_equal(py.reach_dist, cc.reach_dist)
        np.testing.assert_array_equal(py.reach_next, cc.reach_next)
        np.testing.assert_array_equal(py.grid, cc.grid)


def test_min_record_span_constants_agree():
    """MIN_RECORD_SPAN must equal the wire quantum (its rationale) and the
    C++ walker's kMinSpan, or boundary-sliver divergence returns."""
    import re

    from reporter_tpu.matcher.segments import MIN_RECORD_SPAN
    from reporter_tpu.ops.match import OFFSET_QUANTUM

    assert MIN_RECORD_SPAN == OFFSET_QUANTUM
    src = os.path.join(os.path.dirname(__file__), "..", "reporter_tpu",
                       "native", "walker.cc")
    with open(src) as f:
        m = re.search(r"kMinSpan\s*=\s*([0-9.]+)", f.read())
    assert m, "kMinSpan not found in walker.cc"
    assert float(m.group(1)) == MIN_RECORD_SPAN


def test_queue_speed_constants_agree():
    """The queue dwell threshold must match across walkers or queue_length
    diverges between the native and Python paths."""
    import re

    from reporter_tpu.matcher.segments import QUEUE_SPEED, QUEUE_WINDOW

    src = os.path.join(os.path.dirname(__file__), "..", "reporter_tpu",
                       "native", "walker.cc")
    with open(src) as f:
        text = f.read()
    m = re.search(r"kQueueSpeed\s*=\s*([0-9.]+)", text)
    assert m, "kQueueSpeed not found in walker.cc"
    assert float(m.group(1)) == QUEUE_SPEED
    m = re.search(r"kQueueWindow\s*=\s*([0-9.]+)", text)
    assert m, "kQueueWindow not found in walker.cc"
    assert float(m.group(1)) == QUEUE_WINDOW


class TestMatchBatchColumns:
    """The columnar match_many result (VERDICT r2 item 1): MatchBatch's
    flat columns must agree exactly with the per-trace record objects it
    lazily materializes, across multi-slice merges."""

    def test_columns_agree_with_materialized_records(self, tiny_tiles):
        from reporter_tpu.config import Config, MatcherParams
        from reporter_tpu.matcher.api import MatchBatch, SegmentMatcher, Trace
        from reporter_tpu.netgen.traces import synthesize_fleet

        ts = tiny_tiles
        # max_device_batch=8 forces several slices → the merge path
        cfg = Config(matcher_backend="jax",
                     matcher=MatcherParams(max_device_batch=8))
        m = SegmentMatcher(ts, cfg)
        if m._native_walker is None:
            pytest.skip("native toolchain unavailable")
        fleet = synthesize_fleet(ts, 30, num_points=50, seed=33)
        traces = [Trace(uuid=p.uuid, xy=p.xy.astype("float32"),
                        times=p.times) for p in fleet]
        batch = m.match_many(traces)
        assert isinstance(batch, MatchBatch)
        cols = batch.columns
        # trace column is sorted; ranges are contiguous per trace
        assert np.all(np.diff(cols.trace) >= 0)
        assert cols.way_off[0] == 0
        assert cols.way_off[-1] == len(cols.way_ids)
        # flat columns == materialized objects, row for row
        r = 0
        for i in range(len(batch)):
            for rec in batch[i]:
                assert cols.trace[r] == i
                assert cols.segment_id[r] == rec.segment_id
                assert cols.start_time[r] == rec.start_time
                assert cols.end_time[r] == rec.end_time
                assert cols.length[r] == rec.length
                assert cols.queue_length[r] == rec.queue_length
                assert bool(cols.internal[r]) == rec.internal
                lo, hi = cols.way_off[r], cols.way_off[r + 1]
                assert cols.way_ids[lo:hi].tolist() == rec.way_ids
                r += 1
        assert r == cols.n_records

    @staticmethod
    def _one_and_sliced(ts, traces, max_device_batch):
        """(one-slice result, result under ``max_device_batch``, the
        sliced matcher)."""
        from reporter_tpu.config import Config, MatcherParams
        from reporter_tpu.matcher.api import SegmentMatcher

        one = SegmentMatcher(ts, Config(matcher_backend="jax"))
        if one._native_walker is None:
            pytest.skip("native toolchain unavailable")
        many = SegmentMatcher(ts, Config(
            matcher_backend="jax",
            matcher=MatcherParams(max_device_batch=max_device_batch)))
        return one.match_many(traces), many.match_many(traces), many

    def test_slicing_matches_single_slice_run(self, tiny_tiles):
        from reporter_tpu.matcher.api import Trace
        from reporter_tpu.netgen.traces import synthesize_fleet

        ts = tiny_tiles
        fleet = synthesize_fleet(ts, 20, num_points=40, seed=34)
        traces = [Trace(uuid=p.uuid, xy=p.xy.astype("float32"),
                        times=p.times) for p in fleet]
        ra, rb, _ = self._one_and_sliced(ts, traces, 4)
        for a, b in zip(ra, rb):
            assert [x.to_json() for x in a] == [x.to_json() for x in b]

    @pytest.mark.parametrize("max_device_batch", [1, 3, 8])
    def test_sliced_two_bucket_batch_is_bit_identical(self, tiny_tiles,
                                                      max_device_batch):
        """A batch over two point buckets, cut into slices of a few rows,
        gives column for column the bytes of the one-slice run: the
        merge restores trace order across slices and buckets."""
        from reporter_tpu.matcher.api import Trace
        from reporter_tpu.netgen.traces import synthesize_fleet

        ts = tiny_tiles
        fleet = (synthesize_fleet(ts, 13, num_points=40, seed=35)
                 + synthesize_fleet(ts, 9, num_points=100, seed=36))
        traces = [Trace(uuid=f"{i}", xy=p.xy.astype("float32"),
                        times=p.times) for i, p in enumerate(fleet)]
        # interleave the buckets so trace order differs from plan order
        traces = traces[::2] + traces[1::2]
        ra, rb, many = self._one_and_sliced(ts, traces, max_device_batch)
        assert many.metrics.value("dispatch_slices_total") == \
            -(-13 // max_device_batch) - (-9 // max_device_batch)
        a, b = ra.columns, rb.columns
        assert a.n_records > 0
        for field in a._fields:
            x, y = getattr(a, field), getattr(b, field)
            assert x.dtype == y.dtype, field
            np.testing.assert_array_equal(x, y, err_msg=field)


class TestNativeWalker:
    """walker.cc vs the Python segment walk — exact record parity."""

    def test_walker_matches_python_walk(self, tiny_tiles):
        import numpy as np

        from reporter_tpu.config import Config
        from reporter_tpu.matcher.api import SegmentMatcher, Trace
        from reporter_tpu.matcher.native_walk import make_native_walker
        from reporter_tpu.netgen.traces import synthesize_fleet

        ts = tiny_tiles
        walker = make_native_walker(ts)
        if walker is None:
            import pytest
            pytest.skip("native toolchain unavailable")

        fleet = synthesize_fleet(ts, 12, num_points=70, seed=21)
        traces = [Trace(uuid=p.uuid, xy=p.xy.astype("float32"), times=p.times)
                  for p in fleet]
        # teleport a jump into a few traces to force chain breaks
        for tr in traces[::4]:
            tr.xy[len(tr.xy) // 2:] += np.float32(2500.0)
        # stretch some traces' timestamps so they crawl below QUEUE_SPEED:
        # the parity sweep must cover NONZERO queue_length too, or the two
        # queue implementations could diverge unnoticed.
        for tr in traces[1::4]:
            tr.times = tr.times * 25.0

        m = SegmentMatcher(ts, Config(matcher_backend="jax"))
        native = m.match_many(traces)              # native walker path
        m._native_walker = None
        python = m.match_many(traces)              # python walk fallback

        assert len(native) == len(python)
        assert any(r.queue_length > 0 for recs in python for r in recs), \
            "sweep exercised no nonzero queue — queue parity untested"
        for b, (rn, rp) in enumerate(zip(native, python)):
            assert len(rn) == len(rp), f"trace {b}: {len(rn)} vs {len(rp)}"
            for a, c in zip(rn, rp):
                assert a.segment_id == c.segment_id, f"trace {b}"
                assert a.way_ids == c.way_ids, f"trace {b}"
                assert a.internal == c.internal, f"trace {b}"
                np.testing.assert_allclose(
                    [a.start_time, a.end_time, a.length, a.queue_length],
                    [c.start_time, c.end_time, c.length, c.queue_length],
                    rtol=1e-9, atol=1e-9, err_msg=f"trace {b}")
