"""Dispatch granularity: how many rows one device dispatch carries.

``MatcherParams.max_device_batch`` counts rows per data-parallel device;
``SegmentMatcher.slice_rows`` multiplies it by the devices the wire shards
rows over, up to the largest trace-count rung, and ``_plan`` cuts every
bucket group into slices of that many rows. A group over one slice is
dispatched in several slices, so the host walk of one overlaps the
device's work on the next; a group at or under one slice (every
``/report`` batch) is planned exactly as a 4,096-row slice would plan it.
"""

import numpy as np
import pytest

from reporter_tpu.config import Config, MatcherParams
from reporter_tpu.matcher.api import (_MAX_DISPATCH_ROWS, SegmentMatcher,
                                      Trace, _bucket_len)
from reporter_tpu.netgen.traces import synthesize_fleet
from reporter_tpu.service.scheduler import _TRACE_RUNGS


@pytest.fixture(scope="module")
def matcher(tiny_tiles):
    return SegmentMatcher(tiny_tiles, Config(matcher_backend="jax"))


@pytest.fixture(scope="module")
def wide_matcher(tiny_tiles):
    """The same matcher with the former whole-call slice of 4,096 rows."""
    return SegmentMatcher(tiny_tiles, Config(
        matcher_backend="jax", matcher=MatcherParams(max_device_batch=4096)))


@pytest.fixture(scope="module")
def fleet(tiny_tiles):
    return synthesize_fleet(tiny_tiles, 16, num_points=128, seed=71)


def _traces(fleet, n: int, points: int) -> list:
    """n traces of ``points`` points, cycling the fleet's vehicles."""
    return [Trace(uuid=f"t{i}", xy=fleet[i % len(fleet)].xy[:points]
                  .astype(np.float32),
                  times=fleet[i % len(fleet)].times[:points])
            for i in range(n)]


def test_default_is_per_device_2048(matcher):
    assert MatcherParams().max_device_batch == 2048
    assert matcher.wire_mesh is None
    assert matcher.slice_rows == 2048
    # a dispatch never leaves the scheduler's compiled trace-count rungs
    assert _MAX_DISPATCH_ROWS == _TRACE_RUNGS[-1]


@pytest.mark.parametrize("n,want", [(4096, [2048] * 2), (2048, [2048]),
                                    (2049, [2048, 1]), (300, [300])])
def test_one_device_cuts_a_bucket_group_into_2048_row_slices(
        matcher, fleet, n, want):
    work, sliced = matcher.plan_submit(_traces(fleet, n, 100))
    assert [b for b, _ in sliced] == [128] * len(want)
    assert [len(ws) for _, ws in sliced] == want
    # every work item is dispatched once
    assert sorted(w for _, ws in sliced for w in ws) == list(range(n))


@pytest.mark.parametrize("ndev", [2, 4, 8])
def test_mesh_dispatches_at_most_4096_rows(tiny_tiles, fleet, ndev):
    """On a mesh a dispatch carries 2,048 rows per device up to 4,096 in
    all: a 2x2 host keeps four 4,096-row dispatches a 16,384-trace call."""
    from reporter_tpu.parallel.mesh import make_mesh

    m = SegmentMatcher(tiny_tiles, Config(matcher_backend="jax"),
                       mesh=make_mesh(dp=ndev))
    assert m.slice_rows == 4096
    _, sliced = m.plan_submit(_traces(fleet, 16384, 100))
    assert [len(ws) for _, ws in sliced] == [4096] * 4
    small = SegmentMatcher(tiny_tiles, Config(
        matcher_backend="jax", matcher=MatcherParams(max_device_batch=512)),
        mesh=make_mesh(dp=ndev))
    assert small.slice_rows == 512 * ndev


@pytest.mark.parametrize("rung", [r for r in _TRACE_RUNGS if r <= 256])
@pytest.mark.parametrize("points", [33, 64, 65, 128])
def test_report_batches_plan_as_before(matcher, wide_matcher, fleet, rung,
                                       points):
    """Scheduler rungs up to its 256-trace cap, in the report cell's point
    buckets (64 and 128), one bucket or two: the plan is the plan of a
    4,096-row slice, one slice per bucket group."""
    traces = _traces(fleet, rung, points)
    mixed = traces[:rung // 2] + _traces(fleet, rung - rung // 2, 128)
    for batch in (traces, mixed):
        plan = matcher.plan_submit(batch)
        assert plan == wide_matcher.plan_submit(batch)
        groups = {_bucket_len(len(t.xy)) for t in batch}
        assert len(plan[1]) == len(groups)


def test_counters(tiny_tiles, fleet):
    """``dispatch_slices_total``: one per device dispatch;
    ``sliced_calls_total``: one per call that cuts a bucket group."""
    m = SegmentMatcher(tiny_tiles, Config(
        matcher_backend="jax", matcher=MatcherParams(max_device_batch=4)))
    # bucket 64: 8 traces, two slices; bucket 128: 4 traces, one slice
    batch = _traces(fleet, 8, 40) + _traces(fleet, 4, 100)
    m.match_many(batch)
    assert m.metrics.value("dispatch_slices_total") == 3
    assert m.metrics.value("sliced_calls_total") == 1
    m.match_many(batch[8:])                      # one slice: not sliced
    assert m.metrics.value("dispatch_slices_total") == 4
    assert m.metrics.value("sliced_calls_total") == 1
    text = m.metrics.render_prometheus()
    assert "rtpu_dispatch_slices_total 4.0" in text
    assert "rtpu_sliced_calls_total 1.0" in text


def test_report_batch_counts_no_sliced_call(matcher, fleet):
    before = matcher.metrics.value("sliced_calls_total")
    matcher.plan_submit(_traces(fleet, 256, 128))
    assert matcher.metrics.value("sliced_calls_total") == before
