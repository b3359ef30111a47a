"""Service: 95th percentile of the scheduler's queue age
(``sched_queue_age_seconds``), from the registry histogram's change over
the window, interpolated within its bucket."""

from reporter_tpu.utils.metrics import HISTOGRAM_BUCKETS

NAME = "sched_queue_age_seconds"


def read(inputs):
    w = inputs["window"]
    if "registry_after" not in w:
        return None
    new = w["registry_after"]["hist"].get(NAME)
    if new is None:
        return None
    old = w["registry_before"]["hist"].get(NAME, [0] * len(new))
    counts = [a - b for a, b in zip(new, old)]
    total = sum(counts)
    if not total:
        return None
    target, seen = 0.95 * total, 0
    edges = (0.0, *HISTOGRAM_BUCKETS)
    for i, c in enumerate(counts):
        if seen + c >= target:
            if i >= len(HISTOGRAM_BUCKETS):
                return 1000.0 * HISTOGRAM_BUCKETS[-1]
            lo, hi = edges[i], edges[i + 1]
            return 1000.0 * (lo + (hi - lo) * (target - seen) / c)
        seen += c
    return None
