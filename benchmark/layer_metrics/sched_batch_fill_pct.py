"""Service: mean share of a scheduled device batch's rows that carry a
real request (``sched_batch_occupancy``), over the window's batches."""

NAME = "sched_batch_occupancy"


def read(inputs):
    w = inputs["window"]
    if "registry_after" not in w:
        return None
    new, old = w["registry_after"]["counters"], w["registry_before"]["counters"]
    n = new.get(NAME + "_count", 0.0) - old.get(NAME + "_count", 0.0)
    s = new.get(NAME + "_total", 0.0) - old.get(NAME + "_total", 0.0)
    return 100.0 * s / n if n else None
