"""Device: share of the traced window in which no op ran on the chip,
averaged over the cell's chips. One reader for each cell's split of the
metric (``device_idle_pct.backfill``, ``device_idle_pct.report``)."""


def read(inputs):
    t = inputs["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
