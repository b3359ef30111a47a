"""Service: wall time of ``match_many`` per scheduled batch (the
registry's ``batch_match_seconds``), over the window's batches."""

from benchmark.layer_metrics.parse_ms_per_request import mean_ms


def read(inputs):
    return mean_ms(inputs, "batch_match_seconds")
