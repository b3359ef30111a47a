"""Kernels: device time of the candidate-sweep Pallas kernel per
``match_many`` call, from the trace, averaged over the cell's chips."""


def read(inputs):
    t = inputs["trace"]
    if not t["calls"] or not t["sweep_s"]:
        return None
    return 1000.0 * t["sweep_s"] / t["calls"]
