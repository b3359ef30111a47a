"""Device program: busy time that is not the sweep kernel (emissions,
the Viterbi scan, wire pack, copies) per ``match_many`` call, averaged
over the cell's chips."""


def read(inputs):
    t = inputs["trace"]
    if not t["calls"]:
        return None
    return 1000.0 * (t["busy_s"] - t["sweep_s"]) / t["calls"]
