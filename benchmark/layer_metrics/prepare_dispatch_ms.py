"""Matcher host: seconds of host prepare plus async dispatch per
``match_many`` call (the registry's ``decode_seconds`` stage), over the
window's calls."""


def read(inputs):
    c = inputs["window"].get("counters", {})
    n = c.get("decode_seconds_count", 0.0)
    return 1000.0 * c["decode_seconds_total"] / n if n else None
