"""Service: the request thread's parse (body read, JSON decode,
validation; the registry's ``http_parse_seconds``) per request, over the
window's requests."""


def mean_ms(inputs, name: str):
    """Mean milliseconds of the registry series ``name`` over the window,
    from its ``_total`` and ``_count`` counters; None where the program
    records no such series."""
    w = inputs["window"]
    if "registry_after" not in w:
        return None
    new, old = w["registry_after"]["counters"], w["registry_before"]["counters"]
    n = new.get(name + "_count", 0.0) - old.get(name + "_count", 0.0)
    s = new.get(name + "_total", 0.0) - old.get(name + "_total", 0.0)
    return 1000.0 * s / n if n else None


def read(inputs):
    return mean_ms(inputs, "http_parse_seconds")
