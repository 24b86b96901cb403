"""One of the runner's counters, taken over the window (``ctx['counters']``)."""


def read(ctx, params):
    value = ctx["counters"].get(params["key"])
    return None if value is None else float(value) * params.get("scale", 1.0)
