"""Device idle share: 1 - union of device-op intervals / traced window, on the
worst device."""


def read(ctx, params):
    trace = ctx.get("trace")
    return None if trace is None else 100.0 * trace["idle_share_worst"]
