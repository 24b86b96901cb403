"""Exposed collective time: time in all-gather / all-reduce / reduce-scatter /
collective-permute operations during which no other operation runs on that
device, over the traced window, on the worst device. Nothing to read on one
chip."""


def read(ctx, params):
    trace = ctx.get("trace")
    if trace is None or len(trace["devices"]) < 2:
        return None
    return 100.0 * trace["exposed_collective_s_worst"] / trace["window_s"]
