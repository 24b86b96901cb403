"""Peak device memory: ``memory_stats()['peak_bytes_in_use']`` after the
window, the fullest chip, in GB (1e9 bytes)."""


def read(ctx, params):
    peak = ctx.get("memory_peak_bytes")
    return None if not peak else peak / 1e9
