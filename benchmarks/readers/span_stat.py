"""A statistic of one of the benchmark's spans inside the window: ``mean``,
``p50``, ``p95`` (ms) or ``share`` (% of the window's wall time)."""
import statistics

from benchmarks.traffic.generate import percentile


def read(ctx, params):
    t0, t1 = ctx["window"]
    ms = ctx["spans"].durations_ms(params["span"], t0, t1)
    if not ms:
        return None
    stat = params["stat"]
    if stat == "mean":
        return statistics.fmean(ms)
    if stat == "share":
        return 100.0 * sum(ms) / 1e3 / (t1 - t0)
    if stat.startswith("p"):
        return percentile(ms, int(stat[1:]) / 100.0)
    raise ValueError(f"unknown stat {stat!r}")
