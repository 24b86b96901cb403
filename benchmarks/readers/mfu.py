"""Model FLOP/s utilisation on REQUIRED operations: tokens a second a chip
times the FLOPs one trained token requires (``flops.train_flops_per_token``:
causal attention once, recompute and the embedding lookup not counted) over
the chip's bf16 peak. The rate is tokens a step over the median step and data
spans, not the traced window's own rate: stopping the profiler pauses the
loop for seconds inside a traced window."""
import statistics

from benchmarks import flops


def read(ctx, params):
    t0, t1 = ctx["window"]
    steps = ctx["spans"].durations_ms("step", t0, t1)
    data = ctx["spans"].durations_ms("data", t0, t1)
    if not steps or ctx["peak"] is None:
        return None
    seconds = (statistics.median(steps) + statistics.median(data or [0.0])) / 1e3
    rate = ctx["counters"]["tokens_per_step"] / seconds / len(ctx["devices"])
    per_token = flops.train_flops_per_token(ctx["config"],
                                            ctx["traffic"]["seq_len"])
    return 100.0 * rate * per_token / ctx["peak"]["bf16_flops_per_s"]
