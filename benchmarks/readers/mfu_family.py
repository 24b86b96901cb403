"""``readers/mfu.py``'s share of the bf16 peak for a family that counts its
own required FLOPs: the same rate (tokens a step over the median step and
data spans) times ``flops_<family>.train_flops_per_token(config, seq, pairs
held a token)``, the family found by the configuration's ``family``. The
routed experts' part is what the window's steps COUNTED (``counters
["pairs_held"]`` over the tokens of its steps), not its expectation: a chip
whose experts the router favours did more required work. None where there is
nothing to read (no step in the window, no peak, no such family module)."""
import statistics

from benchmarks.readers.family_work import family_flops


def read(ctx, params):
    t0, t1 = ctx["window"]
    steps = ctx["spans"].durations_ms("step", t0, t1)
    data = ctx["spans"].durations_ms("data", t0, t1)
    mod = family_flops(ctx)
    counters = ctx["counters"]
    if not steps or ctx["peak"] is None or mod is None \
            or not counters.get("steps"):
        return None
    seconds = (statistics.median(steps) + statistics.median(data or [0.0])) / 1e3
    rate = counters["tokens_per_step"] / seconds / len(ctx["devices"])
    held = counters.get("pairs_held")
    per_token = mod.train_flops_per_token(
        ctx["config"], ctx["traffic"]["seq_len"],
        None if held is None else
        held / (counters["steps"] * counters["tokens_per_step"]))
    return 100.0 * rate * per_token / ctx["peak"]["bf16_flops_per_s"]
