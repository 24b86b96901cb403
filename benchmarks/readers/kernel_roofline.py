"""A Pallas kernel family's share of its roofline in the traced window: the
least time the chip could take for the work the algorithm REQUIRES
(``flops.py``: the larger of FLOPs over peak FLOP/s and bytes over peak
bytes/s), over the summed device time of the kernel's events.

The program's ``pallas_call``s carry no ``name=`` (PERF.md, Open questions),
so the trace prints every one of them as a ``tpu_custom_call`` and nothing
tells forward from backward by name. The reader therefore takes the family as
a whole: every ``tpu_custom_call`` event of the window is the kernel's time,
and the required work is that of the whole steps (training) or of the decode
steps (serving) the window holds. A forward pass that remat runs twice is
required once, so it lowers the share, as it should.
"""
import json

from benchmarks import flops, trace_reduce

PATTERN = 'custom_call_target="tpu_custom_call"'


def read(ctx, params):
    trace = ctx.get("trace")
    if trace is None or ctx.get("peak") is None:
        return None
    lo, hi = trace["lo_ns"], trace["hi_ns"]
    per_device = []
    for ops in trace["device_ops"].values():
        events = trace_reduce.kernel_events(
            ops, params.get("pattern", PATTERN), lo, hi)
        per_device.append(sum(b - a for _, a, b in events) / 1e9)
    kernel_s = sum(per_device) / len(per_device)
    if kernel_s <= 0:
        return None
    work = WORK[params["kernel"]](ctx, trace, len(per_device))
    if work is None:
        return None
    least_s, bound = work
    print(json.dumps({"roofline": {
        "kernel": params["kernel"], "bound": bound, "least_s": least_s,
        "kernel_s": kernel_s}}), flush=True)
    return 100.0 * least_s / kernel_s


def _flash_attention(ctx, trace, n_devices):
    """Forward once and backward once per layer and whole step in the window;
    each device takes its share of the batch's rows."""
    cfg, traffic = ctx["config"], ctx["traffic"]
    steps = sum(1 for name, a, b in trace["host_spans"] if name == "step"
                and a >= trace["lo_ns"] and b <= trace["hi_ns"])
    if not steps:
        return None
    batch, seq = traffic["global_batch"], traffic["seq_len"]
    total, bounds = 0.0, set()
    for work in (flops.flash_fwd(cfg, batch, seq), flops.flash_bwd(cfg, batch, seq)):
        t, bound = flops.least_time(work, ctx["peak"],
                                    steps * cfg["num_hidden_layers"] / n_devices)
        total += t
        bounds.add(bound)
    return total, "+".join(sorted(bounds))


def _paged_attend(ctx, trace, n_devices):
    """The decode steps' attention over the live contexts the runner counted
    (``counters["decode_context"]``: one wall-clock stamped row a step), inside the window."""
    rows = ctx["counters"].get("decode_context")
    if not rows:
        return None
    t0, t1 = ctx["trace_window"]
    cfg = ctx["config"]
    total_ctx = sum(c for t, c, n in rows if t0 <= t <= t1)
    total_slots = sum(n for t, c, n in rows if t0 <= t <= t1)
    work = flops.paged_attend(cfg, total_ctx, total_slots,
                              ctx["counters"]["kv_bytes"])
    return flops.least_time(work, ctx["peak"])


WORK = {"flash_attention": _flash_attention, "paged_attend": _paged_attend}
