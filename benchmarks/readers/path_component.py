"""Device time of the ``XLA Ops`` events whose ``tf_op`` path has a given
COMPONENT: a Pallas kernel's ``name=`` (``.../attend/paged_latent_attend/
pallas_call:``, ``.../experts/gmm/pallas_call:``) or a ``jax.named_scope``
that ``scope_time``'s fixed list does not know (``latent_proj``). The path is
matched, not the instruction's name (which takes a transform's wrapper where
no scope encloses the call): ``_xplane.components``.

``as: "ms_per_step"``: the events' self time, ms per ``step_span`` span of
the window, mean over devices. ``as: "roofline"``: the least time the chip
could take for the work ``work`` names (``flops_mla_moe.py``, from the run's
counters inside the traced window) over that time, in percent.

A program that carries no such component (the parent of the PR that added
it, another family's cell, a run with no device plane) gives ``None``.
"""
import json

from benchmarks import flops, flops_mla_moe, trace_reduce
from benchmarks.readers import _xplane, scope_time


def component_seconds(device_ops: dict, op_paths: dict, component: str,
                      lo: int, hi: int):
    """Mean over devices of the self time, in seconds, of the events inside
    [lo, hi] whose path has ``component``; None where no event has it."""
    total, found = 0.0, False
    for ops in device_ops.values():
        for name, self_ns, a, b in trace_reduce.self_times(ops):
            if a < lo or b > hi or not self_ns:
                continue
            if component in _xplane.components(op_paths.get(name, "")):
                total += self_ns
                found = True
    return total / 1e9 / len(device_ops) if found else None


def _in_window(ctx, rows):
    t0, t1 = ctx["trace_window"]
    return [row for row in rows if t0 <= row[0] <= t1]


def _latent_attend(ctx):
    rows = _in_window(ctx, ctx["counters"].get("decode_context") or ())
    if not rows:
        return None
    return flops_mla_moe.latent_attend(
        ctx["config"], sum(c for _, c, _ in rows), sum(n for _, _, n in rows),
        ctx["counters"]["kv_bytes"])


def _expert_gmm(ctx):
    rows = _in_window(ctx, ctx["counters"].get("routing_steps") or ())
    if not rows:
        return None
    return flops_mla_moe.expert_gmm(
        ctx["config"], sum(touched for _, _, touched in rows),
        sum(held for _, held, _ in rows))


WORK = {"latent_attend": _latent_attend, "expert_gmm": _expert_gmm}


def read(ctx, params):
    found = _xplane.traced(ctx)
    if found is None:
        return None
    trace, path = found
    lo, hi = trace["lo_ns"], trace["hi_ns"]
    seconds = component_seconds(trace["device_ops"],
                                scope_time.op_paths_of(path),
                                params["component"], lo, hi)
    if not seconds:
        return None
    if params["as"] == "ms_per_step":
        steps = sum(1 for name, a, b in trace["host_spans"]
                    if name == params["step_span"] and a >= lo and b <= hi)
        return 1e3 * seconds / steps if steps else None
    if ctx.get("peak") is None:
        return None
    work = WORK[params["work"]](ctx)
    if work is None:
        return None
    least_s, bound = flops.least_time(work, ctx["peak"])
    print(json.dumps({"roofline": {
        "kernel": params["component"], "bound": bound, "least_s": least_s,
        "kernel_s": seconds}}), flush=True)
    return 100.0 * least_s / seconds
