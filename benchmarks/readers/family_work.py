"""A kernel's share of its roofline where the required work is the FAMILY's
own to count: ``path_component.py``'s roofline (the self time of the ``XLA
Ops`` events whose path has one of ``components``,
``path_component.component_seconds``) against the least time the chip could
take for the work ``work`` names in ``benchmarks/flops_<family>.py``
(``window_work(name, config, traffic, steps, routing)``: what the algorithm
REQUIRES of the traced window's whole steps, forward once and backward once),
the family found by the configuration's ``family``. ``path_component.WORK``
is a fixed dict over ``flops.py``, which counts one head count, every layer
full causal and every layer a dense FFN.

``routing``: the ``(pairs held, pairs routed, fullest expert's rows)`` the
runner read with each traced step's loss (``counters["routing_steps"]``).

None where there is nothing to read: no trace, no device plane, no peak, no
event with such a component (the parent of the PR that added the family), a
configuration without a ``family`` or a family without such a module.
"""
import importlib
import json

from benchmarks import flops, trace_reduce
from benchmarks.readers import _xplane, scope_time
from benchmarks.readers.path_component import component_seconds


def traced_routing(ctx, steps: int) -> list:
    """The traced steps' routing counts, oldest first."""
    t0, t1 = ctx["trace_window"]
    rows = [row[1:] for row in ctx["counters"].get("routing_steps") or ()
            if t0 <= row[0] <= t1]
    return rows[:steps]


def family_flops(ctx):
    family = ctx["config"].get("family")
    if family is None:
        return None
    try:
        return importlib.import_module(f"benchmarks.flops_{family}")
    except ModuleNotFoundError:
        return None


def read(ctx, params):
    found, mod = _xplane.traced(ctx), family_flops(ctx)
    if found is None or mod is None or ctx.get("peak") is None \
            or not hasattr(mod, "window_work"):
        return None
    trace, path = found
    seconds = component_seconds(
        trace["device_ops"], scope_time.op_paths_of(path),
        params["components"], trace["lo_ns"], trace["hi_ns"])
    steps = trace_reduce.spans_inside(trace, params["step_span"])
    if not seconds or not steps:
        return None
    work = mod.window_work(params["work"], ctx["config"], ctx["traffic"],
                           steps, traced_routing(ctx, steps))
    if work is None:
        return None
    least_s, bound = flops.least_time(work, ctx["peak"])
    print(json.dumps({"roofline": {
        "kernel": params["components"], "bound": bound, "least_s": least_s,
        "kernel_s": seconds, "steps": steps}}), flush=True)
    return 100.0 * least_s / seconds
