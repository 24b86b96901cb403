"""``mixed_attend_roofline``: the paged attend kernel's share of its roofline
in a family whose attention layers differ in REACH (``mimo_v2``: full layers
and window layers, each kind with its own kv heads and its own page class).
``path_component.py``'s roofline with another work function: the least time
to read the decode steps' live k and v ONCE at the PUBLISHED bytes a token,
every live position in the full layers and the last ``sliding_window`` of
each slot in the window layers (``flops_mimo_v2.mixed_attend``;
``flops.paged_attend`` multiplies by ``num_hidden_layers`` and one kv-head
count), over the self time of the events with the component ``paged_attend``
under ``serve_decode`` (one kernel serves both kinds). None where there is
nothing to read: no trace, no such kernel in the program (the parent of the
PR that added the family), a configuration without ``hybrid_layer_pattern``.
"""
import json

from benchmarks import flops, flops_mimo_v2, trace_reduce
from benchmarks.readers import _xplane, scope_time
from benchmarks.readers.path_component import component_seconds


def read(ctx, params):
    found = _xplane.traced(ctx)
    if found is None or ctx.get("peak") is None \
            or "hybrid_layer_pattern" not in ctx["config"]:
        return None
    trace, path = found
    device_ops, _ = trace_reduce.program_ops(trace, params["program"])
    seconds = component_seconds(device_ops, scope_time.op_paths_of(path),
                                params["component"], trace["lo_ns"],
                                trace["hi_ns"])
    t0, t1 = ctx["trace_window"]
    rows = [row for row in ctx["counters"].get("decode_context") or ()
            if t0 <= row[0] <= t1]
    if not seconds or not rows:
        return None
    work = flops_mimo_v2.mixed_attend(
        ctx["config"], sum(c for _, c, _ in rows), sum(n for _, _, n in rows),
        ctx["counters"]["kv_bytes"])
    least_s, bound = flops.least_time(work, ctx["peak"])
    print(json.dumps({"roofline": {
        "kernel": params["component"], "bound": bound, "least_s": least_s,
        "kernel_s": seconds}}), flush=True)
    return 100.0 * least_s / seconds
