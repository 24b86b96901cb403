"""``retention_step_roofline`` and ``retention_chunk_roofline`` (``brumby``):
``path_component.py``'s roofline with the family's own work functions
(``flops_brumby.py``; ``path_component.WORK`` is a fixed dict over
``flops.py``): the least time the chip could take for the REQUIRED work over
the self time of the events whose path has ``component`` under ``program`` (a
Pallas kernel's ``name=`` or a ``named_scope``: the same work whatever
implements it, and for the step the scope holds XLA's feature rows and
normaliser beside the kernel).

``work: "retention_step"``: the decode steps of the traced window, each live
slot's state in and out once a layer (slots from
``counters["decode_context"]``). ``work: "retention_chunk"``: the prefill
chunks of the traced window, from the program's own ``dtg.serve.prefill``
spans: their ``tokens`` statistic (the REAL tokens a chunk program took) and
their ``start`` (0 for a sequence's first chunk, whose state is zero).

A third copy of ``kda_work.py`` / ``ssm_work.py``'s frame (PERF.md section 7:
a ``benchmark`` PR merges them). None where there is nothing to read: no
trace, no peak, another family's configuration, no event with such a
component or no such span with a ``start`` (the parent of the PR that added
the family).
"""
import json

from benchmarks import flops, flops_brumby, trace_reduce
from benchmarks.readers import _xplane, scope_time
from benchmarks.readers.path_component import component_seconds


def _step_work(ctx, trace, path):
    t0, t1 = ctx["trace_window"]
    slot_steps = sum(n for t, _, n in
                     ctx["counters"].get("decode_context") or ()
                     if t0 <= t <= t1)
    if not slot_steps:
        return None
    return flops_brumby.retention_step(ctx["config"], slot_steps)


def _chunk_work(ctx, trace, path):
    chunks = [(int(s[4]["tokens"]), int(s[4]["start"]) > 0)
              for s in _xplane.program_spans(path)
              if s[0] == "serve.prefill" and "start" in s[4]
              and trace["lo_ns"] <= s[1] and s[2] <= trace["hi_ns"]]
    if not chunks:
        return None
    return flops_brumby.retention_chunk(ctx["config"], chunks)


WORK = {"retention_step": _step_work, "retention_chunk": _chunk_work}


def read(ctx, params):
    found = _xplane.traced(ctx)
    if (found is None or ctx.get("peak") is None
            or ctx["config"].get("family") != "brumby"):
        return None
    trace, path = found
    device_ops, _ = trace_reduce.program_ops(trace, params["program"])
    seconds = component_seconds(device_ops, scope_time.op_paths_of(path),
                                params["component"], trace["lo_ns"],
                                trace["hi_ns"])
    if not seconds:
        return None
    work = WORK[params["work"]](ctx, trace, path)
    if work is None:
        return None
    least_s, bound = flops.least_time(work, ctx["peak"])
    print(json.dumps({"roofline": {
        "kernel": params["component"], "bound": bound, "least_s": least_s,
        "kernel_s": seconds}}), flush=True)
    return 100.0 * least_s / seconds
