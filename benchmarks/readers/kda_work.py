"""``kda_step_roofline``, ``kda_chunk_roofline`` and ``gqa_attend_roofline``:
a mixer computation's share of its roofline (``solar_open2``).
``path_component.py``'s roofline with the family's own work functions
(``flops_solar_open2.py``; ``path_component.WORK`` is a fixed dict over
``flops.py``): the least time the chip could take for the REQUIRED work over
the self time of the events whose path has ``component`` under ``program`` (a
Pallas kernel's ``name=`` or a ``named_scope``: the same work whatever
implements it).

``work: "kda_step"``: the decode steps of the traced window, each live
slot's state in and out once a KDA layer (slots from
``counters["decode_context"]``). ``work: "kda_chunk"``: the prefill chunks of
the traced window, from the program's own ``dtg.serve.prefill`` spans (their
``tokens`` statistic: the runner keeps no count of a chunk's real tokens).
``work: "gqa_attend"``: the decode steps' live k and v read once in the GQA
layers alone (``hybrid_attend_roofline``'s work function counts the attending
layers from ``layer_types``, which this family's configuration does not
have).

None where there is nothing to read: no trace, no peak, a configuration
without KDA layers, no event with such a component (the parent of the PR that
added the family; another family's cell).
"""
import json

from benchmarks import flops, flops_solar_open2, trace_reduce
from benchmarks.readers import _xplane, scope_time
from benchmarks.readers.path_component import component_seconds


def _decode_rows(ctx):
    """``(context tokens, live slots)`` summed over the traced decode steps."""
    t0, t1 = ctx["trace_window"]
    rows = [row for row in ctx["counters"].get("decode_context") or ()
            if t0 <= row[0] <= t1]
    return sum(c for _, c, _ in rows), sum(n for _, _, n in rows)


def _step_work(ctx, trace, path):
    _, slot_steps = _decode_rows(ctx)
    if not slot_steps:
        return None
    return flops_solar_open2.kda_step(ctx["config"], slot_steps)


def _attend_work(ctx, trace, path):
    context, slot_steps = _decode_rows(ctx)
    if not slot_steps:
        return None
    return flops_solar_open2.gqa_attend(ctx["config"], context, slot_steps,
                                        ctx["counters"]["kv_bytes"])


def _chunk_work(ctx, trace, path):
    chunks = [s for s in _xplane.program_spans(path)
              if s[0] == "serve.prefill"
              and trace["lo_ns"] <= s[1] and s[2] <= trace["hi_ns"]]
    if not chunks:
        return None
    return flops_solar_open2.kda_chunk(
        ctx["config"], sum(int(s[4].get("tokens", 0)) for s in chunks),
        len(chunks))


WORK = {"kda_step": _step_work, "kda_chunk": _chunk_work,
        "gqa_attend": _attend_work}


def read(ctx, params):
    found = _xplane.traced(ctx)
    if found is None or ctx.get("peak") is None \
            or "linear_attn_config" not in ctx["config"]:
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
