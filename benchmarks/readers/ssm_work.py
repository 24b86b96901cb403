"""``ssm_step_roofline``, ``ssm_chunk_roofline``, ``mqa_attend_roofline`` and
``serve.chunk_fill_pct`` (``jamba``). The rooflines are
``path_component.py``'s with the family's own work functions
(``flops_jamba.py``; ``path_component.WORK`` is a fixed dict over
``flops.py``): the least time the chip could take for the REQUIRED work over
the self time of the events whose path has ``component`` under ``program`` (a
Pallas kernel's ``name=`` or a ``named_scope``: the same work whatever
implements it).

``work: "ssm_step"``: the decode steps of the traced window, each live slot's
state in and out once a Mamba layer (slots from
``counters["decode_context"]``). ``work: "ssm_chunk"``: the prefill chunks of
the traced window, from the program's own ``dtg.serve.prefill`` spans (their
``tokens`` statistic: the REAL tokens a chunk program took; the runner keeps
no count of them). ``work: "mqa_attend"``: the decode steps' live k and v
read once in the attention layers alone (``hybrid_attend_roofline``'s work
function counts the attending layers from ``layer_types``, which this
family's configuration does not have). ``as: "chunk_fill_pct"``: the same
spans' real tokens over their number times ``job.engine.prefill_chunk``, the
share of a padded chunk program's rows that are a prompt's.

None where there is nothing to read: no trace, no peak, a configuration
without Mamba layers, no event with such a component or no such span (the
parent of the PR that added the family; another family's cell).
"""
import json

from benchmarks import flops, flops_jamba, trace_reduce
from benchmarks.readers import _xplane, scope_time
from benchmarks.readers.path_component import component_seconds


def _decode_rows(ctx):
    """``(context tokens, live slots)`` summed over the traced decode steps."""
    t0, t1 = ctx["trace_window"]
    rows = [row for row in ctx["counters"].get("decode_context") or ()
            if t0 <= row[0] <= t1]
    return sum(c for _, c, _ in rows), sum(n for _, _, n in rows)


def _chunks(trace, path):
    """``(real tokens, chunks)`` of the traced window's prefill spans."""
    chunks = [s for s in _xplane.program_spans(path)
              if s[0] == "serve.prefill"
              and trace["lo_ns"] <= s[1] and s[2] <= trace["hi_ns"]]
    return sum(int(s[4].get("tokens", 0)) for s in chunks), len(chunks)


def _step_work(ctx, trace, path):
    _, slot_steps = _decode_rows(ctx)
    if not slot_steps:
        return None
    return flops_jamba.ssm_step(ctx["config"], slot_steps)


def _attend_work(ctx, trace, path):
    context, slot_steps = _decode_rows(ctx)
    if not slot_steps:
        return None
    return flops_jamba.mqa_attend(ctx["config"], context, slot_steps,
                                  ctx["counters"]["kv_bytes"])


def _chunk_work(ctx, trace, path):
    tokens, chunks = _chunks(trace, path)
    if not chunks:
        return None
    return flops_jamba.ssm_chunk(ctx["config"], tokens, chunks)


WORK = {"ssm_step": _step_work, "ssm_chunk": _chunk_work,
        "mqa_attend": _attend_work}


def read(ctx, params):
    found = _xplane.traced(ctx)
    if found is None or "mamba_d_state" not in ctx["config"]:
        return None
    trace, path = found
    if params.get("as") == "chunk_fill_pct":
        tokens, chunks = _chunks(trace, path)
        if not chunks:
            return None
        return 100.0 * tokens / (chunks * ctx["job"]["engine"]["prefill_chunk"])
    if ctx.get("peak") is None:
        return None
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
