"""Device busy time of ONE execution of a jitted program, in ms: the union
of the ``XLA Ops`` events under each of its executions in the traced window
(the ``XLA Modules`` line tells them apart: ``trace_reduce.program_runs``),
mean over the executions and the devices. ``program`` may name a size the
cell's engine settings give: ``serve_chunk_t{prefill_chunk}``. None where the
window ran no such program (a cell whose window holds no prefill; a run with
no device plane).
"""
from benchmarks import trace_reduce
from benchmarks.readers import _xplane


def read(ctx, params):
    found = _xplane.traced(ctx)
    if found is None:
        return None
    trace, _ = found
    program = params["program"].format(**ctx["job"].get("engine", {}))
    lo, hi = trace["lo_ns"], trace["hi_ns"]
    busy_ns, n_runs = 0, 0
    for d, events in trace["device_ops"].items():
        runs = trace_reduce.program_runs(
            trace["device_modules"].get(d, ()), program, lo, hi)
        inside = trace_reduce.under(runs)
        busy_ns += trace_reduce.measure(trace_reduce.union(
            (a, b) for _, a, b in events if inside(a, b)))
        n_runs += len(runs)
    return busy_ns / 1e6 / n_runs if n_runs else None
