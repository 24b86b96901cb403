"""Device time by the program's own ``jax.named_scope`` names, per step of
the traced window (``distributed_training_guide_tpu/utils/trace.py: SCOPES``).

Every ``XLA Ops`` event's self time (its duration minus what its nested
events cover, ``trace_reduce.self_times``) goes to the INNERMOST scope in its
path (``attn/attend/paged_attend`` is ``attend``; ``_xplane.scope_of``), or to
``unscoped`` where the path has none: what the partitioner or the compiler
made, a scan's own slicing, what a scope missed. So the scopes and
``unscoped`` add up to the device's busy time. ``recompute`` lies across them:
the operations under ``jax.checkpoint``'s ``rematted_computation`` wrapper,
forward work run a second time. Steps are the benchmark's ``step_span`` spans
inside the window; where the metric names a ``program`` (``serve_decode``),
only the events that ran under that jitted program count (the ``XLA Modules``
line) and a step is one execution of it, so that a prefill chunk inside the
window, which runs the same scopes, is not read as decode time. A value is a
mean over the cell's devices, in ms per step.

The whole table is printed once, on an earlier line
(``{"device_ms_by_scope": ...}``), with the largest unscoped operations and,
on several chips, the exposed-collective seconds by scope; where a program is
named, every other program the window ran gets such a line of its own (ms per
execution: a chunk step's split beside the decode step's). A program that
names none of its parts (the parent of the PR that added the names) gives
``None`` for every metric.
"""
import json

from benchmarks import trace_reduce
from benchmarks.readers import _xplane

# distributed_training_guide_tpu/utils/trace.py: SCOPES, said again here
# because the readers also run over a checkout that has no such module
SCOPES = ("embed", "attn", "mlp", "final_norm", "loss_head", "optimizer",
          "router", "experts", "attend", "kv_write", "sample", "layers")


def table_from(device_ops: dict, op_paths: dict, lo: int, hi: int,
               n_steps: int) -> dict | None:
    """``device_ops``: device -> ``(name, start, end)`` events; ``op_paths``:
    event name -> scope path. None where no operation carries a scope."""
    if not device_ops or not n_steps:
        return None
    n_dev = len(device_ops)
    by_scope: dict[str, float] = {}
    exposed: dict[str, float] = {}
    unscoped_ops: dict[str, float] = {}
    recompute = busy = 0.0
    for ops in device_ops.values():
        for name, self_ns, a, b in trace_reduce.self_times(ops):
            if a < lo or b > hi or not self_ns:
                continue
            path = op_paths.get(name, "")
            scope = _xplane.scope_of(path, SCOPES) or "unscoped"
            by_scope[scope] = by_scope.get(scope, 0.0) + self_ns
            busy += self_ns
            if _xplane.is_recompute(path):
                recompute += self_ns
            if scope == "unscoped":
                short = trace_reduce.short_name(name)
                unscoped_ops[short] = unscoped_ops.get(short, 0.0) + self_ns
        if n_dev > 1:
            for scope, ns in exposed_by_scope(ops, op_paths, lo, hi).items():
                exposed[scope] = exposed.get(scope, 0.0) + ns
    if set(by_scope) <= {"unscoped"}:
        return None
    per_step = 1e-6 / (n_dev * n_steps)         # ns -> ms a step a device
    out = {
        "steps": n_steps, "devices": n_dev,
        "ms_per_step": {k: v * per_step for k, v in sorted(
            by_scope.items(), key=lambda kv: -kv[1])},
        "recompute_ms_per_step": recompute * per_step,
        "busy_ms_per_step": busy * per_step,
        "unscoped_top": [[k, v * per_step] for k, v in sorted(
            unscoped_ops.items(), key=lambda kv: -kv[1])[:8]],
    }
    if exposed:
        out["exposed_collective_s_by_scope"] = {
            k: v / 1e9 / n_dev for k, v in sorted(
                exposed.items(), key=lambda kv: -kv[1])}
    return out


def exposed_by_scope(ops, op_paths: dict, lo: int, hi: int) -> dict:
    """One device's exposed collective time (``trace_reduce``: inside a
    collective while no other operation runs there), by the collective's
    scope."""
    leaves = trace_reduce.self_leaf_intervals(ops)
    other = trace_reduce.union(trace_reduce.clip(
        [(a, b) for n, a, b in leaves if not trace_reduce.is_collective(n)],
        lo, hi))
    out: dict[str, float] = {}
    for name, a, b in leaves:
        if not trace_reduce.is_collective(name):
            continue
        alone = trace_reduce.measure(trace_reduce.subtract(
            trace_reduce.clip([(a, b)], lo, hi), other))
        if alone:
            scope = _xplane.scope_of(op_paths.get(name, ""),
                                     SCOPES) or "unscoped"
            out[scope] = out.get(scope, 0.0) + alone
    return out


def op_paths_of(path) -> dict:
    """Event name -> scope path, over the trace's device planes."""
    out: dict[str, str] = {}
    for plane, found in _xplane.metadata_stat(path, "tf_op").items():
        if trace_reduce.DEVICE_PLANE.match(plane):
            out.update(found)
    return out


def table(ctx, step_span: str, program: str | None = None) -> dict | None:
    """The cell's table, worked out and printed once a run."""
    if "scope_table" in ctx:
        return ctx["scope_table"]
    found, result = _xplane.traced(ctx), None
    if found is not None:
        trace, path = found
        lo, hi = trace["lo_ns"], trace["hi_ns"]
        paths = op_paths_of(path)
        if program is None:
            result = table_from(trace["device_ops"], paths, lo, hi,
                                trace_reduce.spans_inside(trace, step_span))
        else:
            ops, runs = trace_reduce.program_ops(trace, program)
            result = table_from(ops, paths, lo, hi, runs)
            for other in sorted(trace_reduce.programs_run(trace) - {program}):
                ops, runs = trace_reduce.program_ops(trace, other)
                split = table_from(ops, paths, lo, hi, runs)
                if split is not None:
                    print(json.dumps({"device_ms_by_scope": split,
                                      "program": other}), flush=True)
    if result is not None:
        print(json.dumps({"device_ms_by_scope": result}), flush=True)
    ctx["scope_table"] = result
    return result


def read(ctx, params):
    found = table(ctx, params["step_span"], params.get("program"))
    if found is None:
        return None
    if params["scope"] == "recompute":
        return found["recompute_ms_per_step"]
    return found["ms_per_step"].get(params["scope"])
