"""The serve engine's host loop by phase, from the program's own spans
(``dtg.serve.*``, ``distributed_training_guide_tpu/utils/trace.py``) in the
traced window. Spans nest by containment on one thread; ``serve.step`` is one
engine iteration and everything else lies inside one.

``stat`` picks the number. The two per-step numbers are read over DECODE
steps alone, the steps with no ``serve.prefill`` span inside (a step that
also runs a prefill chunk does other host work and waits twice):

- ``host_ms_per_step``: a ``serve.step`` minus the ``serve.wait`` spans inside
  it (the blocking reads of the device's results), mean over the steps, ms.
- ``schedule_ms_per_step``: the time inside a step under ``serve.expire``,
  ``serve.admit``, ``serve.reserve`` and ``serve.book``, mean over the steps.
- ``idle_unattributed_pct``: of the worst device's idle time that falls inside
  ``serve.step`` spans, the share that no span inside the step covers. Idle
  time between steps is the caller's (``breakdown.idle_gaps`` shows it under
  the benchmark's ``client`` span).

Printed once, on an earlier line: ``{"idle_by_program_span": ...}``, the worst
device's idle seconds by the deepest program span over each instant of each
gap (``outside`` where there is none). No ``dtg.serve.step`` in the trace, as on
the parent of the PR that added the spans: ``None``.
"""
import json

from benchmarks import trace_reduce
from benchmarks.readers import _xplane

STEP = "serve.step"
WAIT = ("serve.wait",)
PREFILL = "serve.prefill"
SCHEDULE = ("serve.expire", "serve.admit", "serve.reserve", "serve.book")


def steps_with_children(spans, lo: int, hi: int) -> list:
    """``[(step, [children])]`` for the ``serve.step`` spans inside the
    window; a child is any other span inside the step on its thread."""
    steps = sorted((s for s in spans if s[0] == STEP and s[1] >= lo
                    and s[2] <= hi), key=lambda s: s[1])
    out = []
    for step in steps:
        _, a, b, thread, _ = step
        out.append((step, [s for s in spans if s[0] != STEP
                           and s[3] == thread and s[1] >= a and s[2] <= b]))
    return out


def decode_steps(steps) -> list:
    """Of ``steps_with_children``'s steps, those that ran no prefill."""
    return [(step, children) for step, children in steps
            if all(c[0] != PREFILL for c in children)]


def covered_ns(children, names=None) -> int:
    return trace_reduce.measure(trace_reduce.union(
        (a, b) for n, a, b, _, _ in children if names is None or n in names))


def host_ms_per_step(steps) -> float:
    return sum((step[2] - step[1]) - covered_ns(children, WAIT)
               for step, children in steps) / len(steps) / 1e6


def schedule_ms_per_step(steps) -> float:
    return sum(covered_ns(children, SCHEDULE)
               for _, children in steps) / len(steps) / 1e6


def deepest_pieces(spans) -> list:
    """``(name, start, end)`` pieces, disjoint on each thread: where each
    span is the deepest one (its own time, its children's taken out)."""
    out = []
    for thread in {s[3] for s in spans}:
        stack = []              # [name, end, cursor]
        for name, a, b, _, _ in sorted((s for s in spans if s[3] == thread),
                                       key=lambda s: (s[1], -s[2])):
            while stack and stack[-1][1] <= a:
                top = stack.pop()
                if top[1] > top[2]:
                    out.append((top[0], top[2], top[1]))
                if stack:
                    stack[-1][2] = top[1]
            if stack and a > stack[-1][2]:
                out.append((stack[-1][0], stack[-1][2], a))
            stack.append([name, b, a])
        while stack:
            top = stack.pop()
            if top[1] > top[2]:
                out.append((top[0], top[2], top[1]))
            if stack:
                stack[-1][2] = top[1]
    return out


def idle_by_span(gaps, spans) -> dict:
    """Idle seconds by the deepest program span over each instant of each
    gap (``outside`` where there is none: between the engine's steps)."""
    pieces = sorted(deepest_pieces(spans), key=lambda p: p[1])
    totals: dict[str, float] = {}
    for lo, hi in gaps:
        left = hi - lo
        for name, a, b in pieces:
            if a >= hi:
                break
            cover = min(b, hi) - max(a, lo)
            if cover > 0:
                totals[name] = totals.get(name, 0.0) + cover / 1e9
                left -= cover
        if left > 0:
            totals["outside"] = totals.get("outside", 0.0) + left / 1e9
    return dict(sorted(totals.items(), key=lambda kv: -kv[1]))


def idle_unattributed_pct(by_span: dict) -> float:
    """From :func:`idle_by_span`'s table: of the idle time inside steps,
    the share under ``serve.step`` itself, which no span inside it covers."""
    inside = sum(v for k, v in by_span.items() if k != "outside")
    return 100.0 * by_span.get(STEP, 0.0) / inside if inside else 0.0


def worst_device_gaps(trace) -> list:
    worst = max(trace["per_device"],
                key=lambda d: trace["per_device"][d]["idle_share"])
    return trace_reduce.busy_and_gaps(trace["device_ops"][worst],
                                      trace["lo_ns"], trace["hi_ns"])[1]


def reduce(ctx) -> dict | None:
    """All three numbers, worked out and printed once a run."""
    if "program_span_stats" in ctx:
        return ctx["program_span_stats"]
    found, result = _xplane.traced(ctx), None
    if found is not None:
        trace, path = found
        spans = _xplane.program_spans(path)
        steps = steps_with_children(spans, trace["lo_ns"], trace["hi_ns"])
        decode = decode_steps(steps)
        if decode:
            by_span = idle_by_span(worst_device_gaps(trace), spans)
            result = {
                "host_ms_per_step": host_ms_per_step(decode),
                "schedule_ms_per_step": schedule_ms_per_step(decode),
                "idle_unattributed_pct": idle_unattributed_pct(by_span),
            }
            print(json.dumps({"idle_by_program_span": by_span,
                              "program_steps": len(steps),
                              "decode_steps": len(decode)}), flush=True)
    ctx["program_span_stats"] = result
    return result


def read(ctx, params):
    found = reduce(ctx)
    return None if found is None else found[params["stat"]]
