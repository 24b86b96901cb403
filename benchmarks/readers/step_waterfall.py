"""The serve engine's step as a waterfall that sums to the step: every
``dtg.serve.step`` of the traced window that holds exactly one
``serve.dispatch`` and one ``serve.wait`` after it (the synchronous path; a
double-buffered horizon step is skipped and counted) is joined with the
execution of the dispatch's ``program`` on the worst device's ``XLA Modules``
line and cut at five instants::

    step.start .. dispatch.start   pre       expire, admit, prefill, sample, reserve,
                                             arrays, upload, ... and the step's own remainder
    dispatch.start .. module.start launch    the host's call path, the runtime's launch,
                                             uploads still in flight
    module.start .. module.end     device    busy + the program's own inner gaps
    module.end .. wait.end         readback  the result's way to the host
    wait.end .. step.end           post      book, release, remainder

The five are a partition of the step. DECODE steps (no ``serve.prefill``
inside, ``program_span.decode_steps``) and CHUNK steps are kept apart, decode
steps also by whether a ``serve.build`` lies inside (rebuilt / quiet).

The host's spans and the device's line come from two clocks that the profiler
aligns to a millisecond or so, not to the microsecond: recorded v5e traces
have the program START 0.8 to 1.4 ms BEFORE the ``serve.dispatch`` that
enqueues it. ``clock_slack_ms`` is what causality leaves open, over the
window's steps: the device line may be moved by anything from ``max(not_before
- module.start)`` to ``min(not_after - module.end)``. ``not_before`` /
``not_after`` are the TPU runtime's own host events around the execution where
the trace has them (``DoEnqueueProgram`` on its queue's thread, and the
``CompleteCallbacks`` of the same ``run_id``: a program runs after it is
enqueued and before its completion is handled; 0.4 ms of slack on a recorded
trace), else the dispatch's start and the wait's end (1.1-1.3 ms).
``steps_anchored_by_runtime_events`` says how many steps had the former. Where
0 lies inside the slack, the trace's own alignment is kept; where it does not,
the device line is moved by the LEAST amount that restores causality
(``device_clock_shift_ms``), so the fastest launch of the window reads the
time to its enqueue (13 recorded steps: 1.36-1.41 ms between enqueue and the
unmoved start in 12 of them, so the device starts what is enqueued within
tens of us and the least shift is the likely one). ``launch + readback`` and
the difference between two steps' launches do not depend on the alignment; the
split of one step's round trip does, by up to the slack's width.

``stat`` picks the number (ms are means over the decode steps that were cut):

- ``launch_ms_per_step``, ``readback_ms_per_step``: those phases.
- ``upload_ms_per_step``, ``arrays_ms_per_step``: ``serve.upload`` /
  ``serve.arrays`` time inside the decode steps over ALL of them, rebuilt or
  not, so that they add up with the others.
- ``rebuild_steps_pct``: decode steps with a ``serve.build`` inside.
- ``chunk_step_gap_ms``: a chunk step less the union of the device's op
  intervals inside it, mean over the chunk steps.
- ``slow_step_excess_pct``: sum over the decode steps longer than 3 x the
  median decode step of (step - median), as a share of the traced window.

Printed once a run, on earlier lines: ``{"step_waterfall": ...}`` (ms by
phase and, inside ``pre`` and ``post``, by span), ``{"rebuild_reasons": ...}``
(the ``reason`` of every ``serve.build`` in the window, bytes and arrays a
build) and ``{"slow_steps": [...]}``: each slow step's ``seq``, wall and
``cpu_ms``, the (phase, span) that holds most of its excess over that piece's
median, the device's busy ms inside it and any ``dtg.gc`` span inside it.
No ``dtg.serve.arrays`` in the trace, as on the parent of the PR that added
it: ``None``, and nothing is printed.
"""
import bisect
import json
import statistics

from benchmarks import trace_reduce
from benchmarks.readers import _xplane, program_span

STEP, DISPATCH, WAIT = "serve.step", "serve.dispatch", "serve.wait"
BUILD, ARRAYS, UPLOAD, GC = "serve.build", "serve.arrays", "serve.upload", "gc"
ENQUEUE, COMPLETE = "DoEnqueueProgram", "CompleteCallbacks"   # the runtime's
PHASES = ("pre", "launch", "device", "readback", "post")
SLOW = 3.0          # a slow step: longer than this many median decode steps


def match_run(dispatch, wait, runs):
    """The execution among the program's sorted ``runs`` that overlaps
    ``[dispatch.start, wait.end]`` most, if more than half of it lies
    inside: robust to a device line that is a millisecond off the host's."""
    lo, hi = dispatch[1], wait[2]
    i = bisect.bisect_left(runs, (lo, lo))
    best, best_cover = None, 0
    for a, b in runs[max(0, i - 1): i + 2]:
        cover = min(b, hi) - max(a, lo)
        if cover > best_cover:
            best, best_cover = (a, b), cover
    if best is None or 2 * best_cover <= best[1] - best[0]:
        return None
    return best


def join(steps, modules, executions=()) -> tuple:
    """``[(step, children, dispatch, wait, run, bounds)]`` for the steps that
    hold exactly one dispatch and one wait after it and whose program's
    execution is on the device's line, and the number of steps left out.
    ``bounds``: the host instants the run lies between, the one of the
    runtime's sorted ``executions`` ``(enqueued, completed)`` inside the round
    trip, else the dispatch's start and the wait's end."""
    runs: dict[str, list] = {}
    joined = []
    for step, children in steps:
        dispatches = [c for c in children if c[0] == DISPATCH]
        waits = [c for c in children if c[0] == WAIT]
        if len(dispatches) != 1 or len(waits) != 1 \
                or waits[0][1] < dispatches[0][2]:
            continue
        program = str(dispatches[0][4].get("program", ""))
        if program.startswith("serve_horizon_k"):
            continue        # its wait reads the block dispatched a step ago
        if program not in runs:
            prefix = f"jit_{program}("
            runs[program] = sorted((a, b) for name, a, b in modules
                                   if name.startswith(prefix))
        run = match_run(dispatches[0], waits[0], runs[program])
        if run is None:
            continue
        lo, hi = dispatches[0][1], waits[0][2]
        i = bisect.bisect_left(executions, (lo, lo))
        inside = [e for e in executions[i:i + 2] if e[0] <= hi]
        bounds = inside[0] if len(inside) == 1 and lo <= inside[0][0] \
            <= inside[0][1] <= hi else (lo, hi)
        joined.append((step, children, dispatches[0], waits[0], run, bounds))
    return joined, len(steps) - len(joined)


def clock_slack(joined) -> tuple:
    """``(least, most)`` ns by which the device's line may be moved later
    with every program inside its ``bounds``."""
    least = max(bounds[0] - run[0] for *_, run, bounds in joined)
    most = min(bounds[1] - run[1] for *_, run, bounds in joined)
    return least, most


def clock_shift(least: int, most: int) -> int:
    """0 where the trace's alignment is causal, else the least move that
    makes it so (``least > most`` cannot be: both are then honoured halfway)."""
    if least > most:
        return (least + most) // 2
    return least if least > 0 else (most if most < 0 else 0)


def cut(step, children, dispatch, wait, run, shift: int = 0) -> dict:
    """One joined step: ns by phase, and by (phase, deepest span)."""
    a, b = step[1], step[2]
    # inside the step, and inside the round trip; a clock that is off cannot
    # push an instant past its neighbours
    m0 = min(max(run[0] + shift, dispatch[1]), wait[2])
    m1 = min(max(run[1] + shift, m0), wait[2])
    edges = (a, dispatch[1], m0, m1, wait[2], b)
    phases = {p: edges[i + 1] - edges[i] for i, p in enumerate(PHASES)}
    parts: dict[tuple, int] = {}
    for name, lo, hi in program_span.deepest_pieces([step, *children]):
        for i, p in enumerate(PHASES):
            cover = min(hi, edges[i + 1]) - max(lo, edges[i])
            if cover > 0:
                parts[(p, name)] = parts.get((p, name), 0) + cover
    return {"seq": step[4].get("seq"), "ns": b - a, "phases": phases,
            "parts": parts, "cpu_ms": step[4].get("cpu_ms"),
            "rebuilt": any(c[0] == BUILD for c in children),
            "chunk": any(c[0] == program_span.PREFILL for c in children),
            "span": (a, b)}


def table(cuts) -> dict | None:
    """Mean ms by phase and, inside ``pre`` and ``post``, by span."""
    if not cuts:
        return None
    n = len(cuts)
    total = sum(c["ns"] for c in cuts)
    by_phase = {p: sum(c["phases"][p] for c in cuts) for p in PHASES}
    out = {"steps": n, "step_ms": total / n / 1e6,
           "step_ms_p50": statistics.median(c["ns"] for c in cuts) / 1e6,
           "sums_to_step": sum(by_phase.values()) == total,
           **{p: by_phase[p] / n / 1e6 for p in PHASES}}
    for phase in ("pre", "post"):
        by_span: dict[str, int] = {}
        for c in cuts:
            for (p, name), ns in c["parts"].items():
                if p == phase:
                    by_span[name] = by_span.get(name, 0) + ns
        out[f"{phase}_by_span"] = {
            name: ns / n / 1e6
            for name, ns in sorted(by_span.items(), key=lambda kv: -kv[1])}
    return out


def busy_inside(busy, starts, a: int, b: int) -> int:
    """ns of the sorted disjoint ``busy`` intervals inside ``[a, b]``."""
    i = max(0, bisect.bisect_right(starts, a) - 1)
    total = 0
    while i < len(busy) and busy[i][0] < b:
        total += max(0, min(busy[i][1], b) - max(busy[i][0], a))
        i += 1
    return total


def slow_steps(decode, busy, spans) -> tuple:
    """``(rows, excess_ns)``: the decode steps over ``SLOW`` medians, each
    with the (phase, span) piece that holds most of its excess over that
    piece's median over all decode steps."""
    median = statistics.median(c["ns"] for c in decode)
    slow = [c for c in decode if c["ns"] > SLOW * median]
    if not slow:
        return [], 0
    keys = {k for c in decode for k in c["parts"]}
    medians = {k: statistics.median(c["parts"].get(k, 0) for c in decode)
               for k in keys}
    starts = [a for a, _ in busy]
    collections = [s for s in spans if s[0] == GC]
    rows = []
    for c in slow:
        (phase, name), over = max(
            ((k, v - medians[k]) for k, v in c["parts"].items()),
            key=lambda kv: kv[1])
        a, b = c["span"]
        rows.append({
            "seq": c["seq"], "wall_ms": c["ns"] / 1e6, "cpu_ms": c["cpu_ms"],
            "median_step_ms": median / 1e6, "phase": phase, "span": name,
            "excess_there_ms": over / 1e6,
            "phases_ms": {p: c["phases"][p] / 1e6 for p in PHASES},
            "device_busy_ms": busy_inside(busy, starts, a, b) / 1e6,
            "gc": [{"generation": g[4].get("generation"),
                    "collected": g[4].get("collected"),
                    "ms": (g[2] - g[1]) / 1e6}
                   for g in collections if g[1] < b and g[2] > a]})
    return rows, sum(c["ns"] - median for c in slow)


def waterfall(spans, modules, ops, lo: int, hi: int,
              executions=()) -> dict | None:
    """Every number of this reader from plain lists: the program's spans,
    one device's module and op events, the window, the runtime's sorted
    ``(enqueued, completed)`` host instants (:func:`host_events`). None where
    there is no ``serve.arrays`` span or no decode step to cut."""
    if not any(s[0] == ARRAYS for s in spans):
        return None
    steps = program_span.steps_with_children(spans, lo, hi)
    joined, skipped = join(steps, modules, executions)
    if not joined:
        return None
    least, most = clock_slack(joined)
    shift = clock_shift(least, most)
    cuts = [cut(*j[:5], shift=shift) for j in joined]
    decode = [c for c in cuts if not c["chunk"]]
    chunk = [c for c in cuts if c["chunk"]]
    if not decode:
        return None
    busy = [(a + shift, b + shift)
            for a, b in trace_reduce.busy_and_gaps(ops, lo - shift,
                                                   hi - shift)[0]]
    starts = [a for a, _ in busy]
    builds = [c for _, children in steps for c in children if c[0] == BUILD]
    uploads = [c for _, children in steps for c in children
               if c[0] == UPLOAD]
    reasons: dict[str, int] = {}
    for build in builds:
        reason = str(build[4].get("reason", "none"))
        reasons[reason] = reasons.get(reason, 0) + 1
    rows, excess_ns = slow_steps(decode, busy, spans)
    n = len(decode)

    def per_decode_step(name):
        return sum(ns for c in decode for (_, s), ns in c["parts"].items()
                   if s == name) / n / 1e6

    tables = {"decode": table(decode),
              "decode_rebuilt": table([c for c in decode if c["rebuilt"]]),
              "decode_quiet": table([c for c in decode if not c["rebuilt"]]),
              "chunk": table(chunk)}
    stats = {
        "launch_ms_per_step": tables["decode"]["launch"],
        "readback_ms_per_step": tables["decode"]["readback"],
        "upload_ms_per_step": per_decode_step(UPLOAD),
        "arrays_ms_per_step": per_decode_step(ARRAYS),
        "rebuild_steps_pct": 100.0 * sum(c["rebuilt"] for c in decode) / n,
        "slow_step_excess_pct": 100.0 * excess_ns / (hi - lo),
        "chunk_step_gap_ms": (sum(
            c["ns"] - busy_inside(busy, starts, *c["span"])
            for c in chunk) / len(chunk) / 1e6 if chunk else None),
    }
    return {
        "stats": stats,
        "step_waterfall": {
            **{k: v for k, v in tables.items() if v is not None},
            "steps": len(steps), "steps_skipped": skipped,
            "steps_anchored_by_runtime_events": sum(
                j[5] != (j[2][1], j[3][2]) for j in joined),
            "clock_slack_ms": [least / 1e6, most / 1e6],
            "device_clock_shift_ms": shift / 1e6},
        "rebuild_reasons": {
            "rebuild_reasons": dict(sorted(reasons.items(),
                                           key=lambda kv: -kv[1])),
            "builds": len(builds),
            "upload_bytes_per_build": (sum(
                float(u[4].get("bytes", 0)) for u in uploads) / len(builds)
                if builds else None),
            "arrays_per_build": (sum(
                float(u[4].get("arrays", 0)) for u in uploads) / len(builds)
                if builds else None)},
        "slow_steps": rows,
    }


def host_events(path) -> tuple:
    """One pass over the host planes: the program's spans as
    ``_xplane.program_spans`` gives them, and the sorted ``(enqueued,
    completed)`` ns of the executions the runtime's own events bracket
    (``DoEnqueueProgram`` and ``CompleteCallbacks`` starts, paired by
    ``run_id``; none where the runtime emits no such events)."""
    from jax.profiler import ProfileData

    prefix = _xplane.PROGRAM_PREFIX
    spans, enqueued, completed = [], {}, {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name.startswith(prefix):
                    spans.append((name[len(prefix):], int(e.start_ns),
                                  int(e.start_ns + e.duration_ns), line.name,
                                  dict(e.stats)))
                elif name in (ENQUEUE, COMPLETE):
                    into = enqueued if name == ENQUEUE else completed
                    into[dict(e.stats).get("run_id")] = int(e.start_ns)
    return spans, sorted((a, completed[k]) for k, a in enqueued.items()
                         if k in completed)


def reduce(ctx) -> dict | None:
    """Worked out and printed once a run."""
    if "step_waterfall_stats" in ctx:
        return ctx["step_waterfall_stats"]
    found, stats = _xplane.traced(ctx), None
    if found is not None:
        trace, path = found
        worst = max(trace["per_device"],
                    key=lambda d: trace["per_device"][d]["idle_share"])
        spans, executions = host_events(path)
        got = waterfall(spans, trace["device_modules"].get(worst, ()),
                        trace["device_ops"][worst],
                        trace["lo_ns"], trace["hi_ns"], executions)
        if got is not None:
            stats = got["stats"]
            print(json.dumps({"step_waterfall": got["step_waterfall"]}),
                  flush=True)
            print(json.dumps(got["rebuild_reasons"]), flush=True)
            print(json.dumps({"slow_steps": got["slow_steps"]}), flush=True)
    ctx["step_waterfall_stats"] = stats
    return stats


def read(ctx, params):
    found = reduce(ctx)
    return None if found is None else found[params["stat"]]
