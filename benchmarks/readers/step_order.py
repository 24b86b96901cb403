"""Which order each engine step took, what kept it from pipelining, and what
admission did, from the program's own statistics in the traced window
(``distributed_training_guide_tpu/utils/trace.py``): ``serve.step``'s
``order`` (``STEP_ORDERS``), ``serve.quiet``'s ``held_by`` (``NOT_QUIET``; a
quiet test arrives without it: the profiler keeps no empty statistic) and
``serve.admit``'s ``admitted`` / ``blocked_by`` / ``queue_ms``.

``stat`` picks the number:

- ``pipelined_steps_pct``: of the window's DECODE steps (no ``serve.prefill``
  inside, ``program_span.decode_steps``), those whose ``order`` is
  ``pipelined``.
- ``queue_blocked_steps_pct``: of ALL the window's engine steps, those that
  hold a ``serve.admit`` with ``admitted`` 0 (the queue's head waited there).
- ``queue_wait_ms_p50``: the median ``queue_ms`` of the ``serve.admit`` spans
  with ``admitted`` 1, each request once; None under ``MIN_ADMISSIONS``.
- ``host_slack_ms_per_step``: over the pipelined steps, how long the device's
  queue held the program THAT step enqueued before it started: its start on
  the worst device's ``XLA Modules`` line minus the end of the step's
  ``serve.dispatch``, mean. 0 is the host setting the pace again (not
  clamped: ``host_slack``).

The join for the last is by ORDER, not by overlap (``step_waterfall.join``
takes the execution that overlaps a step's round trip most, which in a
pipelined step is the program in flight and not the one the step enqueued).
Programs of one stream run in the order they were enqueued and are read in
that order: the n-th ``serve.wait`` whose ``waits_for`` names step ``s`` read
the n-th plain program ``s``'s ``serve.dispatch`` enqueued (``programs``: 1,
or 2 where the step entered the pipeline). ONE wait anchors the count on the
device's line (the execution whose end lies nearest its end), every other
program follows by counting, and every other wait checks it: the program it
read ended before the wait did and the one after it had not
(``paired_waits_out_of_order``). The two clocks differ by a millisecond or
two: ``clock_slack_ms`` is what causality leaves open, as
``step_waterfall.py`` prints it (a program starts after the dispatch that
enqueued it and ends before the wait that read it), and the device's line is
moved by the least that restores it.

Printed once a run, on an earlier line: ``{"step_orders": {<order>: {"steps",
"ms_mean", "device_idle_ms_mean"}}, "not_quiet": {<cause>: n}, "admission":
{"admitted", "blocked": {<cause>: n}, "blocked_steps", "blocked_pages_p50":
{"need", "free", "headroom"}, "queue_ms_p50", "queue_ms_p95"}, "host_slack":
{...}, "clock_slack_ms"}``. No ``serve.step`` with an ``order`` in the window,
as on the parent of the PR that added it: None, and nothing is printed.
"""
import bisect
import collections
import json
import statistics

from benchmarks.readers import _xplane, program_span
from benchmarks.readers.step_waterfall import busy_inside, clock_shift
from benchmarks.traffic.generate import percentile

QUIET, ADMIT = "serve.quiet", "serve.admit"
DISPATCH, WAIT = "serve.dispatch", "serve.wait"
PLAIN = "serve_decode"
MIN_ADMISSIONS = 20
NEAR_NS = 5_000_000     # an anchor's program ends this near its wait's end


def named(children, name) -> list:
    return sorted((c for c in children if c[0] == name), key=lambda c: c[1])


# ---- the step's order, and what held it -------------------------------------
def by_order(steps, gaps) -> dict:
    """Over ``steps_with_children``'s steps, with ``gaps`` the worst
    device's sorted idle intervals: ``{order: {steps, ms_mean,
    device_idle_ms_mean}}``, the commonest order first."""
    starts = [a for a, _ in gaps]
    rows: dict[str, list] = {}
    for step, _ in steps:
        row = rows.setdefault(str(step[4].get("order")), [0, 0, 0])
        row[0] += 1
        row[1] += step[2] - step[1]
        row[2] += busy_inside(gaps, starts, step[1], step[2])
    return {order: {"steps": n, "ms_mean": ns / n / 1e6,
                    "device_idle_ms_mean": idle / n / 1e6}
            for order, (n, ns, idle) in sorted(
                rows.items(), key=lambda kv: -kv[1][0])}


def first_held(children):
    """``held_by`` of the step's first quiet test that failed, else None."""
    return next((str(q[4]["held_by"]) for q in named(children, QUIET)
                 if q[4].get("held_by")), None)


def not_quiet(steps) -> dict:
    """Steps by the FIRST thing that kept each from pipelining."""
    out: dict[str, int] = {}
    for _, children in steps:
        held = first_held(children)
        if held is not None:
            out[held] = out.get(held, 0) + 1
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def admission(steps) -> dict:
    """The window's admission attempts: admitted, blocked by cause, the wait
    of those admitted (each request once), the steps with a refusal, and of
    the refusals by ``pages`` the medians of what the head needed, what the
    pool had free and the headroom it keeps for the running decodes (the
    head gets in where ``free - headroom >= need``)."""
    waits, blocked, blocked_steps = [], {}, 0
    pages = {"need": [], "free": [], "headroom": []}
    for _, children in steps:
        attempts = named(children, ADMIT)
        refused = [a for a in attempts if not int(a[4].get("admitted", 1))]
        blocked_steps += bool(refused)
        for a in refused:
            cause = str(a[4].get("blocked_by"))
            blocked[cause] = blocked.get(cause, 0) + 1
            for key, seen in pages.items():
                if key in a[4]:
                    seen.append(int(a[4][key]))
        waits += [float(a[4]["queue_ms"]) for a in attempts
                  if int(a[4].get("admitted", 0))]
    return {"admitted": len(waits), "blocked": blocked,
            "blocked_steps": blocked_steps,
            "blocked_pages_p50": {key: statistics.median(seen)
                                  for key, seen in pages.items() if seen},
            "queue_ms_p50": percentile(waits, 0.5) if waits else None,
            "queue_ms_p95": percentile(waits, 0.95) if waits else None}


# ---- the enqueue-order join -------------------------------------------------
def enqueued(steps) -> list:
    """``[(seq, dispatch)]``, one entry a plain decode program in the order
    the window's steps enqueued them (an entering step's dispatch twice)."""
    out = []
    for step, children in steps:
        for d in named(children, DISPATCH):
            if str(d[4].get("program", "")) == PLAIN:
                out += [(int(step[4]["seq"]), d)] * int(
                    d[4].get("programs", 1))
    return out


def paired_waits(steps, programs) -> list:
    """``[(index into programs, wait)]``: the n-th wait that names step
    ``s`` read the n-th program ``s`` enqueued. A wait that names a step
    outside the window, or another program's wait, has no pair."""
    first: dict[int, int] = {}
    for i, (seq, _) in enumerate(programs):
        first.setdefault(seq, i)
    count = collections.Counter(seq for seq, _ in programs)
    taken: dict[int, int] = {}
    out = []
    for _, children in steps:
        for w in named(children, WAIT):
            seq = w[4].get("waits_for")
            if seq is None or int(seq) not in first:
                continue
            seq = int(seq)
            n = taken.get(seq, 0)
            if n < count[seq]:
                taken[seq] = n + 1
                out.append((first[seq] + n, w))
    return out


def join_in_order(steps, runs) -> dict | None:
    """``{"run_of": {program index: (start, end)}, "least", "most",
    "out_of_order"}``: the window's plain decode programs with their
    executions among the sorted ``runs`` of ``jit_serve_decode``, by order;
    None where no wait anchors the count."""
    programs = enqueued(steps)
    waits = paired_waits(steps, programs)
    ends = [b for _, b in runs]
    offset = None
    for index, wait in waits:
        # the anchor: the execution that ends nearest this wait's end
        i = bisect.bisect_left(ends, wait[2])
        near = min((j for j in (i - 1, i) if 0 <= j < len(runs)),
                   key=lambda j: abs(ends[j] - wait[2]), default=None)
        if near is not None and abs(ends[near] - wait[2]) <= NEAR_NS:
            offset = near - index
            break
    if offset is None:
        return None
    run_of = {i: runs[i + offset] for i in range(len(programs))
              if 0 <= i + offset < len(runs)}
    # what causality leaves open, over every program with both its ends
    least = max((programs[i][1][1] - run[0] for i, run in run_of.items()),
                default=0)
    most = min((wait[2] - run_of[i][1] for i, wait in waits if i in run_of),
               default=0)
    shift = clock_shift(least, most)
    out_of_order = 0
    for i, wait in waits:
        if i not in run_of:
            continue
        nxt = run_of.get(i + 1)
        if run_of[i][1] + shift > wait[2] or (
                nxt is not None and nxt[1] + shift <= wait[2]):
            out_of_order += 1
    return {"programs": programs, "run_of": run_of, "least": least,
            "most": most, "shift": shift, "paired_waits": len(waits),
            "out_of_order": out_of_order}


def host_slack(steps, runs) -> dict | None:
    """Over the pipelined steps whose program is on the device's line: ms
    from the end of the step's dispatch to the start of the program it
    enqueued. Not clamped: a program may start while the dispatch that
    enqueued it is still open (the device was waiting for it), which reads
    negative by a part of that span; ``steps_negative`` counts those, and
    one further below 0 than a dispatch lasts is a join off by one or a
    wrong clock shift."""
    joined = join_in_order(steps, runs)
    if joined is None:
        return None
    pipelined = {int(step[4]["seq"]) for step, _ in steps
                 if step[4].get("order") == "pipelined"}
    slack = [(joined["run_of"][i][0] + joined["shift"] - d[2]) / 1e6
             for i, (seq, d) in enumerate(joined["programs"])
             if seq in pipelined and i in joined["run_of"]]
    return {"steps": len(slack),
            "ms_mean": statistics.fmean(slack) if slack else None,
            "ms_p50": statistics.median(slack) if slack else None,
            "ms_min": min(slack) if slack else None,
            "steps_negative": sum(ms < 0 for ms in slack),
            "programs_joined": len(joined["run_of"]),
            "paired_waits": joined["paired_waits"],
            "paired_waits_out_of_order": joined["out_of_order"],
            "clock_slack_ms": [joined["least"] / 1e6, joined["most"] / 1e6],
            "device_clock_shift_ms": joined["shift"] / 1e6}


# ---- one window -------------------------------------------------------------
def orders(spans, modules, gaps, lo: int, hi: int) -> dict | None:
    """Every number of this reader from plain lists: the program's spans,
    one device's module events and sorted idle intervals, the window."""
    steps = program_span.steps_with_children(spans, lo, hi)
    if not any("order" in step[4] for step, _ in steps):
        return None
    decode = program_span.decode_steps(steps)
    runs = sorted((a, b) for name, a, b in modules
                  if name.startswith(f"jit_{PLAIN}("))
    admitted = admission(steps)
    slack = host_slack(steps, runs)
    stats = {
        "pipelined_steps_pct": (100.0 * sum(
            step[4].get("order") == "pipelined" for step, _ in decode)
            / len(decode) if decode else None),
        "queue_blocked_steps_pct":
            100.0 * admitted["blocked_steps"] / len(steps),
        "queue_wait_ms_p50": (admitted["queue_ms_p50"]
                              if admitted["admitted"] >= MIN_ADMISSIONS
                              else None),
        "host_slack_ms_per_step": slack["ms_mean"] if slack else None,
    }
    return {"stats": stats, "line": {
        "step_orders": by_order(steps, gaps),
        "not_quiet": not_quiet(steps),
        "admission": admitted,
        "clock_slack_ms": slack.pop("clock_slack_ms") if slack else None,
        "host_slack": slack,
        "steps": len(steps), "decode_steps": len(decode)}}


def reduce(ctx) -> dict | None:
    """Worked out and printed once a run."""
    if "step_order_stats" in ctx:
        return ctx["step_order_stats"]
    found, stats = _xplane.traced(ctx), None
    if found is not None:
        trace, path = found
        worst = max(trace["per_device"],
                    key=lambda d: trace["per_device"][d]["idle_share"])
        got = orders(_xplane.program_spans(path),
                     trace["device_modules"].get(worst, ()),
                     program_span.worst_device_gaps(trace),
                     trace["lo_ns"], trace["hi_ns"])
        if got is not None:
            stats = got["stats"]
            print(json.dumps(got["line"]), flush=True)
    ctx["step_order_stats"] = stats
    return stats


def read(ctx, params):
    found = reduce(ctx)
    return None if found is None else found[params["stat"]]
