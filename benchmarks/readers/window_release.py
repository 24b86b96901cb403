"""``serve.window_pages_released_per_step``: pages of the window page class
that the scheduler returned to its free list, a decode step, from the
program's own span: every return is a ``dtg.serve.release`` span whose
``pages`` statistic is the count (``serve/scheduler.py``), summed over the
spans inside the traced window's decode steps (the ``serve.step`` spans with
no prefill inside, ``program_span.py``'s) and divided by their number. None
where there is nothing to read: no trace, no ``serve.step``, or a program
that has no such span (one page class; the parent of the PR that added the
second).
"""
from benchmarks.readers import _xplane, program_span


def read(ctx, params):
    found = _xplane.traced(ctx)
    if found is None:
        return None
    trace, path = found
    spans = _xplane.program_spans(path)
    if not any(s[0] == params["span"] for s in spans):
        return None
    steps = program_span.decode_steps(program_span.steps_with_children(
        spans, trace["lo_ns"], trace["hi_ns"]))
    if not steps:
        return None
    pages = sum(float(child[4].get(params["stat"], 0))
                for _, children in steps for child in children
                if child[0] == params["span"])
    return pages / len(steps)
