"""From a profiler trace (``.xplane.pb``) to numbers: the reduction that every
PR's per-layer device metrics go through.

``jax.profiler.ProfileData`` reads the file with nothing but JAX. A TPU trace
has one plane per chip (``/device:TPU:<n>``) whose ``XLA Ops`` line holds one
event per executed HLO operation (nested where an operation such as a
``while`` contains others) and whose ``XLA Modules`` line holds one event per
executed program (``jit_serve_decode(<id>)``), and host planes whose lines are threads; the
benchmark's own spans are ``TraceAnnotation`` events named ``bench.<span>`` on
a host thread, on the same clock.

All functions work on plain lists of ``(name, start_ns, end_ns)`` so that a
hand-made trace tests them.
"""
from __future__ import annotations

import bisect
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
BENCH_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)")


# ---- interval arithmetic ---------------------------------------------------
def union(intervals) -> list:
    """Merged, sorted, disjoint intervals."""
    out = []
    for a, b in sorted((a, b) for a, b in intervals if b > a):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def measure(intervals) -> int:
    return sum(b - a for a, b in intervals)


def clip(intervals, lo, hi) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a, b) -> list:
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def self_times(events) -> list:
    """``(name, self_ns, start, end)`` per event of ONE line: an event's
    duration minus what the events nested wholly inside it cover. Events that
    overlap without nesting are siblings."""
    out, stack = [], []
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and (stack[-1][2] <= a or stack[-1][2] < b):
            out.append(_pop(stack))
        stack.append([name, a, b, 0])
    while stack:
        out.append(_pop(stack))
    return out


def _pop(stack):
    name, a, b, covered = stack.pop()
    if stack:
        stack[-1][3] += b - a
    return (name, max(0, b - a - covered), a, b)


# ---- the reductions --------------------------------------------------------
def busy_and_gaps(ops, lo, hi):
    """Busy union of one device's op events inside [lo, hi] and its idle gaps."""
    busy = union(clip([(a, b) for _, a, b in ops], lo, hi))
    return busy, subtract([(lo, hi)], busy)


def exposed_collective_ns(ops, lo, hi) -> int:
    """Time inside collective operations during which no other operation runs
    on that device."""
    leaves = self_leaf_intervals(ops)
    coll = union(clip([(a, b) for n, a, b in leaves if is_collective(n)],
                      lo, hi))
    other = union(clip([(a, b) for n, a, b in leaves
                        if not is_collective(n)], lo, hi))
    return measure(subtract(coll, other))


def is_collective(name: str) -> bool:
    """By the instruction's own result name (``%all-gather-start.3 = ...``):
    the rest of an event's name lists its operands, and a fusion that merely
    consumes ``%all-gather-done.3`` is not a collective."""
    return bool(COLLECTIVE.search(name.partition(" = ")[0]))


def self_leaf_intervals(ops) -> list:
    """Events that contain no other event (the operations that do the work;
    a ``while`` or ``conditional`` that wraps them is left out)."""
    out = []
    for name, self_ns, a, b in self_times(ops):
        if self_ns == b - a:
            out.append((name, a, b))
    return out


def attribute_gaps(gaps, host_spans) -> dict:
    """Idle seconds by the benchmark span (``bench.<name>``) that covers most
    of each gap; ``outside`` where none does."""
    spans = sorted(host_spans, key=lambda s: s[1])
    totals: dict[str, float] = {}
    for lo, hi in gaps:
        best, best_cover = "outside", 0
        for name, a, b in spans:
            if a >= hi:
                break
            cover = min(b, hi) - max(a, lo)
            if cover > best_cover:
                best, best_cover = name, cover
        totals[best] = totals.get(best, 0.0) + (hi - lo) / 1e9
    return totals


def op_totals(ops, lo, hi) -> dict:
    """Self seconds by operation name inside the window."""
    totals: dict[str, float] = {}
    for name, self_ns, a, b in self_times(ops):
        if a >= lo and b <= hi and self_ns:
            totals[name] = totals.get(name, 0.0) + self_ns / 1e9
    return totals


def kernel_events(ops, pattern: str, lo=None, hi=None) -> list:
    """Leaf events whose name matches ``pattern`` (a regular expression)."""
    rx = re.compile(pattern)
    return [(n, a, b) for n, a, b in self_leaf_intervals(ops)
            if rx.search(n) and (lo is None or (a >= lo and b <= hi))]


def spans_inside(trace: dict, name: str) -> int:
    """How many of the benchmark's ``name`` spans lie inside the reduced
    trace's window: the steps a per-step number is divided by."""
    return sum(1 for n, a, b in trace["host_spans"]
               if n == name and a >= trace["lo_ns"] and b <= trace["hi_ns"])


def program_runs(modules, program: str, lo, hi) -> list:
    """``(start, end)`` of each execution of the jitted program ``program``
    (``serve_decode`` is the module ``jit_serve_decode(<id>)``) inside
    [lo, hi], sorted: what tells a decode step's events from a prefill
    chunk's, which run the same kernels."""
    prefix = f"jit_{program}("
    return sorted((a, b) for name, a, b in modules
                  if name.startswith(prefix) and a >= lo and b <= hi)


def under(runs):
    """A test of ``(start, end)``: inside one of the sorted disjoint ``runs``."""
    starts = [a for a, _ in runs]

    def inside(a, b):
        i = bisect.bisect_right(starts, a) - 1
        return i >= 0 and b <= runs[i][1]
    return inside


def program_ops(trace: dict, program: str) -> tuple:
    """The reduced trace's op events that ran under the jitted program
    ``program``, per device, and how often it ran (a device)."""
    lo, hi = trace["lo_ns"], trace["hi_ns"]
    ops, n_runs = {}, 0
    for d, events in trace["device_ops"].items():
        runs = program_runs(trace["device_modules"].get(d, ()), program, lo, hi)
        inside = under(runs)
        ops[d] = [e for e in events if inside(e[1], e[2])]
        n_runs += len(runs)
    return ops, n_runs // max(1, len(ops))


def programs_run(trace: dict) -> set:
    """Names of the jitted programs the traced devices ran (``jit_`` and the
    id taken off)."""
    return {m.group(1) for mods in trace["device_modules"].values()
            for name, _, _ in mods
            if (m := re.match(r"jit_(.+)\(\d+\)$", name))}


def short_name(name: str) -> str:
    """An HLO event's name cut to what tells operations apart: the result's
    name and the opcode or custom-call target (the trace prints the whole
    instruction, hundreds of characters)."""
    lhs, _, rhs = name.partition(" = ")
    target = re.search(r'custom_call_target="([^"]+)"', rhs)
    opcode = re.search(r"(?:^|[\s}])([a-z][\w-]*)\(", rhs)
    kind = target.group(1) if target else (opcode.group(1) if opcode else "")
    return f"{lhs.lstrip('%')} {kind}".strip()[:120]


# ---- reading a trace -------------------------------------------------------
def read_planes(path: Path):
    """``(device_ops, host_spans, device_modules)``: per device index the op
    events, the benchmark's own spans from the host planes, and per device
    index the program executions."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    device_ops, host_spans, device_modules = {}, [], {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                into = {OPS_LINE: device_ops,
                        MODULES_LINE: device_modules}.get(line.name)
                if into is not None:
                    into[int(m.group(1))] = [
                        (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(BENCH_PREFIX):
                        host_spans.append(
                            (e.name[len(BENCH_PREFIX):], int(e.start_ns),
                             int(e.start_ns + e.duration_ns)))
    return device_ops, host_spans, device_modules


def find_xplane(trace_dir: Path):
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    return found[-1] if found else None


def reduce_events(device_ops: dict, host_spans: list, n_devices: int,
                  device_modules: dict | None = None) -> dict | None:
    """All the device numbers of one traced window. The window runs from the
    first benchmark span's start to the last one's end (the profiler's clock);
    without spans, from the first device event to the last."""
    device_ops = {k: v for k, v in device_ops.items() if v}
    if not device_ops:
        return None
    used = sorted(device_ops)[:n_devices]
    if host_spans:
        lo = min(a for _, a, _ in host_spans)
        hi = max(b for _, _, b in host_spans)
    else:
        lo = min(a for d in used for _, a, _ in device_ops[d])
        hi = max(b for d in used for _, _, b in device_ops[d])
    window_s = (hi - lo) / 1e9
    busy_s, exposed, per_device = [], [], {}
    worst_idle, worst_gaps = -1.0, []
    for d in used:
        busy, gaps = busy_and_gaps(device_ops[d], lo, hi)
        b = measure(busy) / 1e9
        busy_s.append(b)
        exposed.append(exposed_collective_ns(device_ops[d], lo, hi) / 1e9)
        idle = 1.0 - b / window_s
        per_device[d] = {"busy_s": b, "idle_share": idle}
        if idle > worst_idle:
            worst_idle, worst_gaps = idle, gaps
    totals: dict[str, float] = {}
    for d in used:
        for name, s in op_totals(device_ops[d], lo, hi).items():
            totals[name] = totals.get(name, 0.0) + s / len(used)
    gaps_by_span = attribute_gaps(worst_gaps, host_spans)
    return {
        "window_s": window_s, "lo_ns": lo, "hi_ns": hi,
        "busy_s": sum(busy_s) / len(busy_s),
        "idle_share_worst": worst_idle,
        "exposed_collective_s_worst": max(exposed),
        "per_device": per_device, "devices": used,
        "top_ops": [[short_name(n), s] for n, s in sorted(
            totals.items(), key=lambda kv: -kv[1])],
        "top_gaps": [[n, s] for n, s in sorted(gaps_by_span.items(),
                                               key=lambda kv: -kv[1])],
        "device_ops": {d: device_ops[d] for d in used},
        "device_modules": {d: (device_modules or {}).get(d, [])
                           for d in used},
        "host_spans": host_spans,
    }


def reduce_dir(trace_dir: Path, n_devices: int) -> dict | None:
    path = find_xplane(trace_dir)
    if path is None:
        return None
    device_ops, host_spans, device_modules = read_planes(path)
    return reduce_events(device_ops, host_spans, n_devices, device_modules)
