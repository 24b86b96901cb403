"""What ``jax.profiler.ProfileData`` does not hand out, read from the same
``.xplane.pb``: the statistics of an event's METADATA.

A traced v5e run keeps an operation's scope path there and nowhere else: the
``XLA Ops`` events carry their time only, the device plane has no ``Framework
Name Scope`` line, and the event's name is the HLO instruction without its
``metadata={op_name=...}``. The path is the ``tf_op`` statistic of the event
metadata, e.g. ``jit(train_step)/transpose(jvp())/while/body/closed_call/
checkpoint/rematted_computation/attn/flash_fwd/pallas_call:`` (beside
``source``, ``hlo_category``, ``flops``, ``bytes_accessed``). The file is a
protocol buffer; the few fields needed are read here from the wire format, so
the benchmark needs nothing beyond JAX:

    XSpace.planes = 1
    XPlane: name = 2, lines = 3 (skipped whole), event_metadata = 4 and
            stat_metadata = 5 (maps: key = 1, value = 2)
    XEventMetadata: name = 2, stats = 5;  XStatMetadata: name = 2
    XStat: metadata_id = 1, str_value = 5, ref_value = 7 (a stat_metadata id
           whose name is the string)

Also here, because every new reader needs them: matching a scope inside a
path, and the program's host spans (``dtg.<name>``) with their thread.
"""
from __future__ import annotations

import re
from pathlib import Path

from benchmarks import trace_reduce

PROGRAM_PREFIX = "dtg."
REMAT = "rematted_computation"
_WRAPPED = re.compile(r"^(?:[\w.\-]+\()*([^()]*)\)*$")


# ---- the wire format --------------------------------------------------------
def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, start, end):
    """``(field, wire_type, value)`` of one message; a length-delimited value
    is its ``(start, end)`` inside ``buf``."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield field, wire, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    key, value = 0, None
    for field, wire, v in _fields(buf, *span):
        if field == 1 and wire == 0:
            key = v
        elif field == 2 and wire == 2:
            value = v
    return key, value


def metadata_stat(path: Path, stat: str = "tf_op") -> dict:
    """``{plane name: {event name: value of `stat`}}`` for every event
    metadata that has the statistic (a string, inline or by reference)."""
    buf = memoryview(Path(path).read_bytes())
    out: dict[str, dict] = {}
    for field, wire, plane in _fields(buf, 0, len(buf)):
        if field != 1 or wire != 2:
            continue
        name, events, stat_names = "", [], {}
        for f, w, v in _fields(buf, *plane):
            if w != 2:
                continue
            if f == 2:
                name = _text(buf, v)
            elif f == 4:
                events.append(_map_entry(buf, v)[1])
            elif f == 5:
                key, md = _map_entry(buf, v)
                for g, gw, gv in _fields(buf, *md):
                    if g == 2 and gw == 2:
                        stat_names[key] = _text(buf, gv)
        wanted = {k for k, n in stat_names.items() if n == stat}
        found = {}
        for md in events:
            if md is None:
                continue
            ev_name, value = "", None
            for f, w, v in _fields(buf, *md):
                if f == 2 and w == 2:
                    ev_name = _text(buf, v)
                elif f == 5 and w == 2:
                    sid, text = 0, None
                    for g, gw, gv in _fields(buf, *v):
                        if g == 1 and gw == 0:
                            sid = gv
                        elif g == 5 and gw == 2:
                            text = _text(buf, gv)
                        elif g == 7 and gw == 0:
                            text = stat_names.get(gv, "")
                    if sid in wanted and text is not None:
                        value = text
            if value is not None:
                found[ev_name] = value
        if found:
            out[name] = found
    return out


# ---- scopes inside a path ---------------------------------------------------
def components(op_path: str) -> list:
    """The path's components with their wrappers taken off:
    ``jit(train_step)/transpose(jvp(attn))/while`` -> train_step, attn,
    while. A scope arrives bare (``.../closed_call/attn/...``) or wrapped
    (``transpose(jvp(loss_head))``), so both are looked at the same way."""
    out = []
    for part in op_path.rstrip(":").split("/"):
        m = _WRAPPED.match(part)
        out.append(m.group(1) if m else part)
    return out


def scope_of(op_path: str, scopes) -> str | None:
    """The innermost of ``scopes`` that is a component of the path."""
    for comp in reversed(components(op_path)):
        if comp in scopes:
            return comp
    return None


def is_recompute(op_path: str) -> bool:
    """Forward work run a second time: under ``jax.checkpoint``'s
    ``rematted_computation`` wrapper."""
    return REMAT in components(op_path)


# ---- a run's trace -----------------------------------------------------------
def traced(ctx):
    """``(reduced trace, path of its .xplane.pb)`` of a traced run on a
    device, else None."""
    if ctx.get("trace") is None or ctx.get("trace_dir") is None:
        return None
    path = trace_reduce.find_xplane(ctx["trace_dir"])
    return None if path is None else (ctx["trace"], path)


# ---- the program's host spans ----------------------------------------------
def program_spans(path: Path) -> list:
    """``(name, start_ns, end_ns, thread, stats)`` of every host event named
    ``dtg.<name>`` (``distributed_training_guide_tpu/utils/trace.py``), the
    prefix taken off."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIX):
                    out.append((e.name[len(PROGRAM_PREFIX):], int(e.start_ns),
                                int(e.start_ns + e.duration_ns), line.name,
                                dict(e.stats)))
    return out
