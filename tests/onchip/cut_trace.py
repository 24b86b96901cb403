"""Cut a few engine steps out of a traced serve run's ``.xplane.pb``: how
the recorded fixtures under ``benchmarks/testdata/`` are made (not collected
by pytest; runs anywhere the trace is).

    python tests/onchip/cut_trace.py <in.xplane.pb> <out.xplane.pb> \
        --steps 412:419 [--host-events REGEX]

keeps, of the first device plane, the ``XLA Modules`` and ``XLA Ops`` events
and, of ``/host:CPU``, the events whose name matches ``--host-events``
(default: the program's ``dtg.`` spans, the benchmark's ``bench.`` spans and
the runtime's ``DoEnqueueProgram`` / ``CompleteCallbacks``, on whatever
thread; the Python tracer's events on the spans' own thread are most of a
host line), from the start of the
``dtg.serve.step`` whose ``seq`` is the first number to the end of the one
whose ``seq`` is the second; the device's events from 2 ms earlier, because
its line may lie that far ahead of the host's
(``benchmarks/readers/step_waterfall.py``). Event and statistic metadata that
no kept event refers to is dropped: the names of thousands of HLO
instructions are most of a trace's bytes. The file is rewritten on the wire
format (``benchmarks/readers/_xplane.py`` has the field numbers); nothing
beyond JAX is needed.
"""
import argparse
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.readers import _xplane  # noqa: E402
from benchmarks.readers._xplane import _fields, _text  # noqa: E402

DEVICE_EARLY_NS = 2_000_000
DEVICE_LINES = ("XLA Modules", "XLA Ops")
HOST_EVENTS = r"^(dtg\.|bench\.|DoEnqueueProgram$|CompleteCallbacks$)"


def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(number: int, payload: bytes) -> bytes:
    """A length-delimited field."""
    return varint(number << 3 | 2) + varint(len(payload)) + payload


def stat_refs(buf, span) -> set:
    """Statistic-metadata ids an XStat names: its own and a ``ref_value``."""
    out = set()
    for f, w, v in _fields(buf, *span):
        if w == 0 and f in (1, 7):
            out.add(v)
    return out


def cut_line(buf, span, lo: int, hi: int, wanted=None):
    """``(name, bytes of the line with the events inside [lo, hi] (of the
    ``wanted`` event metadata ids, if given), event metadata ids kept,
    statistic ids kept)``."""
    name, t0 = "", 0
    for f, w, v in _fields(buf, *span):
        if f == 2 and w == 2:
            name = _text(buf, v)
        elif f == 3 and w == 0:
            t0 = v
    out, events, stats = bytearray(), set(), set()
    for f, w, v in _fields(buf, *span):
        if w != 2:
            continue        # scalars are written below, from what was read
        if f != 4:
            out += field(f, bytes(buf[v[0]:v[1]]))
            continue
        meta, offset_ps, duration_ps, refs = 0, 0, 0, set()
        for g, gw, gv in _fields(buf, *v):
            if g == 1 and gw == 0:
                meta = gv
            elif g == 2 and gw == 0:
                offset_ps = gv
            elif g == 3 and gw == 0:
                duration_ps = gv
            elif g == 4 and gw == 2:
                refs |= stat_refs(buf, gv)
        start = t0 + offset_ps // 1000
        if start >= lo and start + duration_ps // 1000 <= hi \
                and (wanted is None or meta in wanted):
            out += field(4, bytes(buf[v[0]:v[1]]))
            events.add(meta)
            stats |= refs
    scalars = b"".join(varint(f << 3) + varint(v)
                       for f, w, v in _fields(buf, *span) if w == 0)
    return name, scalars + bytes(out), events, stats


def event_ids(buf, span, pattern) -> set:
    """Ids of the plane's event metadata whose name matches ``pattern``."""
    out = set()
    for f, w, v in _fields(buf, *span):
        if f == 4 and w == 2:
            key, md = _xplane._map_entry(buf, v)
            for g, gw, gv in _fields(buf, *md):
                if g == 2 and gw == 2 and pattern.search(_text(buf, gv)):
                    out.add(key)
    return out


def cut_plane(buf, span, lo: int, hi: int, keep_line=None,
              wanted=None) -> bytes:
    lines, events, stats = [], set(), set()
    for f, w, v in _fields(buf, *span):
        if f == 3 and w == 2:
            name, body, ev, st = cut_line(buf, v, lo, hi, wanted)
            if (keep_line is None or keep_line(name)) and ev:
                lines.append(body)
                events |= ev
                stats |= st
    out = bytearray()
    kept_meta = []
    for f, w, v in _fields(buf, *span):
        if w == 0:
            out += varint(f << 3) + varint(v)
        elif w == 2 and f == 4:
            key, md = _xplane._map_entry(buf, v)
            if key in events:
                kept_meta.append(v)
                for g, gw, gv in _fields(buf, *md):
                    if g == 5 and gw == 2:
                        stats |= stat_refs(buf, gv)
        elif w == 2 and f == 6:
            stats |= stat_refs(buf, v)
            out += field(f, bytes(buf[v[0]:v[1]]))
        elif w == 2 and f not in (3, 5):
            out += field(f, bytes(buf[v[0]:v[1]]))
    for v in kept_meta:
        out += field(4, bytes(buf[v[0]:v[1]]))
    for f, w, v in _fields(buf, *span):
        if f == 5 and w == 2 and _xplane._map_entry(buf, v)[0] in stats:
            out += field(5, bytes(buf[v[0]:v[1]]))
    for body in lines:
        out += field(3, body)
    return bytes(out)


def plane_name(buf, span) -> str:
    for f, w, v in _fields(buf, *span):
        if f == 2 and w == 2:
            return _text(buf, v)
    return ""


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("source", type=Path)
    parser.add_argument("target", type=Path)
    parser.add_argument("--steps", required=True,
                        help="first:last `seq` of the serve.step spans kept")
    parser.add_argument("--host-events", default=HOST_EVENTS,
                        help="regular expression over /host:CPU event names")
    args = parser.parse_args(argv)
    first, last = (int(x) for x in args.steps.split(":"))
    steps = {int(s[4]["seq"]): s for s in _xplane.program_spans(args.source)
             if s[0] == "serve.step"}
    lo, hi = steps[first][1] - 50_000, steps[last][2] + 50_000
    pattern = re.compile(args.host_events)
    buf = memoryview(args.source.read_bytes())
    out, device_done = bytearray(), False
    for f, w, v in _fields(buf, 0, len(buf)):
        if f != 1 or w != 2:
            continue
        name = plane_name(buf, v)
        if name.startswith("/device:TPU:") and not device_done:
            device_done = True
            out += field(1, cut_plane(buf, v, lo - DEVICE_EARLY_NS, hi,
                                      keep_line=DEVICE_LINES.__contains__))
        elif name == "/host:CPU":
            out += field(1, cut_plane(buf, v, lo, hi,
                                      wanted=event_ids(buf, v, pattern)))
    args.target.write_bytes(bytes(out))
    print(f"{args.target}: {len(out)} bytes, steps {first}..{last}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
