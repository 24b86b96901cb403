"""HLO inspection helpers: collectives and tensor-shape pins.

Tests in this repo pin two kinds of compiled-program properties:

- *shape pins* — a tensor of a given dtype/shape must (not) exist in the
  lowered or compiled text ("the chunked loss never materializes [B*S, V]
  fp32 logits", "no device holds the full-E expert stack"). Lowered
  StableHLO spells avals ``tensor<8x16xf32>``; compiled HLO spells them
  ``f32[8,16]``. ``has_aval`` matches both so a pin survives the
  lowered/compiled choice.
- *collective pins* — which collectives a compiled program holds, how many
  of each kind, and whether one that moves a given array sits inside a
  ``while`` body (``collectives_moving``: "the head's matrix is gathered
  once a step, outside both chunk loops").

Shared by tests/test_moe.py, test_serve.py, test_paged_decode.py,
test_405b_recipe.py, test_chunked_loss.py, test_chip_compile.py.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Iterable, Sequence

COLLECTIVE_KINDS = ("all-gather", "reduce-scatter", "all-reduce",
                    "collective-permute", "all-to-all")


@dataclasses.dataclass(frozen=True)
class CollectiveOp:
    kind: str           # e.g. "all-gather"
    name: str           # e.g. "%all-gather-start.3"
    computation: str    # enclosing HLO computation name
    line: int           # line index into the module text
    is_start: bool
    is_done: bool


_COMPUTATION_RE = re.compile(  # params may be tuple-typed (nested parens)
    r"^\s*(?:ENTRY\s+)?(%[\w.\-]+)\s*(?:\(.*\))?\s*->.*\{\s*$")
# the result type may be tuple-shaped with spaces — async collective
# -start ops always are on TPU: "%ag-start = (f32[8], f32[32]) all-gather-start(..."
# — and the chip's tiled layouts nest parentheses inside it
# ("(bf16[2,128]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) collective-permute-start(...")
# so the tuple runs lazily up to the ") opcode(" that closes it
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*(?:\(.*?\)|\S+)\s+([\w\-]+)\(")


def _iter_ops(text: str):
    """Yield (op_name, op_kind, computation, line_no, line_text) over an HLO
    module's text (compiled ``as_text()`` form)."""
    comp = ""
    for i, line in enumerate(text.splitlines()):
        m = _COMPUTATION_RE.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _OP_RE.match(line)
        if m:
            yield m.group(1), m.group(2), comp, i, line


def find_collectives(text: str, kinds: Sequence[str] = COLLECTIVE_KINDS
                     ) -> list[CollectiveOp]:
    """Every collective op in the module, with its enclosing computation."""
    out = []
    for name, op, comp, line, _ in _iter_ops(text):
        base = op
        is_start = op.endswith("-start")
        is_done = op.endswith("-done")
        if is_start or is_done:
            base = op.rsplit("-", 1)[0]
        if base in kinds:
            out.append(CollectiveOp(kind=base, name=name, computation=comp,
                                    line=line, is_start=is_start,
                                    is_done=is_done))
    return out


_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8}
_SHAPE_RE = re.compile(r"\b([a-z]+\d+|pred)\[([\d,]*)\]")
# the chip's compiler emits a reduce-scatter as a custom fusion whose callee
# it names all-reduce-scatter (the all-reduce inside it is the fusion's
# implementation, not a collective of the program's)
_RS_FUSION = "%all-reduce-scatter"


def _result_arrays(line: str) -> list:
    """``(dtype, elements)`` of each array in an op's result (a tuple has
    several), from its text."""
    m = _OP_RE.match(line)
    out = []
    for dtype, dims in _SHAPE_RE.findall(line[m.end(1):m.start(2)]):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        out.append((dtype, n))
    return out


def _result_bytes(line: str) -> int:
    """Bytes of an op's result (summed over a tuple), from its text."""
    return sum(n * _DTYPE_BYTES.get(dtype, 4)
               for dtype, n in _result_arrays(line))


def collectives_moving(text: str, elements: int, *, shards: int = 1) -> list:
    """``(op, in_a_loop)`` for every all-gather or all-reduce whose result
    holds an array of ``elements`` elements and every reduce-scatter whose
    result holds one of ``elements // shards`` (one shard of it); ``-done``
    halves are left out. What a test of "this matrix crosses the mesh N
    times a step, never inside a loop" asks of a compiled step."""
    lines = text.splitlines()
    loops = while_body_computations(text)
    out = []
    for c in find_collectives(text,
                              ("all-gather", "reduce-scatter", "all-reduce")):
        want = elements // shards if c.kind == "reduce-scatter" else elements
        if not c.is_done and want in [n for _, n in
                                      _result_arrays(lines[c.line])]:
            out.append((c, c.computation in loops))
    return out


def collective_summary(text: str) -> dict:
    """What a compiled step program moves between devices, for a log line:
    how many collectives of each kind it holds (a start/done pair is one),
    how many fused reduce-scatters (``all-reduce-scatter`` custom fusions,
    the chip compiler's form), and the bytes of its largest all-reduce
    outside those fusions — a sharded-state program reduces its gradients by
    reduce-scatter (as an op, as such a fusion, or decomposed into a ring of
    collective-permutes around the partial dots), so a parameter-sized
    all-reduce is the sign that it does not."""
    counts: dict = {}
    largest_all_reduce = 0
    for name, op, comp, _, line in _iter_ops(text):
        if op == "fusion" and f"calls={_RS_FUSION}" in line:
            counts["reduce-scatter-fusion"] = \
                counts.get("reduce-scatter-fusion", 0) + 1
            continue
        base = op[:-len("-start")] if op.endswith("-start") else op
        if base not in COLLECTIVE_KINDS or comp.startswith(_RS_FUSION):
            continue
        counts[base] = counts.get(base, 0) + 1
        if base == "all-reduce":
            largest_all_reduce = max(largest_all_reduce, _result_bytes(line))
    return {"counts": counts, "largest_all_reduce_bytes": largest_all_reduce}


_CALLEE_RE = re.compile(
    r"(?:calls|to_apply|body|condition|branch_computations)="
    r"\{?(%[\w.\-]+(?:,\s*%[\w.\-]+)*)\}?")


def _call_graph(text: str) -> dict[str, set[str]]:
    """computation -> computations its ops reference (fusions, loop bodies,
    reducers, conditionals)."""
    graph: dict[str, set[str]] = {}
    comp = ""
    for line in text.splitlines():
        m = _COMPUTATION_RE.match(line)
        if m:
            comp = m.group(1)
            graph.setdefault(comp, set())
            continue
        for m in _CALLEE_RE.finditer(line):
            graph.setdefault(comp, set()).update(
                c.strip() for c in m.group(1).split(","))
    return graph


def while_body_computations(text: str) -> set[str]:
    """Computations reachable from any ``while`` op's body/condition —
    TRANSITIVELY, because XLA outlines collectives into helper computations
    (fusions, parallel thunks) called from the loop body."""
    graph = _call_graph(text)
    roots = set()
    for m in re.finditer(r"=[^\n]*?\swhile\([^\n]*?"
                         r"condition=(%[\w.\-]+)[^\n]*?body=(%[\w.\-]+)",
                         text):
        roots.update(m.groups())
    seen = set()
    stack = list(roots)
    while stack:
        c = stack.pop()
        if c in seen:
            continue
        seen.add(c)
        stack.extend(graph.get(c, ()))
    return seen


# ---------------------------------------------------------------------------
# tensor-shape pins
# ---------------------------------------------------------------------------

def aval_patterns(dtype: str, shape: Iterable[int]) -> tuple[str, str]:
    """The two textual spellings of an aval: compiled HLO ``f32[8,16]`` and
    lowered StableHLO ``tensor<8x16xf32>``."""
    dims = [str(int(d)) for d in shape]
    return (f"{dtype}[{','.join(dims)}]",
            f"tensor<{'x'.join(dims)}x{dtype}>")


def has_aval(text: str, dtype: str, shape: Iterable[int]) -> bool:
    """True if a tensor of exactly this dtype/shape appears in the module
    text (either spelling)."""
    return any(p in text for p in aval_patterns(dtype, shape))


def has_shape_run(text: str, shape: Iterable[int]) -> bool:
    """True if some tensor's dims contain this CONTIGUOUS run (any dtype,
    any position) — for pins of the form "no [.., E, kT, ..] buffer of any
    width". Dim runs are boundary-delimited so 8192 can't match inside
    18192."""
    dims = [str(int(d)) for d in shape]
    return bool(re.search(r"[\[,]" + ",".join(dims) + r"[,\]]", text)
                or re.search(r"[<x]" + "x".join(dims) + r"[x>]", text))


# ---------------------------------------------------------------------------
# scan pins (jaxprs): what rides a layer scan as carry, and what its body
# slices
# ---------------------------------------------------------------------------

def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for item in (value if isinstance(value, (list, tuple)) else [value]):
            inner = getattr(item, "jaxpr", item)    # ClosedJaxpr or Jaxpr
            if hasattr(inner, "eqns"):
                yield inner


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in _sub_jaxprs(eqn):
            yield from _eqns(inner)


def scans_holding(closed_jaxpr, shape: Iterable[int]) -> list[dict]:
    """One entry for every ``scan`` of a traced program (nested ones too)
    that has an operand or result of exactly ``shape``: how many arrays of
    that shape ride it as ``carry``, as scanned inputs ``xs`` and as stacked
    outputs ``ys``, and ``sliced``: the ``dynamic_slice`` /
    ``dynamic_update_slice`` equations anywhere in its body whose result
    holds at least ONE LAYER of it (``prod(shape[1:])`` elements). The
    serve programs pin their page pools with it: carried whole (``carry``
    2, ``xs`` and ``ys`` 0) and never sliced (``sliced`` empty)."""
    shape = tuple(int(d) for d in shape)
    one_layer = 1
    for d in shape[1:]:
        one_layer *= d
    held = lambda vs: sum(tuple(v.aval.shape) == shape for v in vs)
    out = []
    for eqn in _eqns(closed_jaxpr.jaxpr):
        if eqn.primitive.name != "scan":
            continue
        nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
        entry = {"carry": held(eqn.invars[nc:nc + nk]),
                 "xs": held(eqn.invars[nc + nk:]),
                 "ys": held(eqn.outvars[nk:])}
        if not any(entry.values()):
            continue
        entry["sliced"] = [
            (e.primitive.name, tuple(e.outvars[0].aval.shape))
            for e in _eqns(eqn.params["jaxpr"].jaxpr)
            if e.primitive.name in ("dynamic_slice", "dynamic_update_slice")
            and e.outvars[0].aval.size >= one_layer]
        out.append(entry)
    return out
