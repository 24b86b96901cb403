"""The readers of the program's own names (``readers/scope_time.py``,
``readers/program_span.py``, ``readers/_xplane.py``): on hand-made event
lists, and on recorded v5e traces that carry the names, two training steps of
``qwen3-0.6b.train.seq2048`` and three decode steps of
``olmo2-7b-l12.serve.decode16`` (``benchmarks/testdata/*_named.xplane.pb``),
and four engine steps of that cell of which the second also runs a prefill
chunk (``serve_chunk_and_decode_steps.xplane.pb``)."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import trace_reduce  # noqa: E402
from benchmarks.readers import (_xplane, path_component, program_span,  # noqa: E402
                                scope_time)

TESTDATA = ROOT / "benchmarks" / "testdata"
TRAIN_NAMED = TESTDATA / "train_two_steps_named.xplane.pb"
SERVE_NAMED = TESTDATA / "serve_three_steps_named.xplane.pb"
SERVE_CHUNKED = TESTDATA / "serve_chunk_and_decode_steps.xplane.pb"


# ---- a scope inside a path ---------------------------------------------------

@pytest.mark.parametrize("path,scope,recompute", [
    ("jit(train_step)/jvp()/while/body/closed_call/attn/flash_fwd/pallas_call:",
     "attn", False),
    ("jit(train_step)/transpose(jvp(attn))/mul:", "attn", False),
    ("jit(train_step)/transpose(jvp(loss_head))/while/body/closed_call/"
     "checkpoint/bce,ev->bcv/dot_general:", "loss_head", False),
    ("jit(train_step)/transpose(jvp())/layers/while/body/closed_call/"
     "checkpoint/rematted_computation/mlp/dot_general:", "mlp", True),
    ("jit(train_step)/transpose(jvp())/layers/while/body/add:", "layers",
     False),
    ("jit(serve_decode)/layers/while/body/closed_call/attn/attend/"
     "paged_attend/pallas_call:", "attend", False),
    ("jit(serve_decode)/layers/while/body/closed_call/attn/kv_write/scatter:",
     "kv_write", False),
    ("jit(serve_decode)/loss_head/final_norm/mul:", "final_norm", False),
    ("jit(train_step)/jit(_take)/gather:", None, False),
    ("state.params['layers']['attn']['wq']:", None, False),
    ("", None, False),
])
def test_scope_of_a_path(path, scope, recompute):
    assert _xplane.scope_of(path, scope_time.SCOPES) == scope
    assert _xplane.is_recompute(path) is recompute


def test_scope_table_on_hand_made_events():
    ms = 1_000_000
    paths = {
        "%a": "jit(s)/transpose(jvp(attn))/dot_general:",
        "%r": "jit(s)/transpose(jvp())/layers/while/body/closed_call/"
              "checkpoint/rematted_computation/attn/dot_general:",
        "%w": "jit(s)/jvp()/layers/while:",
        "%m": "jit(s)/jvp()/layers/while/body/closed_call/mlp/dot_general:",
        "%o": "jit(s)/optimizer/mul:",
        # %copy has no path at all: what the compiler inserted
    }
    ops = [("%w", 0, 10 * ms),                 # a while: 2 ms of its own
           ("%m", 1 * ms, 5 * ms), ("%r", 5 * ms, 9 * ms),
           ("%a", 10 * ms, 13 * ms), ("%copy", 13 * ms, 14 * ms),
           ("%o", 16 * ms, 18 * ms),           # 2 ms idle before it
           ("%a", 30 * ms, 33 * ms)]           # outside the window
    got = scope_time.table_from({0: ops}, paths, 0, 20 * ms, n_steps=2)
    assert got["ms_per_step"] == {"attn": 3.5, "mlp": 2.0, "layers": 1.0,
                                  "optimizer": 1.0, "unscoped": 0.5}
    assert got["recompute_ms_per_step"] == 2.0
    assert got["busy_ms_per_step"] == 8.0
    assert sum(got["ms_per_step"].values()) == got["busy_ms_per_step"]
    assert got["unscoped_top"] == [["copy", 0.5]]
    # two devices: the mean; no scope anywhere, or no step: nothing to say
    two = scope_time.table_from({0: ops, 1: ops[:1]}, paths, 0, 20 * ms, 2)
    assert two["ms_per_step"]["layers"] == (2 + 10) / 2 / 2
    assert scope_time.table_from({0: ops}, {}, 0, 20 * ms, 2) is None
    assert scope_time.table_from({0: ops}, paths, 0, 20 * ms, 0) is None
    assert scope_time.table_from({}, paths, 0, 20 * ms, 2) is None


def test_exposed_collectives_go_to_their_scope():
    ops = [("%all-gather.1 = f32[8] all-gather(%p)", 0, 100),
           ("%fusion.1 = f32[8] fusion(%all-gather.1)", 60, 160),
           ("%reduce-scatter.2 = f32[8] reduce-scatter(%g)", 200, 260)]
    paths = {ops[0][0]: "jit(s)/jvp()/layers/while/body/closed_call/mlp/dot:",
             ops[2][0]: ""}
    assert scope_time.exposed_by_scope(ops, paths, 0, 300) == {
        "mlp": 60, "unscoped": 60}


# ---- a prefill chunk inside the window ------------------------------------------

MS = 1_000_000
CHUNKED_PATHS = {
    "%k": "jit(serve_decode)/layers/while/body/attn/attend/paged_attend/pallas_call:",
    "%m": "jit(serve_decode)/layers/while/body/mlp/dot_general:"}


def chunked_trace():
    """One engine step that runs a prefill chunk (the same kernel and scopes
    under ``jit_serve_chunk_t512``) and its decode step, then two decode
    steps; a fourth decode program lies past the window."""
    modules = [("jit_serve_chunk_t512(7)", 0, 10 * MS),
               ("jit_serve_decode(9)", 10 * MS, 14 * MS),
               ("jit_serve_decode(9)", 20 * MS, 24 * MS),
               ("jit_serve_decode(9)", 30 * MS, 34 * MS),
               ("jit_serve_decode(9)", 50 * MS, 54 * MS)]
    ops = [("%k", 1 * MS, 6 * MS), ("%m", 6 * MS, 9 * MS),
           ("%k", 10 * MS, 12 * MS), ("%m", 12 * MS, 14 * MS),
           ("%k", 20 * MS, 22 * MS), ("%m", 22 * MS, 24 * MS),
           ("%k", 30 * MS, 33 * MS), ("%m", 33 * MS, 34 * MS),
           ("%k", 50 * MS, 54 * MS)]
    return {"lo_ns": 0, "hi_ns": 40 * MS, "device_ops": {0: ops},
            "device_modules": {0: modules},
            "host_spans": [("engine.step", 0, 15 * MS),
                           ("engine.step", 19 * MS, 25 * MS),
                           ("engine.step", 29 * MS, 35 * MS)]}


def test_only_the_events_under_the_named_program_count(monkeypatch, capsys):
    trace = chunked_trace()
    assert trace_reduce.programs_run(trace) == {"serve_decode", "serve_chunk_t512"}
    ops, runs = trace_reduce.program_ops(trace, "serve_decode")
    assert runs == 3 and len(ops[0]) == 6
    assert trace_reduce.program_ops(trace, "serve_chunk_t512")[1] == 1
    assert trace_reduce.program_ops(trace, "serve_verify")[1] == 0
    for reader in (scope_time, path_component):
        monkeypatch.setattr(reader._xplane, "traced", lambda ctx: (trace, "x"))
    monkeypatch.setattr(scope_time, "op_paths_of", lambda p: CHUNKED_PATHS)
    read = lambda scope, **kw: scope_time.read({}, {
        "scope": scope, "step_span": "engine.step", **kw})
    # decode steps alone, divided by their number ...
    assert read("attend", program="serve_decode") == pytest.approx(7 / 3)
    assert read("mlp", program="serve_decode") == pytest.approx(5 / 3)
    # ... where every event over the engine steps would read the chunk too
    assert read("attend") == pytest.approx(12 / 3)
    out = capsys.readouterr().out
    chunk = next(json.loads(l) for l in out.splitlines() if '"program"' in l)
    assert chunk["program"] == "serve_chunk_t512"
    assert chunk["device_ms_by_scope"]["ms_per_step"] == {"attend": 5.0, "mlp": 3.0}
    cfg = json.loads((ROOT / "benchmarks" / "configs" / "olmo2-7b-l12.json").read_text())
    peak = json.loads((ROOT / "benchmarks" / "peaks.json").read_text())["TPU v5 lite"]
    ctx = {"config": cfg, "peak": peak, "trace": trace, "trace_window": (0.0, 1.0),
           "counters": {"kv_bytes": 2, "decode_context": [
               (0.2, 16 * 900, 16), (0.4, 16 * 901, 16), (0.6, 16 * 902, 16),
               (1.5, 16 * 903, 16)]}}
    spec = json.loads((ROOT / "benchmarks" / "metrics"
                       / "paged_attend_roofline.json").read_text())
    assert spec["params"]["program"] == "serve_decode"
    from benchmarks import flops
    least = flops.least_time(flops.paged_attend(cfg, 16 * 2703, 48, 2), peak)[0]
    assert path_component.read(ctx, spec["params"]) == pytest.approx(100 * least / 7e-3)
    step = path_component.read(ctx, {"component": "paged_attend", "as": "ms_per_step",
                                     "program": "serve_decode"})
    assert step == pytest.approx(7 / 3)


# ---- the program's host spans -------------------------------------------------

def spans_of_two_steps():
    t = "python3"
    return [
        ("serve.step", 0, 100, t, {"seq": 1}),
        ("serve.expire", 1, 3, t, {}),
        ("serve.reserve", 5, 9, t, {}),
        ("serve.dispatch", 10, 20, t, {"program": "serve_decode"}),
        ("serve.wait", 20, 90, t, {}),
        ("serve.book", 90, 98, t, {"tokens": 16}),
        ("serve.step", 120, 200, t, {"seq": 2}),
        ("serve.admit", 121, 131, t, {"request_id": 7, "queue_ms": 2.5}),
        ("serve.prefill", 131, 150, t, {"request_id": 7}),
        ("serve.dispatch", 150, 155, t, {}),
        ("serve.wait", 155, 195, t, {}),
        ("serve.book", 195, 199, t, {}),
        ("serve.step", 300, 400, t, {"seq": 3}),      # outside the window
        ("serve.wait", 50, 60, "another thread", {}),
    ]


def test_host_and_schedule_time_per_step():
    steps = program_span.steps_with_children(spans_of_two_steps(), 0, 250)
    assert [s[4]["seq"] for s, _ in steps] == [1, 2]
    assert [len(c) for _, c in steps] == [5, 5]
    # (100 - 70) and (80 - 40) ns of host work; (2 + 4 + 8) and (10 + 4) ns
    assert program_span.host_ms_per_step(steps) == pytest.approx(35e-6)
    assert program_span.schedule_ms_per_step(steps) == pytest.approx(14e-6)
    # the metrics are read over decode steps: the second step ran a prefill
    decode = program_span.decode_steps(steps)
    assert [s[4]["seq"] for s, _ in decode] == [1]
    assert program_span.host_ms_per_step(decode) == pytest.approx(30e-6)
    assert program_span.schedule_ms_per_step(decode) == pytest.approx(14e-6)


def test_idle_inside_steps_and_the_share_no_span_covers():
    steps = program_span.steps_with_children(spans_of_two_steps(), 0, 250)
    # idle: 3-5 (between expire and reserve: no child), 12-18 (dispatch),
    # 95-125 (book, the end of step 1, BETWEEN the steps, admit), 210-220
    gaps = [(3, 5), (12, 18), (95, 125), (210, 220)]
    by = program_span.idle_by_span(gaps, spans_of_two_steps())
    # each instant of a gap goes to the deepest span over it: 95-125 is 3 ns
    # of `book`, 2 ns of step 1's own, 20 between the steps, 1 of step 2's
    # own and 4 of `admit`; 3-5 is the first step's own time
    assert by == {"outside": pytest.approx(30e-9),
                  "serve.dispatch": pytest.approx(6e-9),
                  "serve.step": pytest.approx(5e-9),
                  "serve.admit": pytest.approx(4e-9),
                  "serve.book": pytest.approx(3e-9)}
    # inside steps: 18 ns; no child over 3-5, 98-100 and 120-121
    assert program_span.idle_unattributed_pct(by) == pytest.approx(100 * 5 / 18)
    assert program_span.idle_unattributed_pct({"outside": 1.0}) == 0.0
    nested = program_span.idle_by_span([(30, 40)], spans_of_two_steps())
    assert nested == {"serve.wait": pytest.approx(10e-9)}
    pieces = program_span.deepest_pieces(spans_of_two_steps()[:6])
    assert sorted(p for p in pieces if p[0] == "serve.step") == [
        ("serve.step", 0, 1), ("serve.step", 3, 5), ("serve.step", 9, 10),
        ("serve.step", 98, 100)]


def test_no_spans_is_none(tmp_path):
    trace = {"lo_ns": 0, "hi_ns": 10, "per_device": {0: {"idle_share": 0.1}},
             "device_ops": {0: [("%x", 0, 5)]}, "host_spans": []}
    # no trace at all, and (the parent of the PR that named things) a trace
    # whose program carries no spans and no scopes
    for ctx in ({"trace": None, "trace_dir": None},
                {"trace": trace, "trace_dir": tmp_path}):
        assert program_span.read(dict(ctx), {"stat": "host_ms_per_step"}) is None
        assert scope_time.read(dict(ctx), {"scope": "attn",
                                           "step_span": "step"}) is None
    old = TESTDATA / "train_two_steps.xplane.pb"
    ctx = ctx_for(old, tmp_path / "parent")
    assert ctx["trace"]["busy_s"] > 2 and _xplane.program_spans(old) == []
    for scope in ("attn", "unscoped", "recompute"):
        assert scope_time.read(ctx, {"scope": scope,
                                     "step_span": "step"}) is None


# ---- the recorded traces --------------------------------------------------------

def ctx_for(path, tmp_path):
    tmp_path.mkdir(exist_ok=True)
    (tmp_path / path.name).write_bytes(path.read_bytes())
    return {"trace": trace_reduce.reduce_dir(tmp_path, n_devices=1),
            "trace_dir": tmp_path}


def test_recorded_training_steps_by_scope(tmp_path, capsys):
    ctx = ctx_for(TRAIN_NAMED, tmp_path)
    read = lambda scope: scope_time.read(ctx, {"scope": scope,
                                               "step_span": "step"})
    got = {s: read(s) for s in ("attn", "mlp", "loss_head", "optimizer",
                                "recompute", "unscoped")}
    table = ctx["scope_table"]
    assert table["steps"] == 2 and table["devices"] == 1
    # the scopes and `unscoped` are the device's busy time, within 1%
    busy_ms = 1e3 * ctx["trace"]["busy_s"] / 2
    assert sum(table["ms_per_step"].values()) == pytest.approx(busy_ms,
                                                               rel=0.01)
    assert table["busy_ms_per_step"] == pytest.approx(busy_ms, rel=0.01)
    # the step is 1034 ms: attention over half of it, the flash kernels 341
    assert 1000 < busy_ms < 1040
    assert 560 < got["attn"] < 600 and 170 < got["mlp"] < 190
    assert 155 < got["loss_head"] < 170 and 20 < got["optimizer"] < 30
    assert 220 < got["recompute"] < 240
    assert got["unscoped"] < 0.15 * busy_ms
    assert "layers" in table["ms_per_step"]
    # printed once, whatever the number of metrics read from it
    assert capsys.readouterr().out.count("device_ms_by_scope") == 1
    # the kernels by the names ops/ gave them
    calls = trace_reduce.kernel_events(
        ctx["trace"]["device_ops"][0], "tpu_custom_call",
        ctx["trace"]["lo_ns"], ctx["trace"]["hi_ns"])
    names = {trace_reduce.short_name(n).split(".")[0] for n, _, _ in calls}
    assert names == {"flash_fwd", "flash_dq", "flash_dkv"}
    # flash_attention_roofline matches them by path component: the same
    # events as every tpu_custom_call, while flash is the only kernel there
    spec = json.loads((ROOT / "benchmarks" / "metrics"
                       / "flash_attention_roofline.json").read_text())
    got = path_component.component_seconds(
        ctx["trace"]["device_ops"], scope_time.op_paths_of(TRAIN_NAMED),
        spec["params"]["components"], ctx["trace"]["lo_ns"], ctx["trace"]["hi_ns"])
    assert got == pytest.approx(sum(b - a for _, a, b in calls) / 1e9)


def test_recorded_decode_steps_by_scope_and_span(tmp_path, capsys):
    ctx = ctx_for(SERVE_NAMED, tmp_path)
    read = lambda scope: scope_time.read(ctx, {"scope": scope,
                                               "step_span": "engine.step"})
    attend, kv_write, unscoped = (read("attend"), read("kv_write"),
                                  read("unscoped"))
    table = ctx["scope_table"]
    assert table["steps"] == 3
    # a window of decode steps alone reads the same under its program's name
    named = dict(ctx)
    del named["scope_table"]
    assert scope_time.read(named, {"scope": "attend", "step_span": "engine.step",
                                   "program": "serve_decode"}) == attend
    assert named["scope_table"] == table
    busy_ms = 1e3 * ctx["trace"]["busy_s"] / 3
    assert sum(table["ms_per_step"].values()) == pytest.approx(busy_ms,
                                                               rel=0.01)
    assert 280 < busy_ms < 300 and 235 < attend < 250
    assert 0 < kv_write < 1 and unscoped < 0.15 * busy_ms
    host = program_span.read(ctx, {"stat": "host_ms_per_step"})
    sched = program_span.read(ctx, {"stat": "schedule_ms_per_step"})
    idle = program_span.read(ctx, {"stat": "idle_unattributed_pct"})
    assert 0 < sched < host < 20 and 0 <= idle < 10
    out = capsys.readouterr().out
    assert out.count("idle_by_program_span") == 1
    spans = _xplane.program_spans(SERVE_NAMED)
    assert {s[0] for s in spans} >= {"serve.step", "serve.expire",
                                     "serve.reserve", "serve.dispatch",
                                     "serve.wait", "serve.book"}
    assert all(s[4]["program"] == "serve_decode" for s in spans
               if s[0] == "serve.dispatch")


def test_recorded_chunk_step_is_not_read_as_decode_time(tmp_path, capsys):
    """Four engine steps of ``decode16`` on the v5e; the second admits a new
    512-token prompt, so it runs ``jit_serve_chunk_t512`` (22.8 ms, the same
    ``paged_attend`` kernel at its chunk tile) before its decode program."""
    ctx = ctx_for(SERVE_CHUNKED, tmp_path)
    trace = ctx["trace"]
    lo, hi = trace["lo_ns"], trace["hi_ns"]
    assert {"serve_decode", "serve_chunk_t512"} <= trace_reduce.programs_run(trace)
    steps = sum(1 for n, a, b in trace["host_spans"] if n == "engine.step")
    decode_ops, decode_runs = trace_reduce.program_ops(trace, "serve_decode")
    chunk_ops, chunk_runs = trace_reduce.program_ops(trace, "serve_chunk_t512")
    assert (steps, decode_runs, chunk_runs) == (4, 4, 1)
    # the kernel: every tpu_custom_call of the window is a paged_attend, and
    # only the decode programs' count for the decode steps' roofline
    paths = scope_time.op_paths_of(SERVE_CHUNKED)
    seconds = lambda ops: path_component.component_seconds(
        ops, paths, "paged_attend", lo, hi)
    calls = trace_reduce.kernel_events(trace["device_ops"][0],
                                       "tpu_custom_call", lo, hi)
    assert seconds(trace["device_ops"]) == pytest.approx(
        sum(b - a for _, a, b in calls) / 1e9)
    assert seconds(decode_ops) + seconds(chunk_ops) == pytest.approx(
        seconds(trace["device_ops"]))
    assert 4.5e-3 < seconds(chunk_ops) < 5.0e-3
    # by scope: decode steps alone, divided by their number
    read = lambda c, scope, **kw: scope_time.read(c, {
        "scope": scope, "step_span": "engine.step", **kw})
    named = dict(ctx)
    attend = read(named, "attend", program="serve_decode")
    assert attend == pytest.approx(1e3 * seconds(decode_ops) / 4)
    assert 2.4 < attend < 2.9 and named["scope_table"]["steps"] == 4
    assert 10.5 < named["scope_table"]["busy_ms_per_step"] < 11.5
    assert read(named, "kv_write", program="serve_decode") < 0.1
    # ... where every event over the four engine steps reads the chunk too
    assert read(dict(ctx), "attend") > attend + 1e3 * seconds(chunk_ops) / 4
    assert read(dict(ctx), "kv_write") > 0.4
    out = capsys.readouterr().out
    chunk = next(json.loads(l) for l in out.splitlines()
                 if '"program": "serve_chunk_t512"' in l)["device_ms_by_scope"]
    assert chunk["steps"] == 1 and 22 < chunk["busy_ms_per_step"] < 24
    # the host's time a step: the three steps that ran no prefill
    spans = _xplane.program_spans(SERVE_CHUNKED)
    all_steps = program_span.steps_with_children(spans, lo, hi)
    decode = program_span.decode_steps(all_steps)
    assert (len(all_steps), len(decode)) == (4, 3)
    host = program_span.read(ctx, {"stat": "host_ms_per_step"})
    assert host == pytest.approx(program_span.host_ms_per_step(decode))
    assert 2 < host < 6 < program_span.host_ms_per_step(all_steps)


def test_the_readers_know_the_programs_scopes():
    from distributed_training_guide_tpu.utils import trace

    assert set(scope_time.SCOPES) == set(trace.SCOPES)
    assert _xplane.PROGRAM_PREFIX == trace.PREFIX
    assert {program_span.STEP, *program_span.WAIT,
            *program_span.SCHEDULE} <= set(trace.SPANS)


def test_wire_reader_finds_the_scope_paths():
    found = _xplane.metadata_stat(TRAIN_NAMED, "tf_op")
    assert set(found) == {"/device:TPU:0"}
    paths = found["/device:TPU:0"]
    flash = [p for n, p in paths.items() if n.startswith("%flash_dkv")]
    assert flash and all(p.endswith("/attn/flash_dkv/pallas_call:")
                         for p in flash)
    assert _xplane.metadata_stat(TRAIN_NAMED, "no_such_stat") == {}
