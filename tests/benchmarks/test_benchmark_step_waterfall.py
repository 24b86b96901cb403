"""``readers/step_waterfall.py``: the cut of a serve step at the device
program's own start and end, on hand-made spans and module runs, and end to
end on a recorded v5e trace of ``olmo2-7b-l12.serve.decode16`` that carries
the spans of the PR that added the reader
(``benchmarks/testdata/serve_waterfall_steps.xplane.pb``: decode steps,
rebuilt and quiet, and one chunk step)."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import trace_reduce  # noqa: E402
from benchmarks.readers import _xplane, program_span, step_waterfall  # noqa: E402

RECORDED = ROOT / "benchmarks" / "testdata" / "serve_waterfall_steps.xplane.pb"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
US = 1_000
MS = 1_000_000
STEP_MS = 10            # a hand-made decode step: 10 ms, its program 6 ms
METRICS = {
    "serve.launch_ms_per_step": "launch_ms_per_step",
    "serve.readback_ms_per_step": "readback_ms_per_step",
    "serve.upload_ms_per_step": "upload_ms_per_step",
    "serve.arrays_ms_per_step": "arrays_ms_per_step",
    "serve.rebuild_steps_pct": "rebuild_steps_pct",
    "serve.chunk_step_gap_ms": "chunk_step_gap_ms",
    "serve.slow_step_excess_pct": "slow_step_excess_pct",
}


def span(name, a, b, **stats):
    return (name, a, b, "python3", stats)


def decode_step(t0, seq, *, rebuilt=False, stretch=0, where="readback",
                cpu_ms=1.0, program="serve_decode", dispatches=1):
    """One hand-made step from ``t0`` and the program's run on the device:
    pre 1 ms (2 ms rebuilt: 0.2 arrays + 0.7 upload inside a 1 ms build),
    launch 0.5, device 6, readback 1.5, post 1. ``stretch`` ns go into
    ``where``."""
    extra = {p: stretch if p == where else 0 for p in step_waterfall.PHASES}
    t = t0
    spans = [span("serve.expire", t + 10 * US, t + 30 * US),
             span("serve.reserve", t + 40 * US, t + 90 * US, grown=int(rebuilt))]
    t += 1 * MS + extra["pre"]
    if rebuilt:
        spans += [span("serve.build", t, t + 1 * MS, reason="grown"),
                  span("serve.arrays", t + 50 * US, t + 250 * US),
                  span("serve.upload", t + 250 * US, t + 950 * US, arrays=11,
                       bytes=6000)]
        t += 1 * MS
    d0 = t
    spans.append(span("serve.dispatch", d0, d0 + 400 * US, program=program))
    for i in range(1, dispatches):
        spans.append(span("serve.dispatch", d0 + 400 * US * i,
                          d0 + 400 * US * (i + 1), program=program))
    m0 = d0 + 500 * US + extra["launch"]
    m1 = m0 + 6 * MS + extra["device"]
    w1 = m1 + 1500 * US + extra["readback"]
    spans.append(span("serve.wait", d0 + 400 * US * dispatches + 10 * US, w1))
    spans.append(span("serve.book", w1 + 10 * US, w1 + 200 * US, tokens=16))
    end = w1 + 1 * MS + extra["post"]
    spans.insert(0, span("serve.step", t0, end, seq=seq, cpu_ms=cpu_ms))
    ops = [("%fusion.1", m0, m0 + 2 * MS), ("%paged_attend.2", m0 + 2 * MS, m1)]
    return spans, (f"jit_{program}(7)", m0, m1), ops, end


def window(n=8, rebuilt=(0, 2, 3, 5), **special):
    """``n`` decode steps back to back, 0.1 ms of client between them;
    ``special`` maps a step's index to ``decode_step`` keywords."""
    spans, modules, ops, t = [], [], [], 1000 * MS
    for i in range(n):
        got = decode_step(t, 100 + i, rebuilt=i in rebuilt,
                          **special.get(f"s{i}", {}))
        spans += got[0]
        modules.append(got[1])
        ops += got[2]
        t = got[3] + 100 * US
    return spans, modules, ops, 1000 * MS - 1, t


# ---- the cut on hand-made spans ---------------------------------------------
def test_phases_are_a_partition_of_every_step():
    spans, modules, ops, lo, hi = window()
    got = step_waterfall.waterfall(spans, modules, ops, lo, hi)
    water = got["step_waterfall"]
    assert water["steps"] == 8 and water["steps_skipped"] == 0
    for kind, n in (("decode", 8), ("decode_rebuilt", 4), ("decode_quiet", 4)):
        t = water[kind]
        assert t["steps"] == n and t["sums_to_step"] is True
        assert sum(t[p] for p in step_waterfall.PHASES) == \
            pytest.approx(t["step_ms"], abs=1e-9)
        assert (t["launch"], t["device"], t["readback"], t["post"]) == \
            pytest.approx((0.5, 6.0, 1.5, 1.0))
    assert "chunk" not in water
    assert water["decode_quiet"]["pre"] == pytest.approx(1.0)
    assert water["decode_rebuilt"]["pre"] == pytest.approx(2.0)
    assert water["decode_quiet"]["step_ms"] == pytest.approx(STEP_MS)
    # inside pre, by the deepest span: the build's own row is what neither
    # child covers, the step's own remainder is named for the step
    pre = water["decode_rebuilt"]["pre_by_span"]
    assert pre["serve.upload"] == pytest.approx(0.7)
    assert pre["serve.arrays"] == pytest.approx(0.2)
    assert pre["serve.build"] == pytest.approx(0.1)
    assert pre["serve.step"] == pytest.approx(1.0 - 0.02 - 0.05)
    assert water["decode"]["post_by_span"]["serve.book"] == pytest.approx(0.19)
    # the trace's own alignment is causal: nothing is moved
    assert water["clock_slack_ms"] == pytest.approx([-0.5, 1.5])
    assert water["device_clock_shift_ms"] == 0
    stats = got["stats"]
    assert stats["launch_ms_per_step"] == pytest.approx(0.5)
    assert stats["readback_ms_per_step"] == pytest.approx(1.5)
    assert stats["upload_ms_per_step"] == pytest.approx(0.7 * 4 / 8)
    assert stats["arrays_ms_per_step"] == pytest.approx(0.2 * 4 / 8)
    assert stats["rebuild_steps_pct"] == 50.0
    assert stats["slow_step_excess_pct"] == 0.0
    assert stats["chunk_step_gap_ms"] is None
    assert got["slow_steps"] == []
    assert got["rebuild_reasons"] == {
        "rebuild_reasons": {"grown": 4}, "builds": 4,
        "upload_bytes_per_build": 6000.0, "arrays_per_build": 11.0}


def test_a_step_with_two_dispatches_is_skipped_and_counted():
    spans, modules, ops, lo, hi = window(s4={"dispatches": 2})
    got = step_waterfall.waterfall(spans, modules, ops, lo, hi)
    assert got["step_waterfall"]["steps"] == 8
    assert got["step_waterfall"]["steps_skipped"] == 1
    assert got["step_waterfall"]["decode"]["steps"] == 7
    # so is a pipelined horizon step, whose wait reads another dispatch's
    # block, and a step whose program never ran on the device's line
    spans, modules, ops, lo, hi = window(s1={"program": "serve_horizon_k4"})
    assert step_waterfall.waterfall(spans, modules, ops, lo, hi)[
        "step_waterfall"]["steps_skipped"] == 1
    spans, modules, ops, lo, hi = window()
    del modules[6]
    assert step_waterfall.waterfall(spans, modules, ops, lo, hi)[
        "step_waterfall"]["steps_skipped"] == 1


def test_a_chunk_step_is_classed_as_one_and_its_gap_is_outside_the_device():
    # step 3 also ran a prefill chunk: 4 ms of its pre under serve.prefill
    # and serve.sample, 3 ms of them busy on the device
    spans, modules, ops, lo, hi = window(s3={"stretch": 4 * MS, "where": "pre"})
    step = next(s for s in spans if s[0] == "serve.step" and s[4]["seq"] == 103)
    t = step[1] + 100 * US
    spans += [span("serve.prefill", t, t + 1 * MS, request_id=9, tokens=512,
                   program="serve_chunk_t512"),
              span("serve.sample", t + 1 * MS, t + 3900 * US, request_id=9)]
    modules.append(("jit_serve_chunk_t512(3)", t + 500 * US, t + 3500 * US))
    ops.append(("%fusion.9", t + 500 * US, t + 3500 * US))
    got = step_waterfall.waterfall(spans, modules, ops, lo, hi)
    water = got["step_waterfall"]
    assert water["chunk"]["steps"] == 1 and water["decode"]["steps"] == 7
    assert water["chunk"]["sums_to_step"] is True
    assert water["chunk"]["pre"] == pytest.approx(6.0)       # rebuilt + 4 ms
    assert water["chunk"]["pre_by_span"]["serve.sample"] == pytest.approx(2.9)
    # 15 ms of step, 6 + 3 ms of device ops inside it
    assert got["stats"]["chunk_step_gap_ms"] == pytest.approx(15.0 - 9.0)
    assert got["stats"]["rebuild_steps_pct"] == pytest.approx(100 * 3 / 7)


@pytest.mark.parametrize("where,span_name", [
    ("readback", "serve.wait"), ("launch", "serve.wait"),
    ("pre", "serve.step"), ("post", "serve.step"), ("device", "serve.wait")])
def test_a_ten_times_step_lands_in_slow_steps_with_its_phase(where, span_name):
    spans, modules, ops, lo, hi = window(
        s5={"stretch": 90 * MS, "where": where, "cpu_ms": 2.5})
    # a collection inside the slow step, and one outside every step's excess
    slow = next(s for s in spans if s[0] == "serve.step" and s[4]["seq"] == 105)
    spans.append(("gc", slow[2] - 300 * US, slow[2] - 100 * US, "python3",
                  {"generation": 2, "collected": 41}))
    got = step_waterfall.waterfall(spans, modules, ops, lo, hi)
    assert got["step_waterfall"]["decode"]["sums_to_step"] is True
    (row,) = got["slow_steps"]
    assert row["seq"] == 105 and row["cpu_ms"] == 2.5
    assert row["wall_ms"] == pytest.approx(90 + STEP_MS + 1)   # a rebuilt step
    assert row["phase"] == where and row["span"] == span_name
    assert row["excess_there_ms"] == pytest.approx(90, abs=0.3)
    assert row["phases_ms"][where] >= 90
    busy = 96.0 if where == "device" else 6.0
    assert row["device_busy_ms"] == pytest.approx(busy)
    assert row["gc"] == [{"generation": 2, "collected": 41,
                          "ms": pytest.approx(0.2)}]
    median = row["median_step_ms"]
    assert got["stats"]["slow_step_excess_pct"] == pytest.approx(
        100 * (row["wall_ms"] - median) * MS / (hi - lo))
    # the slow step is a decode step like the others: it is in the means
    assert got["step_waterfall"]["decode"]["steps"] == 8


def test_no_arrays_span_reads_none():
    """The parent of the PR that added the spans: nothing to read, nothing
    printed, no metric in the line."""
    spans, modules, ops, lo, hi = window()
    old = [s for s in spans if s[0] not in ("serve.arrays", "serve.upload")]
    assert step_waterfall.waterfall(old, modules, ops, lo, hi) is None
    assert step_waterfall.waterfall([], modules, ops, lo, hi) is None
    ctx = {"trace": None, "trace_dir": None}
    assert step_waterfall.read(ctx, {"stat": "launch_ms_per_step"}) is None


def test_a_device_line_ahead_of_the_host_is_moved_by_the_least_that_is_causal():
    """Recorded v5e traces have the program start up to 1.1 ms before its
    dispatch. The sum of launch and readback does not depend on it."""
    spans, modules, ops, lo, hi = window()
    early = [(n, a - 1200 * US, b - 1200 * US) for n, a, b in modules]
    early_ops = [(n, a - 1200 * US, b - 1200 * US) for n, a, b in ops]
    got = step_waterfall.waterfall(spans, early, early_ops, lo, hi)
    water = got["step_waterfall"]
    assert water["steps_skipped"] == 0
    assert water["clock_slack_ms"] == pytest.approx([0.7, 2.7])
    assert water["device_clock_shift_ms"] == pytest.approx(0.7)
    assert water["steps_anchored_by_runtime_events"] == 0
    t = water["decode"]
    assert t["launch"] == pytest.approx(0.0) and t["sums_to_step"] is True
    assert t["launch"] + t["readback"] == pytest.approx(2.0)
    assert t["device"] == pytest.approx(6.0)
    # the runtime's own events narrow what is open: every program was
    # enqueued 0.45 ms after its dispatch began and its completion handled
    # 0.3 ms after its end; one step's pair is missing and keeps the spans'
    executions = sorted((a + 1200 * US - 50 * US, b + 1200 * US + 300 * US)
                        for _, a, b in early[1:])
    got = step_waterfall.waterfall(spans, early, early_ops, lo, hi, executions)
    water = got["step_waterfall"]
    assert water["steps_anchored_by_runtime_events"] == 7
    assert water["clock_slack_ms"] == pytest.approx([1.15, 1.5])
    assert water["device_clock_shift_ms"] == pytest.approx(1.15)
    t = water["decode"]
    assert (t["launch"], t["readback"]) == pytest.approx((0.45, 1.55))
    assert t["device"] == pytest.approx(6.0) and t["sums_to_step"] is True


# ---- the listings -----------------------------------------------------------
@pytest.mark.parametrize("metric", sorted(METRICS))
def test_each_metric_names_the_reader_and_its_stat(metric):
    spec = json.loads((ROOT / "benchmarks" / "metrics" /
                       f"{metric}.json").read_text())
    assert spec == {"reader": "step_waterfall",
                    "params": {"stat": METRICS[metric]}}
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert entry["layer"] == "serve engine" and entry["better"] == "lower"
    assert entry["source"] == "program_span"
    serve = [w["name"] for w in BENCH["workloads"] if ".serve." in w["name"]]
    want = ([c for c in serve if c.endswith(("decode16", "chat64"))]
            if metric == "serve.chunk_step_gap_ms" else serve[:4])
    assert entry["workloads"] == want
    e2e = next(m for m in BENCH["end_to_end"] if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(e2e["workloads"])


# ---- end to end on the recorded trace ---------------------------------------
@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The reader's ``ctx`` over the recorded trace, as a traced run has it."""
    trace_dir = tmp_path_factory.mktemp("trace")
    (trace_dir / "t.xplane.pb").write_bytes(RECORDED.read_bytes())
    ctx = {"trace_dir": trace_dir,
           "trace": trace_reduce.reduce_dir(trace_dir, n_devices=1)}
    return ctx


def test_recorded_trace_carries_the_new_spans(recorded):
    spans = _xplane.program_spans(trace_reduce.find_xplane(
        recorded["trace_dir"]))
    names = {s[0] for s in spans}
    assert {"serve.step", "serve.build", "serve.arrays", "serve.upload",
            "serve.dispatch", "serve.wait", "serve.prefill"} <= names
    builds = [s for s in spans if s[0] == "serve.build"]
    uploads = [s for s in spans if s[0] == "serve.upload"]
    assert builds and len(builds) == len(uploads)
    from distributed_training_guide_tpu.utils.trace import REBUILD_REASONS
    assert {b[4]["reason"] for b in builds} <= set(REBUILD_REASONS)
    assert all(u[4]["arrays"] == 11 and u[4]["bytes"] > 0 for u in uploads)
    # the chip host's thread clock ticks every 10 ms: most steps read 0
    assert all(s[4]["cpu_ms"] >= 0 for s in spans if s[0] == "serve.step")


def test_reader_end_to_end_on_the_recorded_trace(recorded, capsys):
    values = {m: step_waterfall.read(recorded, {"stat": stat})
              for m, stat in METRICS.items()}
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [next(iter(l)) for l in lines] == [
        "step_waterfall", "rebuild_reasons", "slow_steps"]   # printed once
    water = lines[0]["step_waterfall"]
    trace = recorded["trace"]
    spans = _xplane.program_spans(trace_reduce.find_xplane(
        recorded["trace_dir"]))
    steps = program_span.steps_with_children(spans, trace["lo_ns"],
                                             trace["hi_ns"])
    decode = program_span.decode_steps(steps)
    assert water["steps"] == len(steps) and water["steps_skipped"] == 0
    assert water["decode"]["steps"] == len(decode) >= 4
    assert water["chunk"]["steps"] == len(steps) - len(decode) == 1
    assert water["decode_rebuilt"]["steps"] >= 1
    assert water["decode_quiet"]["steps"] >= 1
    for kind in ("decode", "decode_rebuilt", "decode_quiet", "chunk"):
        t = water[kind]
        assert t["sums_to_step"] is True
        assert all(t[p] >= 0 for p in step_waterfall.PHASES)
        assert sum(t[p] for p in step_waterfall.PHASES) == \
            pytest.approx(t["step_ms"], rel=1e-9)
    # the decode steps' mean is the program spans' own
    mean = sum(s[2] - s[1] for s, _ in decode) / len(decode) / 1e6
    assert water["decode"]["step_ms"] == pytest.approx(mean, rel=1e-12)
    # the device program is most of a decode step, the round trip the rest
    t = water["decode"]
    assert t["device"] > 0.5 * t["step_ms"]
    assert 0.5 < t["launch"] + t["readback"] < 5.0
    # every run lies between the runtime's own enqueue and completion events,
    # which leave the two clocks under half a millisecond of play
    assert water["steps_anchored_by_runtime_events"] == len(steps)
    slack = water["clock_slack_ms"]
    assert 0 < slack[0] <= slack[1] < slack[0] + 0.5
    assert water["device_clock_shift_ms"] == slack[0]
    # a quiet step builds nothing; a rebuilt one spends its build in the two
    pre = water["decode_rebuilt"]["pre_by_span"]
    assert "serve.upload" not in water["decode_quiet"]["pre_by_span"]
    assert pre["serve.build"] < 0.05 * (pre["serve.arrays"] + pre["serve.upload"])
    # host_ms_per_step, from the other reader, is the step less its wait
    host = program_span.host_ms_per_step(decode)
    wait = sum(b - a for _, ch in decode for n, a, b, _, _ in ch
               if n == "serve.wait") / len(decode) / 1e6
    assert host + wait == pytest.approx(t["step_ms"], rel=1e-9)
    assert values["serve.launch_ms_per_step"] == t["launch"]
    assert values["serve.readback_ms_per_step"] == t["readback"]
    assert 0 < values["serve.arrays_ms_per_step"] \
        < values["serve.upload_ms_per_step"] < t["pre"]
    assert 0 < values["serve.rebuild_steps_pct"] < 100
    assert values["serve.slow_step_excess_pct"] == 0.0
    # a chunk step: its two programs and what lies between and around them
    chunk = water["chunk"]
    assert chunk["pre_by_span"]["serve.sample"] > 1.0
    assert 1.0 < values["serve.chunk_step_gap_ms"] < chunk["step_ms"] / 2
    reasons = lines[1]
    assert sum(reasons["rebuild_reasons"].values()) == reasons["builds"] == \
        sum(1 for s in spans if s[0] == "serve.build")
    assert reasons["arrays_per_build"] == 11.0
    assert lines[2] == {"slow_steps": []}
