"""``readers/step_order.py`` on hand-made spans: the order each step took,
what held it, what admission did, and the join of a pipelined step's
dispatch with the program IT enqueued, by order and not by overlap, under a
device line that is two milliseconds off the host's either way."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.readers import program_span, step_order  # noqa: E402
from benchmarks.traffic.generate import percentile  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
US, MS = 1_000, 1_000_000
PROGRAM = 10 * MS       # a decode program on the device
LAUNCH = 200 * US       # an enqueue on an idle device starts this much later
READ = 300 * US         # a wait ends this much after its program
OPEN = 500 * US         # what causality leaves open here: the sooner of the
                        # two, an enqueue to its start or a program to its read
METRICS = {
    "serve.pipelined_steps_pct": "pipelined_steps_pct",
    "serve.queue_blocked_steps_pct": "queue_blocked_steps_pct",
    "serve.queue_wait_ms_p50": "queue_wait_ms_p50",
    "serve.host_slack_ms_per_step": "host_slack_ms_per_step",
}


def span(name, a, b, **stats):
    return (name, a, b, "python3", stats)


class Window:
    """A hand-made window: the host's spans and the device's line of one
    stream, programs run in the order they were enqueued."""

    def __init__(self, t0=1000 * MS, seq=100):
        self.t, self.seq = t0, seq
        self.spans, self.modules = [], []
        self.free_at = t0           # the device's queue drains here
        self.flying = []            # (seq that enqueued it, its end)
        self.slack = {}             # seq -> true ns its program sat queued
        self.run_of = []            # the truth: (seq, (start, end)) in order

    def enqueue(self, at, seq):
        start = max(at + LAUNCH, self.free_at)
        self.free_at = start + PROGRAM
        self.modules.append(("jit_serve_decode(7)", start, self.free_at))
        self.flying.append((seq, self.free_at))
        self.run_of.append((seq, (start, self.free_at)))
        return start

    def quiet(self, t, held_by):
        # (the profiler keeps no empty statistic: a quiet test has none)
        stats = {"held_by": held_by} if held_by else {}
        return [span("serve.quiet", t, t + 100 * US, **stats),
                span("serve.reserve", t + 20 * US, t + 80 * US, grown=0)]

    def read(self, t):
        """The wait on the oldest program in flight, and its booking."""
        seq, end = self.flying.pop(0)
        done = max(t + 10 * US, end + READ)
        return [span("serve.wait", t, done, waits_for=seq),
                span("serve.book", done + 10 * US, done + 200 * US,
                     tokens=16)], done + 300 * US

    def step(self, order, held_by="", admits=(), prefill=False,
             host_ms=1.0):
        t0, seq = self.t, self.seq
        children, t = [], t0 + 50 * US
        if order in ("pipelined", "drain"):
            children += self.quiet(t, held_by)
            t += int(host_ms * MS)
            if order == "pipelined":
                d = span("serve.dispatch", t, t + 400 * US,
                         program="serve_decode", programs=1)
                start = self.enqueue(t + 300 * US, seq)
                self.slack[seq] = start - d[2]
                children.append(d)
                t += 450 * US
            got, t = self.read(t)
            children += got
        elif order in ("sync", "enter"):
            for rid, admitted, queue_ms, blocked in admits:
                stats = {"blocked_by": blocked, "need": 8, "free": 70 + rid % 3,
                         "headroom": 71} if blocked else {}
                children.append(span("serve.admit", t, t + 100 * US,
                                     request_id=rid, queue_ms=queue_ms,
                                     admitted=admitted, **stats))
                t += 120 * US
            if prefill:
                children.append(span("serve.prefill", t, t + 2 * MS,
                                     request_id=1, tokens=512))
                self.free_at = max(self.free_at, t) + 20 * MS   # the chunk
                t += 2 * MS
            children += self.quiet(t, held_by)
            t += int(host_ms * MS)
            programs = 2 if order == "enter" else 1
            children.append(span("serve.dispatch", t, t + 400 * US * programs,
                                 program="serve_decode", programs=programs))
            for i in range(programs):
                self.enqueue(t + 300 * US * (i + 1), seq)
            got, t = self.read(t + 400 * US * programs + 50 * US)
            children += got
        else:                       # idle: an admission that waits, no slot
            for rid, admitted, queue_ms, blocked in admits:
                children.append(span("serve.admit", t, t + 100 * US,
                                     request_id=rid, queue_ms=queue_ms,
                                     admitted=admitted, blocked_by=blocked))
            t += 500 * US
        self.spans += [span("serve.step", t0, t, seq=seq, order=order,
                            cpu_ms=1.0, overlapped=0), *children]
        self.t, self.seq = t + 100 * US, seq + 1     # the client's share

    def gaps(self):
        """The device's idle intervals over the window."""
        out, at = [], self.spans[0][1]
        for _, a, b in sorted(self.modules, key=lambda m: m[1]):
            if a > at:
                out.append((at, a))
            at = max(at, b)
        return out

    def orders(self, shift=0):
        modules = [(n, a + shift, b + shift) for n, a, b in self.modules]
        return step_order.orders(self.spans, modules, self.gaps(), 0, 2 ** 62)


def closed_loop(admissions=24):
    """A reply ends every sixth step: a drain (its budget), a synchronous
    chunk step that admits the next request after it waited two steps for
    pages, entering the pipeline, then pipelined steps."""
    w = Window()
    for i in range(admissions):
        w.step("enter", admits=[(i, 1, 25.0 + i, None)], prefill=True)
        for _ in range(3):
            w.step("pipelined")
        w.step("drain", held_by="budget")
        w.step("sync", held_by="queued",
               admits=[(i + 1, 0, 5.0, "pages")])
    return w


def test_the_four_numbers_from_hand_made_spans():
    w = closed_loop()
    got = w.orders()
    stats, line = got["stats"], got["line"]
    # 24 x (enter with a prefill, 3 pipelined, drain, sync): the entering
    # steps ran a chunk, so five of six are decode steps, three pipelined
    assert stats["pipelined_steps_pct"] == pytest.approx(100 * 3 / 5)
    assert stats["queue_blocked_steps_pct"] == pytest.approx(100 / 6)
    waits = [25.0 + i for i in range(24)]
    assert stats["queue_wait_ms_p50"] == percentile(waits, 0.5)
    # the program a pipelined step enqueued sat behind the one in flight
    truth = sum(w.slack.values()) / len(w.slack) / 1e6
    assert 5.0 < truth < PROGRAM / 1e6
    assert stats["host_slack_ms_per_step"] == pytest.approx(truth, abs=1e-6)
    assert {k: v["steps"] for k, v in line["step_orders"].items()} == {
        "pipelined": 72, "enter": 24, "drain": 24, "sync": 24}
    assert line["not_quiet"] == {"budget": 24, "queued": 24}
    assert line["admission"] == {
        "admitted": 24, "blocked": {"pages": 24}, "blocked_steps": 24,
        "blocked_pages_p50": {"need": 8, "free": 71, "headroom": 71},
        "queue_ms_p50": percentile(waits, 0.5),
        "queue_ms_p95": percentile(waits, 0.95)}
    # the counts close, as ISSUE 53 holds every traced run to
    assert sum(v["steps"] for v in line["step_orders"].values()) \
        == line["steps"] == len(program_span.steps_with_children(
            w.spans, 0, 2 ** 62))
    assert sum(line["not_quiet"].values()) \
        == line["step_orders"]["sync"]["steps"] \
        + line["step_orders"]["drain"]["steps"]
    # a pipelined step leaves the device nothing to wait for; the steps
    # around a reply's end do
    rows = line["step_orders"]
    assert rows["pipelined"]["device_idle_ms_mean"] == 0
    assert rows["sync"]["device_idle_ms_mean"] > 0.5
    assert rows["pipelined"]["ms_mean"] == pytest.approx(10.0, abs=0.5)
    assert line["host_slack"]["paired_waits_out_of_order"] == 0


def test_queue_wait_wants_twenty_admissions():
    got = closed_loop(admissions=19).orders()
    assert got["stats"]["queue_wait_ms_p50"] is None
    assert got["line"]["admission"]["queue_ms_p50"] is not None
    assert got["stats"]["pipelined_steps_pct"] == pytest.approx(60.0)


def test_nothing_to_read_without_an_order():
    """The parent of the PR that added ``order``: ``pipelined`` 0/1 on the
    step, no ``serve.quiet``, no ``admitted``: None, whatever else is there."""
    w = closed_loop(admissions=3)
    old = [(n, a, b, th, {**{k: v for k, v in st.items() if k != "order"},
                          "pipelined": int(st.get("order") == "pipelined")})
           if n == "serve.step" else (n, a, b, th, st)
           for n, a, b, th, st in w.spans if n != "serve.quiet"]
    assert step_order.orders(old, w.modules, w.gaps(), 0, 2 ** 62) is None
    assert step_order.read({"step_order_stats": None},
                           {"stat": "pipelined_steps_pct"}) is None
    # a run that was not traced, or not on a device
    assert step_order.read({"trace": None, "trace_dir": None},
                           {"stat": "host_slack_ms_per_step"}) is None


@pytest.mark.parametrize("shift_ms", [-2.0, 0.0, 2.0])
def test_the_join_is_by_order_under_a_shifted_device_line(shift_ms):
    """The device's line 2 ms early or late: every program keeps ITS
    execution (the k-th enqueued is the k-th run), an entering step's two
    among them, and the slack moves by no more than the clocks' tolerance
    (the least shift that restores causality leaves ``OPEN`` open)."""
    w = closed_loop()
    steps = program_span.steps_with_children(w.spans, 0, 2 ** 62)
    shift = int(shift_ms * MS)
    runs = sorted((a + shift, b + shift) for _, a, b in w.modules)
    joined = step_order.join_in_order(steps, runs)
    assert joined["out_of_order"] == 0
    assert [seq for seq, _ in joined["programs"]] \
        == [seq for seq, _ in w.run_of]
    assert len(joined["run_of"]) == len(w.run_of)
    for i, (_, (a, b)) in enumerate(w.run_of):
        assert joined["run_of"][i] == (a + shift, b + shift)
    # an entering step's dispatch holds two programs, back to back
    enter = [i for i, (seq, d) in enumerate(joined["programs"])
             if d[4]["programs"] == 2]
    assert len(enter) == 48
    for first, second in zip(enter[::2], enter[1::2]):
        assert second == first + 1
        assert joined["run_of"][second][0] == joined["run_of"][first][1]
    # what causality leaves open holds the true offset, and the applied
    # shift brings the line back to within it
    assert joined["least"] <= -shift <= joined["most"]
    assert abs(joined["shift"] + shift) <= OPEN
    truth = sum(w.slack.values()) / len(w.slack) / 1e6
    got = w.orders(shift)["stats"]["host_slack_ms_per_step"]
    assert got == pytest.approx(truth, abs=OPEN / 1e6 + 1e-6)
    # by overlap, a pipelined step's round trip holds the program IN FLIGHT
    from benchmarks.readers import step_waterfall
    piped = [(s, c) for s, c in steps if s[4]["order"] == "pipelined"]
    by_overlap, _ = step_waterfall.join(
        piped, [(n, a + shift, b + shift) for n, a, b in w.modules])
    index = {seq: i for i, (seq, _) in enumerate(joined["programs"])}
    mispaired = sum(run != joined["run_of"][index[step[4]["seq"]]]
                    for step, _, _, _, run, _ in by_overlap)
    assert mispaired == len(by_overlap) > 0


def test_a_window_that_opens_on_a_pipeline_in_flight():
    """No synchronous step in the window (the all-decode cells): the first
    wait names a step before the window and pairs with nothing; the second
    anchors the count."""
    w = Window()
    w.step("enter")
    for _ in range(30):
        w.step("pipelined")
    cut = w.spans[0][2]     # the window opens after the entering step
    steps = program_span.steps_with_children(w.spans, cut, 2 ** 62)
    assert {s[4]["order"] for s, _ in steps} == {"pipelined"}
    runs = sorted((a, b) for _, a, b in w.modules)
    joined = step_order.join_in_order(steps, runs)
    assert joined["out_of_order"] == 0 and joined["paired_waits"] == 29
    truth = dict(w.run_of[2:])          # step 100 enqueued the first two
    for i, (seq, _) in enumerate(joined["programs"]):
        assert joined["run_of"][i] == truth[seq]
    got = step_order.orders(w.spans, w.modules, w.gaps(), cut, 2 ** 62)
    assert got["stats"]["pipelined_steps_pct"] == 100.0
    assert got["stats"]["queue_blocked_steps_pct"] == 0.0
    assert got["stats"]["queue_wait_ms_p50"] is None
    assert got["stats"]["host_slack_ms_per_step"] == pytest.approx(
        sum(w.slack.values()) / len(w.slack) / 1e6, abs=1e-6)


def test_a_host_that_sets_the_pace_reads_no_slack():
    """A host slower than the program: the device's queue is empty when a
    pipelined step enqueues, and the program starts at once."""
    w = Window()
    w.step("enter")
    for _ in range(10):
        w.step("pipelined", host_ms=12.0)
    got = w.orders()
    assert got["stats"]["host_slack_ms_per_step"] < LAUNCH / 1e6
    assert got["line"]["step_orders"]["pipelined"]["device_idle_ms_mean"] > 1


def test_a_program_that_starts_inside_its_dispatch_reads_below_zero():
    """Nothing is clamped: a program that started while the dispatch that
    enqueued it was still open reads negative and is counted, so a join off
    by one or a wrong clock shift shows in the line and in the metric."""
    w = Window()
    w.step("enter")
    for _ in range(10):
        w.step("pipelined", host_ms=12.0)
    sound = w.orders()
    assert sound["line"]["host_slack"]["steps_negative"] == 0
    assert sound["line"]["host_slack"]["ms_min"] == pytest.approx(0.1)
    name, a, b = w.modules[5]
    w.modules[5] = (name, a - 300 * US, b)      # 200 us before its close
    got = w.orders()
    assert got["line"]["host_slack"]["steps_negative"] == 1
    assert got["line"]["host_slack"]["ms_min"] == pytest.approx(-0.2)
    assert got["stats"]["host_slack_ms_per_step"] == pytest.approx(
        sound["stats"]["host_slack_ms_per_step"] - 0.3 / 10)
    assert got["line"]["admission"]["blocked_pages_p50"] == {}


@pytest.mark.parametrize("metric,stat", sorted(METRICS.items()))
def test_the_metric_is_listed_where_its_reader_finds_something(metric, stat):
    spec = json.loads(
        (ROOT / "benchmarks" / "metrics" / f"{metric}.json").read_text())
    assert spec == {"reader": "step_order", "params": {"stat": stat}}
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == metric]
    assert entry["source"] == "program_span"
    assert entry["moves"] == "serve.out_tokens_per_s"
    (moved,) = [m for m in BENCH["end_to_end"]
                if m["name"] == "serve.out_tokens_per_s"]
    assert entry["workloads"] and set(entry["workloads"]) \
        <= set(moved["workloads"])
    # appended: nothing that was there moved
    names = [m["name"] for m in BENCH["per_layer"]]
    assert set(names[-4:]) == set(METRICS)
