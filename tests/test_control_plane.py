"""Open-loop load harness (serve/loadgen.py) + SLO control plane
(serve/controller.py), pure logic — no compiles: Poisson/trace schedule
determinism, the arrival-burst fault knob, scenario/goodput accounting,
controller hysteresis + cooldowns, two-phase drain-before-remove
scale-down (with the chaos-abandon races), the degradation ladder's
declared order and unwind, the staleness fence, and a randomized
property drill over chaotic stats traces. Real-engine chaos drills live
in test_chaos_serve.py.
"""
import dataclasses
import random

import pytest

from distributed_training_guide_tpu.serve.controller import SLO, Controller
from distributed_training_guide_tpu.serve.loadgen import (
    LoadReport, build_schedule, default_scenarios, percentile,
    poisson_arrivals, run_open_loop, summarize, trace_arrivals)
from distributed_training_guide_tpu.serve.router import Replica, Router
from distributed_training_guide_tpu.serve.scheduler import (RefusalError,
                                                            Request,
                                                            RequestResult)
from distributed_training_guide_tpu.utils import faults

pytestmark = [pytest.mark.serve, pytest.mark.loadgen, pytest.mark.control]


# ---- arrival schedules ------------------------------------------------------

def test_poisson_arrivals_deterministic_monotone_and_rate_shaped():
    a = poisson_arrivals(8.0, 10.0, seed=3)
    b = poisson_arrivals(8.0, 10.0, seed=3)
    assert a == b, "the trace is a pure function of (rate, duration, seed)"
    assert a != poisson_arrivals(8.0, 10.0, seed=4)
    assert all(0 <= t < 10.0 for t in a)
    assert a == sorted(a)
    # ~80 expected arrivals; a factor-2 band is loose enough to never
    # flake on a fixed seed and tight enough to catch a rate bug
    assert 40 <= len(a) <= 160
    with pytest.raises(ValueError):
        poisson_arrivals(0.0, 1.0)


def test_arrival_burst_fault_multiplies_rate_in_window(monkeypatch):
    monkeypatch.setenv(faults.ENV_ARRIVAL_BURST, "6@1.0:2.0")
    arrivals = poisson_arrivals(10.0, 3.0, seed=0)
    per_second = [sum(1 for t in arrivals if s <= t < s + 1)
                  for s in range(3)]
    # seconds 0 and 2 run at 10 rps, second 1 at 60 rps — the burst
    # second must dominate both flanks decisively (deterministic seed)
    assert per_second[1] > 2 * max(per_second[0], per_second[2])
    monkeypatch.delenv(faults.ENV_ARRIVAL_BURST)
    base = poisson_arrivals(10.0, 3.0, seed=0)
    assert arrivals != base, "the knob must actually reshape the trace"


def test_trace_arrivals_sorts_and_rejects_negative():
    assert trace_arrivals([3.0, 1.0, 2.0]) == [1.0, 2.0, 3.0]
    assert trace_arrivals([]) == []
    with pytest.raises(ValueError):
        trace_arrivals([1.0, -0.5])


# ---- scenarios + schedule ---------------------------------------------------

def test_default_scenarios_always_fit_the_engine_budget():
    """Every sampled request must fit max_len (prompt + generation):
    refusals in a sweep should be backpressure, never a bad request."""
    rng = random.Random(0)
    for max_len, page in ((32, 4), (128, 16)):
        scenarios = default_scenarios(max_len=max_len, page_size=page,
                                      vocab=256, deadline_s=1.0)
        names = {s.name for s in scenarios}
        assert {"chat", "long_prompt", "long_gen", "urgent",
                "batch"} <= names
        for s in scenarios:
            for i in range(50):
                req = s.sample(rng, 256, i)
                assert len(req.prompt_ids) + req.max_new_tokens <= max_len
                assert all(0 < t < 256 for t in req.prompt_ids)
                assert req.priority == s.priority
        chat = next(s for s in scenarios if s.name == "chat")
        assert chat.shared_prefix, "chat turns share a system prompt"
        urgent = next(s for s in scenarios if s.name == "urgent")
        batch = next(s for s in scenarios if s.name == "batch")
        assert urgent.deadline_s < batch.deadline_s
        assert urgent.priority > batch.priority


def test_build_schedule_is_deterministic_and_preserves_arrivals():
    scenarios = default_scenarios(max_len=32, page_size=4, vocab=128)
    arrivals = poisson_arrivals(5.0, 4.0, seed=1)
    s1 = build_schedule(arrivals, scenarios, vocab=128, seed=2)
    s2 = build_schedule(arrivals, scenarios, vocab=128, seed=2)
    assert [t for t, _ in s1] == arrivals
    assert [(t, r.prompt_ids, r.max_new_tokens, r.priority)
            for t, r in s1] \
        == [(t, r.prompt_ids, r.max_new_tokens, r.priority)
            for t, r in s2]


# ---- report accounting ------------------------------------------------------

def _result(rid, reason="eos", submitted=0.0, first=0.5, finished=1.0,
            n_gen=4):
    return RequestResult(request_id=rid, prompt_ids=[1, 2],
                         generated_ids=list(range(n_gen)),
                         finish_reason=reason, submitted_at=submitted,
                         admitted_at=submitted, finished_at=finished,
                         first_token_at=first)


def test_summarize_goodput_and_tails():
    schedule = [(float(i), Request(prompt_ids=[1, 2])) for i in range(6)]
    results = {
        0: _result(0, "eos", submitted=0.0, first=0.2, finished=1.0),
        1: _result(1, "length", submitted=1.0, first=1.4, finished=2.0),
        2: _result(2, "deadline", submitted=2.0, first=0.0, n_gen=0),
        3: _result(3, "resubmit_exhausted", submitted=3.0, first=3.1,
                   n_gen=2),
    }
    rep = summarize(schedule, results, [(4.0, "queue_full"),
                                        (5.0, "shed_low_priority")],
                    wall_s=10.0)
    assert rep.offered == 6 and rep.submitted == 4 and rep.refused == 2
    assert rep.completed == 2 and rep.deadline_met == 2
    assert rep.deadline_missed == 1 and rep.resubmit_exhausted == 1
    assert rep.goodput_rps == pytest.approx(0.2)
    assert rep.refusal_rate == pytest.approx(2 / 6, abs=1e-3)
    assert rep.refused_by_reason == {"queue_full": 1,
                                     "shed_low_priority": 1}
    # TTFT measured from client submit (the resubmission bugfix's
    # observable): request 1 submitted at 1.0, first token 1.4
    assert rep.ttft_p50_s in (pytest.approx(0.2), pytest.approx(0.4))
    assert isinstance(rep.as_dict(), dict)


def test_percentile_nearest_rank():
    vals = [0.1, 0.2, 0.3, 0.4]
    assert percentile([], 0.99) == 0.0
    assert percentile(vals, 0.0) == 0.1
    assert percentile(vals, 1.0) == 0.4
    assert percentile(vals, 0.5) in vals, "never invents a value"


# ---- the controller over a fake fleet ---------------------------------------

class CtlEngine:
    """Engine-shaped stats source the controller (via a real Router)
    observes: every knob the control law reads is a writable field."""

    def __init__(self, page_size=4, n_slots=4):
        self.page_size, self.n_slots = page_size, n_slots
        self.queued = 0
        self.active = 0
        self.finished = 0
        self.missed = 0
        self.working = False
        self.decode_steps = self.decode_tokens = 0
        self.draining = False
        self.closed = False
        self._ids = iter(range(10 ** 6))

    def stats(self):
        return {"n_slots": self.n_slots, "queued": self.queued,
                "active_slots": self.active, "pool_occupancy": 0.0,
                "pages_capacity": 10, "pages_free": 10, "pages_held": 0,
                "finished": self.finished,
                "deadline_missed_queued": self.missed,
                "draining": self.draining, "max_queue": 64}

    def submit(self, request):
        return next(self._ids)

    def resubmit(self, request, generated=(), first_token_at=0.0,
                 submitted_at=None):
        return next(self._ids)

    def partial_tokens(self):
        return {}

    def step(self):
        return []

    @property
    def has_work(self):
        return self.working

    def drain(self):
        self.draining = True

    def close(self):
        self.closed = True


def _ctl_fleet(n=2, t=None, **ctl_kw):
    t = t if t is not None else [0.0]
    clock = lambda: t[0]  # noqa: E731
    replicas = [Replica(f"r{i}", CtlEngine(), clock=clock)
                for i in range(n)]
    router = Router(replicas, clock=clock,
                    heartbeat_timeout_s=10 ** 9)
    spawned = iter(range(100))
    ctl_kw.setdefault(
        "spawn", lambda: Replica(f"n{next(spawned)}", CtlEngine(),
                                 clock=clock))
    ctl = Controller(router, **ctl_kw)
    return router, ctl, t


def _tick(router, ctl, t, dt=0.1):
    """One observation: advance time, drive the fleet (stats_seq moves),
    then let the controller look."""
    t[0] += dt
    router.step()
    ctl.step()


def test_steady_trace_inside_dead_band_actuates_nothing():
    router, ctl, t = _ctl_fleet(2, hold_up=2, hold_down=3, cooldown_s=0.0)
    for rep in router.replicas.values():
        rep.engine.queued = 1            # between queue_low and queue_high
        rep.engine.active = 3            # slot_occ 6/8 > low -> not under
    for _ in range(50):
        _tick(router, ctl, t)
    assert ctl.actions == []
    assert ctl.state == "steady"
    assert ctl.counters["observations"] == 50


def test_overload_scales_up_after_hold_up_and_records_cold_start():
    router, ctl, t = _ctl_fleet(1, hold_up=3, cooldown_s=0.0,
                                max_replicas=2)
    router.replicas["r0"].engine.queued = 50
    _tick(router, ctl, t)
    _tick(router, ctl, t)
    assert ctl.counters["scale_up"] == 0, "hysteresis: 2 < hold_up"
    _tick(router, ctl, t)
    assert ctl.counters["scale_up"] == 1
    assert len(router.replicas) == 2
    assert ctl.cold_starts and ctl.cold_starts[0] >= 0.0
    up = [a for a in ctl.actions if a["kind"] == "scale_up"]
    assert up and "cold_start_s" in up[0]
    # the spawned replica is routable: keyless traffic prefers it (idle)
    rid = router.submit(Request(prompt_ids=[1, 2]))
    assert router._records[rid].replica == up[0]["target"]


def test_cooldown_gates_membership_and_ladder_fills_the_gap():
    router, ctl, t = _ctl_fleet(1, hold_up=2, cooldown_s=5.0,
                                max_replicas=3)
    router.replicas["r0"].engine.queued = 50
    _tick(router, ctl, t)
    _tick(router, ctl, t)
    assert ctl.counters["scale_up"] == 1
    # overload persists inside the cooldown: membership is gated, so the
    # fleet degrades (shed) instead of flapping replicas
    for rep in router.replicas.values():
        rep.engine.queued = 50
    _tick(router, ctl, t)
    _tick(router, ctl, t)
    assert ctl.counters["scale_up"] == 1
    assert ctl.state == "shed"
    assert router.min_priority == ctl.slo.shed_below_priority
    # past the cooldown the next persistent overload scales up again
    t[0] += 10.0
    _tick(router, ctl, t)
    _tick(router, ctl, t)
    assert ctl.counters["scale_up"] == 2


def test_shed_refuses_low_priority_at_the_front_door():
    router, ctl, t = _ctl_fleet(1, hold_up=1, cooldown_s=0.0,
                                max_replicas=1)
    router.replicas["r0"].engine.queued = 50
    _tick(router, ctl, t)
    assert ctl.state == "shed"
    with pytest.raises(RefusalError) as exc:
        router.submit(Request(prompt_ids=[1, 2], priority=0))
    assert exc.value.reason == "shed_low_priority"
    assert exc.value.http_status == 429
    assert exc.value.retry_after_s > 0
    # priority at/above the bar still admits
    router.submit(Request(prompt_ids=[1, 2], priority=1))
    assert router.stats()["refused"]["shed_low_priority"] == 1


def test_degradation_ladder_order_and_unwind():
    """shed -> backpressure under persistent overload at max capacity;
    unwind in REVERSE as calm holds — and never a rung that touches
    running sequences (the only actuators are admission knobs)."""
    router, ctl, t = _ctl_fleet(1, hold_up=2, hold_down=3, cooldown_s=0.0,
                                max_replicas=1)
    eng = router.replicas["r0"].engine
    eng.queued = 50
    for _ in range(4):
        _tick(router, ctl, t)
    assert [a["kind"] for a in ctl.actions] == ["shed_on",
                                                "backpressure_on"]
    assert ctl.state == "backpressure"
    assert router.retry_after_floor_s == ctl.slo.retry_after_floor_s
    # ... and the tightened hint reaches refused clients
    eng.queued = 1                       # calm (dead band)
    for _ in range(3):
        _tick(router, ctl, t)
    assert ctl.state == "shed"
    assert router.retry_after_floor_s == 0.0
    for _ in range(3):
        _tick(router, ctl, t)
    assert ctl.state == "steady"
    assert router.min_priority is None
    assert [a["kind"] for a in ctl.actions] == [
        "shed_on", "backpressure_on", "backpressure_off", "shed_off"]


def test_scale_down_is_two_phase_drain_then_remove():
    router, ctl, t = _ctl_fleet(2, hold_down=3, cooldown_s=0.0)
    victim_engine = None
    for rep in router.replicas.values():
        rep.engine.queued = 0
    router.replicas["r1"].engine.working = True   # r1 still busy
    for _ in range(3):
        _tick(router, ctl, t)
    # underload held: the least-loaded live replica drains, nothing is
    # removed while it has work
    assert ctl.state == "draining"
    victim = ctl.stats()["draining_victim"]
    victim_engine = router.replicas[victim].engine
    assert victim_engine.draining
    assert len(router.replicas) == 2
    assert ctl.counters["scale_down"] == 0
    _tick(router, ctl, t)
    if victim_engine.working:
        assert len(router.replicas) == 2, "drain incomplete -> no remove"
    victim_engine.working = False
    victim_engine.queued = 0
    _tick(router, ctl, t)
    assert ctl.counters["scale_down"] == 1
    assert victim not in router.replicas
    assert victim_engine.closed, "removed replica's engine is closed"
    assert ctl.state == "steady"
    kinds = [a["kind"] for a in ctl.actions]
    assert kinds.index("drain") < kinds.index("scale_down")


def test_scale_down_abandoned_when_chaos_kills_the_victim():
    router, ctl, t = _ctl_fleet(2, hold_down=2, cooldown_s=0.0)
    router.replicas["r0"].engine.working = True
    router.replicas["r1"].engine.working = True
    for _ in range(2):
        _tick(router, ctl, t)
    assert ctl.state == "draining"
    victim = ctl.stats()["draining_victim"]
    router.replicas[victim].kill()       # chaos wins the race
    _tick(router, ctl, t)                # router fences; controller sees
    assert ctl.state == "steady"
    assert ctl.counters["scale_down_abandoned"] == 1
    assert ctl.counters["scale_down"] == 0, \
        "never remove a corpse that was not drained"


def test_stale_snapshot_is_counted_and_inert():
    router, ctl, t = _ctl_fleet(1, hold_up=1, cooldown_s=0.0,
                                max_replicas=4)
    router.replicas["r0"].engine.queued = 50
    _tick(router, ctl, t)
    n_up = ctl.counters["scale_up"]
    # nobody drives the fleet between polls: stats_seq frozen -> the one
    # legal actuation is NOTHING, however loud the stale numbers are
    for _ in range(10):
        t[0] += 0.1
        ctl.step()
    assert ctl.counters["stale_snapshots"] == 10
    assert ctl.counters["scale_up"] == n_up


def test_actuation_never_targets_fenced_replicas():
    router, ctl, t = _ctl_fleet(3, hold_down=2, cooldown_s=0.0,
                                min_replicas=1)
    router.replicas["r1"].state = "fenced"
    for _ in range(4):
        _tick(router, ctl, t)
    for action in ctl.actions:
        assert action["target"] != "r1"
    assert ctl.stats()["draining_victim"] != "r1"


def test_controller_property_chaotic_traces_respect_invariants():
    """Satellite property drill: drive random load/chaos traces and pin
    (1) membership-channel starts (drain / scale_up) respect cooldown_s
    against the previous membership action, (2) remove_replica only ever
    fires on a drained, idle victim (asserted at the call), (3) the
    controller never raises, whatever chaos does to the fleet."""
    for trial in range(12):
        rng = random.Random(100 + trial)
        t = [0.0]
        clock = lambda: t[0]  # noqa: E731
        replicas = [Replica(f"r{i}", CtlEngine(), clock=clock)
                    for i in range(3)]
        router = Router(replicas, clock=clock, heartbeat_timeout_s=10 ** 9)
        removed_log = []
        original_remove = router.remove_replica

        def checked_remove(name):
            rep = router.replicas[name]
            assert not rep.engine.has_work, \
                "remove_replica on a replica with live work"
            assert rep.engine.draining, "remove without a completed drain"
            removed_log.append(name)
            return original_remove(name)

        router.remove_replica = checked_remove
        spawned = iter(range(100))
        cooldown = rng.choice([0.0, 0.3, 1.0])
        ctl = Controller(
            router, cooldown_s=cooldown,
            hold_up=rng.randint(1, 3), hold_down=rng.randint(1, 4),
            max_replicas=4,
            spawn=lambda: Replica(f"n{next(spawned)}", CtlEngine(),
                                  clock=clock))
        for _ in range(80):
            t[0] += rng.choice([0.05, 0.1, 0.4])
            for rep in list(router.replicas.values()):
                if rep.state != "live":
                    continue
                rep.engine.queued = rng.choice([0, 0, 1, 2, 6, 40])
                rep.engine.working = rng.random() < 0.3
                if rng.random() < 0.03:
                    rep.kill()           # chaos
            router.step()
            ctl.step()                   # must never raise
        membership = [a for a in ctl.actions
                      if a["kind"] in ("drain", "scale_up")]
        anchors = [a for a in ctl.actions
                   if a["kind"] in ("drain", "scale_up", "scale_down")]
        for action in membership:
            prior = [a for a in anchors if a["t"] < action["t"]]
            if prior:
                assert action["t"] - prior[-1]["t"] >= cooldown - 1e-9, \
                    f"membership action inside cooldown: {action}"
        assert ctl.counters["scale_down"] == len(removed_log)


# ---- the open-loop driver over fakes ---------------------------------------

class LoopEngine(CtlEngine):
    """Completes every submitted request after a fixed number of steps —
    enough machinery for run_open_loop's bookkeeping to be pinned
    without a compile."""

    def __init__(self, delay_steps=2, **kw):
        super().__init__(**kw)
        self.delay_steps = delay_steps
        self.pending = []                # (ready_at_step, rid, request)
        self.step_n = 0

    def submit(self, request):
        rid = next(self._ids)
        self.pending.append((self.step_n + self.delay_steps, rid, request))
        return rid

    def resubmit(self, request, generated=(), first_token_at=0.0,
                 submitted_at=None):
        return self.submit(request)

    @property
    def has_work(self):
        return bool(self.pending)

    def step(self):
        self.step_n += 1
        done, keep = [], []
        for ready, rid, req in self.pending:
            if self.step_n >= ready:
                done.append(RequestResult(
                    request_id=rid, prompt_ids=list(req.prompt_ids),
                    generated_ids=[7, 8], finish_reason="eos",
                    submitted_at=0.0, admitted_at=0.0, finished_at=0.1,
                    first_token_at=0.05))
            else:
                keep.append((ready, rid, req))
        self.pending = keep
        self.finished += len(done)
        return done


def test_run_open_loop_submits_on_schedule_and_collects_results():
    t = [0.0]
    clock = lambda: t[0]  # noqa: E731

    def sleep(dt):
        t[0] += dt

    engine = LoopEngine()
    schedule = [(0.0, Request(prompt_ids=[1, 2])),
                (0.5, Request(prompt_ids=[3, 4])),
                (1.0, Request(prompt_ids=[5, 6]))]
    report = run_open_loop(engine, schedule, clock=clock, sleep=sleep)
    assert report.offered == 3 and report.submitted == 3
    assert report.completed == 3 and report.refused == 0
    assert not report.timed_out
    assert report.goodput_rps > 0


def test_run_open_loop_counts_refusals_and_never_blocks_on_them():
    t = [0.0]
    clock = lambda: t[0]  # noqa: E731

    class Refusing(LoopEngine):
        def submit(self, request):
            if request.priority == 0:
                raise RefusalError("queue_full", "full", http_status=429)
            return super().submit(request)

    engine = Refusing()
    schedule = [(0.0, Request(prompt_ids=[1], priority=1)),
                (0.1, Request(prompt_ids=[2], priority=0)),
                (0.2, Request(prompt_ids=[3], priority=1))]
    report = run_open_loop(engine, schedule, clock=clock,
                           sleep=lambda dt: t.__setitem__(0, t[0] + dt))
    assert report.refused == 1 and report.submitted == 2
    assert report.refused_by_reason == {"queue_full": 1}
    assert report.completed == 2


def test_run_open_loop_gives_up_at_max_wall():
    t = [0.0]
    clock = lambda: t[0]  # noqa: E731

    class Stuck(LoopEngine):
        def step(self):
            self.step_n += 1
            t[0] += 0.01                 # time passes, nothing finishes
            return []

    report = run_open_loop(Stuck(), [(0.0, Request(prompt_ids=[1]))],
                           clock=clock, sleep=lambda dt: None,
                           max_wall_s=0.5)
    assert report.timed_out
    assert report.completed == 0
