"""Fleet router (serve/router.py): prefix-affinity key properties
(page-aligned proper prefix, stable across prefill mode / kv dtype,
random-fleet property test), load-aware + rendezvous routing, 429
spillover honoring retry_after_s, heartbeat fencing with bitwise
resubmission replay, the structured resubmit-exhausted give-up, drain,
readiness gates, and the HTTP layer's Retry-After / /readyz / graceful
drain. Env-knob chaos drills live in test_chaos_serve.py.
"""
import dataclasses
import json
from http.client import HTTPConnection

import jax
import jax.numpy as jnp
import pytest

from distributed_training_guide_tpu.models import get_model
from distributed_training_guide_tpu.serve import (RefusalError, Request,
                                                  ServeEngine)
from distributed_training_guide_tpu.serve.api import generate_many, serve_http
from distributed_training_guide_tpu.serve.router import (
    Replica, Router, local_fleet, prefix_affinity_key, readiness,
    rendezvous_order, replica_load)

pytestmark = [pytest.mark.serve, pytest.mark.router]


@pytest.fixture(scope="module")
def llama():
    bundle = get_model("llama-debug", dtype=jnp.float32)
    return bundle, bundle.init(bundle.config, jax.random.key(0))


def _fresh(req):
    return dataclasses.replace(req, request_id=None)


def _ref(bundle, params, req, **kw):
    eng = ServeEngine(bundle, params, n_slots=1, prefix_cache=False, **kw)
    return generate_many(eng, [_fresh(req)])[0]


# ---- affinity key properties ------------------------------------------------

def test_affinity_key_is_page_aligned_proper_prefix():
    page = 4
    # no full cacheable page -> no key (<= page tokens: the "proper
    # prefix" rule leaves the last token out, exactly PrefixCache.match)
    assert prefix_affinity_key([1, 2, 3], page) is None
    assert prefix_affinity_key([1, 2, 3, 4], page) is None
    key5 = prefix_affinity_key([1, 2, 3, 4, 5], page)
    assert key5 is not None
    # the tail past the aligned prefix does not move the key...
    assert prefix_affinity_key([1, 2, 3, 4, 99], page) == key5
    assert prefix_affinity_key([1, 2, 3, 4, 5, 6, 7, 8], page) == key5
    # ...but one more full page does, and a different prefix does
    assert prefix_affinity_key([1, 2, 3, 4, 5, 6, 7, 8, 9], page) != key5
    assert prefix_affinity_key([9, 2, 3, 4, 5], page) != key5


def test_affinity_key_sees_only_prompt_and_page_size():
    """The stability satellite, at the source: the key is a pure
    function of (prompt, page_size, adapter) — engine config (the prefill
    chunk size, int8 kv_dtype) cannot appear in it because it is never an
    input. Content-hashed, so stable across processes too."""
    import inspect

    sig = inspect.signature(prefix_affinity_key)
    assert list(sig.parameters) == ["prompt_ids", "page_size",
                                    "adapter_id"]
    assert sig.parameters["adapter_id"].default == 0
    # content hash, not Python hash(): a known digest pins cross-process
    # stability (PYTHONHASHSEED cannot move this)
    assert prefix_affinity_key(list(range(8)), 4).hex() == \
        prefix_affinity_key(tuple(range(8)), 4).hex()
    # adapter 0 keys are bitwise the pre-multi-LoRA keys (base traffic
    # keeps its affinity assignments across an upgrade); tenants fork
    # the keyspace because cached pages are namespaced per adapter slot
    assert prefix_affinity_key(list(range(8)), 4, adapter_id=0) == \
        prefix_affinity_key(list(range(8)), 4)
    assert prefix_affinity_key(list(range(8)), 4, adapter_id=1) != \
        prefix_affinity_key(list(range(8)), 4)
    assert prefix_affinity_key(list(range(8)), 4, adapter_id=1) != \
        prefix_affinity_key(list(range(8)), 4, adapter_id=2)


def test_rendezvous_fencing_moves_only_the_fenced_keys():
    names = ["r0", "r1", "r2", "r3"]
    keys = [prefix_affinity_key(list(range(i, i + 8)), 4)
            for i in range(50)]
    before = {k: rendezvous_order(k, names)[0] for k in keys}
    survivors = [n for n in names if n != "r1"]
    for k in keys:
        after = rendezvous_order(k, survivors)[0]
        if before[k] != "r1":
            assert after == before[k], "non-fenced keys must not move"


# ---- routing over fake engines (pure logic, no compiles) --------------------

class FakeEngine:
    def __init__(self, page_size=4, n_slots=4, queued=0, refuse=None):
        self.page_size, self.n_slots = page_size, n_slots
        self.queued, self.refuse = queued, refuse
        self.decode_steps = self.decode_tokens = 0
        self.submitted, self.resubmitted = [], []
        self.draining = False
        self._ids = iter(range(10 ** 6))

    def stats(self):
        return {"n_slots": self.n_slots, "queued": self.queued,
                "active_slots": 0, "pool_occupancy": 0.0,
                "pages_capacity": 10, "pages_free": 10, "pages_held": 0,
                "draining": self.draining}

    def submit(self, request):
        if self.refuse is not None:
            raise self.refuse
        self.submitted.append(request)
        return next(self._ids)

    def resubmit(self, request, generated=(), first_token_at=0.0,
                 submitted_at=None):
        self.resubmitted.append((request, list(generated), submitted_at))
        return next(self._ids)

    def partial_tokens(self):
        return {}

    def step(self):
        return []

    @property
    def has_work(self):
        return False

    def drain(self):
        self.draining = True


def _fake_fleet(n=3, clock=None, **router_kw):
    replicas = [Replica(f"r{i}", FakeEngine(),
                        clock=clock or (lambda: 0.0)) for i in range(n)]
    return Router(replicas, clock=clock or (lambda: 0.0), **router_kw)


def test_affinity_routes_shared_prefix_to_one_replica():
    router = _fake_fleet(3)
    prefix = list(range(8))
    targets = set()
    for i in range(6):
        rid = router.submit(Request(prompt_ids=prefix + [50 + i]))
        targets.add(router._records[rid].replica)
    assert len(targets) == 1
    assert router.counters["affinity_routed"] == 6


def test_keyless_traffic_routes_least_loaded():
    clock = lambda: 0.0  # noqa: E731
    replicas = [Replica("busy", FakeEngine(queued=5), clock=clock),
                Replica("idle", FakeEngine(queued=0), clock=clock)]
    router = Router(replicas, clock=clock)
    for i in range(4):
        rid = router.submit(Request(prompt_ids=[i, i + 1]))  # no key
        assert router._records[rid].replica == "idle"
    assert router.counters["affinity_routed"] == 0


def test_affinity_miss_on_fenced_target_degrades_cleanly():
    """Fencing the affinity winner reroutes its keys; everyone else's
    stay put (rendezvous), and keyless traffic never sees the fence."""
    router = _fake_fleet(3)
    prefix = list(range(8))
    rid = router.submit(Request(prompt_ids=prefix + [1]))
    winner = router._records[rid].replica
    router.replicas[winner].state = "fenced"
    rid2 = router.submit(Request(prompt_ids=prefix + [2]))
    assert router._records[rid2].replica != winner
    assert router._records[rid2].replica in router.replicas


def test_spillover_on_429_respects_retry_after():
    t = [0.0]
    clock = lambda: t[0]  # noqa: E731
    refusal = RefusalError("queue_full", "full", http_status=429,
                           detail={"queue_depth": 9, "retry_after_s": 1.5})
    replicas = [Replica("full", FakeEngine(refuse=refusal), clock=clock),
                Replica("open", FakeEngine(queued=99), clock=clock)]
    router = Router(replicas, clock=clock)
    # "full" is the less-loaded candidate -> tried first -> 429 ->
    # spillover lands on "open" and "full" backs off for retry_after_s
    rid = router.submit(Request(prompt_ids=[1, 2]))
    assert router._records[rid].replica == "open"
    assert router.counters["spillovers"] == 1
    assert router.replicas["full"].unroutable_until == pytest.approx(1.5)
    # inside the backoff window the refusing replica is not even tried
    rid2 = router.submit(Request(prompt_ids=[3, 4]))
    assert router._records[rid2].replica == "open"
    assert router.counters["spillovers"] == 1
    # past the window it becomes routable again
    t[0] = 2.0
    replicas[0].engine.refuse = None
    rid3 = router.submit(Request(prompt_ids=[5, 6]))
    assert router._records[rid3].replica == "full"


def test_all_replicas_refusing_propagates_429_with_hint():
    refusal = RefusalError("queue_full", "full", http_status=429,
                           detail={"queue_depth": 9, "retry_after_s": 0.7})
    clock = lambda: 0.0  # noqa: E731
    replicas = [Replica(f"r{i}", FakeEngine(refuse=refusal), clock=clock)
                for i in range(2)]
    router = Router(replicas, clock=clock)
    with pytest.raises(RefusalError) as exc:
        router.submit(Request(prompt_ids=[1, 2]))
    assert exc.value.http_status == 429
    assert exc.value.retry_after_s == 0.7


def test_no_live_replica_refuses_503():
    router = _fake_fleet(2)
    for replica in router.replicas.values():
        replica.kill()
    with pytest.raises(RefusalError, match="no live") as exc:
        router.submit(Request(prompt_ids=[1, 2]))
    assert exc.value.http_status == 503


def test_draining_replica_is_unroutable():
    router = _fake_fleet(2)
    prefix = list(range(8))
    rid = router.submit(Request(prompt_ids=prefix + [1]))
    winner = router._records[rid].replica
    router.replicas[winner].drain()
    rid2 = router.submit(Request(prompt_ids=prefix + [2]))
    assert router._records[rid2].replica != winner
    assert router.stats()["replicas"][winner]["draining"]


def test_property_random_fleets_route_live_and_deterministically():
    """Property test over random fleets: every routed request lands on a
    live, non-draining replica; keyed requests land on the rendezvous
    winner among live replicas; the same (fleet state, prompt) always
    routes identically."""
    import random

    rng = random.Random(7)
    for trial in range(30):
        n = rng.randint(1, 5)
        clock = lambda: 0.0  # noqa: E731
        replicas = [Replica(f"r{i}", FakeEngine(queued=rng.randint(0, 5)),
                            clock=clock) for i in range(n)]
        router = Router(replicas, clock=clock)
        fenced = [r for r in replicas if rng.random() < 0.3 and n > 1]
        for r in fenced[:n - 1]:
            r.state = "fenced"
        live = [r.name for r in replicas if r.state == "live"]
        if not live:
            continue
        for _ in range(5):
            prompt = [rng.randint(0, 99)
                      for _ in range(rng.randint(1, 12))]
            req = Request(prompt_ids=prompt)
            try:
                rid = router.submit(req)
            except RefusalError:
                assert not live
                continue
            chosen = router._records[rid].replica
            assert chosen in live
            key = prefix_affinity_key(prompt, 4)
            if key is not None:
                assert chosen == rendezvous_order(key, live)[0]
            else:
                loads = {name: replica_load(
                    router.replicas[name].engine.stats())
                    for name in live}
                assert loads[chosen] == min(loads.values())
            # determinism: the identical submit routes identically
            rid2 = router.submit(dataclasses.replace(req, request_id=None))
            assert router._records[rid2].replica == chosen


def test_wedge_is_fenced_by_heartbeat_age_and_resubmitted():
    t = [0.0]
    clock = lambda: t[0]  # noqa: E731
    router = _fake_fleet(2, clock=clock, heartbeat_timeout_s=1.0)
    rid = router.submit(Request(prompt_ids=list(range(8)) + [1]))
    victim = router._records[rid].replica
    other = next(n for n in router.replicas if n != victim)
    router.replicas[victim].wedge()
    # beats stop; within the timeout nothing fences. Step in increments
    # small enough that the HEALTHY replica keeps beating AND the router
    # is never idle long enough to forgive (gap < timeout/2) — only the
    # wedged one's age crosses the timeout. The FIRST step forgives
    # unconditionally (the pre-traffic window is unobserved), so the
    # wedge clock effectively starts there.
    for tick in (0.4, 0.8, 1.2):
        t[0] = tick
        router.step()
        assert router.replicas[victim].state == "live"
    t[0] = 1.6          # victim's last (forgiven) beat t=0.4 -> age 1.2
    router.step()
    assert router.replicas[victim].state == "fenced"
    assert router.replicas[other].state == "live"
    # the in-flight request moved to the backlog and re-placed on the
    # survivor via resubmit (replay path)
    t[0] = 2.0
    router.step()
    record = router._records[rid]
    assert record.replica == other
    assert router.replicas[other].engine.resubmitted
    assert router.counters["fenced"] == 1
    assert router.counters["resubmitted"] == 1


def test_idle_router_gap_does_not_fence_healthy_fleet():
    """Regression (found driving the real HTTP server): the worker only
    steps a router that has work, so replicas don't beat while the fleet
    is idle — the first request after a quiet spell must NOT find
    everyone fenced. Unobserved windows are forgiven; only staleness
    across DRIVEN steps fences."""
    t = [100.0]         # construction happened "long ago" relative to t=0
    clock = lambda: t[0]  # noqa: E731
    router = _fake_fleet(2, clock=clock, heartbeat_timeout_s=1.0)
    t[0] = 200.0        # a 100s idle gap, 100x the timeout
    rid = router.submit(Request(prompt_ids=[1, 2]))
    router.step()
    assert all(r.state == "live" for r in router.replicas.values())
    assert router._records[rid].replica is not None
    assert router.counters["fenced"] == 0


def test_slow_steps_do_not_mask_a_wedged_replica():
    """The dual of idle-gap forgiveness: time spent INSIDE replica.step
    calls is driven time, not idleness — a fleet whose healthy engine
    steps take longer than heartbeat_timeout/2 must still fence a
    wedged replica (forgiveness keys on the end-of-step -> start-of-step
    gap, never on step duration)."""
    t = [0.0]
    clock = lambda: t[0]  # noqa: E731

    class SlowEngine(FakeEngine):
        @property
        def has_work(self):
            return True

        def step(self):
            t[0] += 1.2         # a slow engine iteration, > timeout/2
            return []

    replicas = [Replica("slow", SlowEngine(), clock=clock),
                Replica("wedged", SlowEngine(), clock=clock)]
    router = Router(replicas, clock=clock, heartbeat_timeout_s=2.0)
    rid = router.submit(Request(prompt_ids=[1, 2]))
    router._records[rid].replica = "wedged"   # pin the victim
    router._by_engine[("wedged", router._records[rid].engine_rid)] = rid
    router.replicas["wedged"].wedge()
    for _ in range(4):          # ages 1.2, 2.4 -> fenced on the 2nd+
        router.step()
    assert router.replicas["wedged"].state == "fenced"
    assert router.replicas["slow"].state == "live"
    assert router.counters["resubmitted"] == 1


def test_resubmit_exhausted_is_a_structured_strict_prefix_result():
    t = [0.0]
    clock = lambda: t[0]  # noqa: E731
    router = _fake_fleet(1, clock=clock)
    rid = router.submit(Request(prompt_ids=[1, 2, 3]))
    router._records[rid].generated = [5, 6]       # tokens the router saw
    router.replicas["r0"].kill()
    t[0] = 1.0
    out = router.step()
    assert [r.request_id for r in out] == [rid]
    assert out[0].finish_reason == "resubmit_exhausted"
    assert out[0].generated_ids == [5, 6]
    assert not router.has_work
    assert router.stats()["resubmit_exhausted"] == 1


def test_resubmission_preserves_original_submit_timestamp():
    """Bugfix pin: a fence/spillover resubmission carries the ORIGINAL
    client submit time through to the engine's requeue — TTFT and
    deadline accounting measure from FIRST submit, not from the hop
    (the scheduler would otherwise restamp its clock and a twice-moved
    request would look forever young to its own deadline)."""
    t = [10.0]
    clock = lambda: t[0]  # noqa: E731
    router = _fake_fleet(2, clock=clock)
    rid = router.submit(Request(prompt_ids=[1, 2, 3]))
    record = router._records[rid]
    assert record.submitted_at == 10.0
    victim = record.replica
    other = next(n for n in router.replicas if n != victim)
    record.generated = [5]              # a token the router already saw
    router.replicas[victim].kill()
    t[0] = 25.0
    router.step()                       # fences victim -> backlog
    t[0] = 25.1                         # past the resubmit backoff
    router.step()                       # re-places on the survivor
    assert router._records[rid].replica == other
    assert router._records[rid].submitted_at == 10.0
    req, gen, submitted_at = router.replicas[other].engine.resubmitted[-1]
    assert gen == [5]
    assert submitted_at == 10.0, \
        "resubmission must thread the original client submit time"


# ---- real-engine identity ---------------------------------------------------

def test_fleet_matches_batch1_and_fence_recovery_replays(llama):
    """End-to-end over real engines: a 2-replica fleet completes a mixed
    workload token-identical to batch-1; killing one replica mid-decode
    fences it and every in-flight request resubmits + replays to the
    SAME tokens (shared params + position-keyed sampling)."""
    bundle, params = llama
    reqs = [Request(prompt_ids=[3 + i, 17, 42, 9, 5][:2 + i % 3],
                    max_new_tokens=8, seed=i,
                    temperature=0.7 if i % 2 else 0.0) for i in range(6)]
    router = local_fleet(bundle, params, 2, n_slots=2, page_size=4,
                         max_len=32,
                         router_kw=dict(heartbeat_timeout_s=60.0))
    ids = [router.submit(_fresh(r)) for r in reqs]
    done, it = {}, 0
    while router.has_work:
        if it == 3:       # mid-decode, no env knob: the direct API
            router.replicas["r0"].kill()
        for res in router.step():
            done[res.request_id] = res
        it += 1
        assert it < 3000
    assert router.stats()["fenced"] == 1
    for rid, req in zip(ids, reqs):
        want = _ref(bundle, params, req, page_size=4, max_len=32)
        assert done[rid].token_ids == want.token_ids, f"seed={req.seed}"
    # survivor audit: pool balanced after the drain
    surv = router.replicas["r1"].engine
    assert surv.scheduler.pool.n_free \
        + surv.scheduler.cache_pages_held() == surv.scheduler.pool.capacity


@pytest.mark.slow
def test_routing_choice_identical_across_engine_configs(llama):
    """The affinity-stability satellite, end to end (the heavy fleet
    grid — 6 engines; the tier-1 pin of the same property is
    test_affinity_key_sees_only_prompt_and_page_size): fleets whose
    replicas differ in prefill chunk size (the engine's own vs 4) and kv
    dtype (fp32 vs int8) route the same prompts to the same replica NAMES —
    the key never sees engine config, so cache locality survives
    heterogeneous rollouts (e.g. an int8 canary)."""
    bundle, params = llama
    prompts = [list(range(1, 9)) + [50 + i] for i in range(3)] \
        + [[9, 8, 7, 6, 5, 4, 3, 2] + [70 + i] for i in range(3)]
    choices = {}
    for tag, kw in (("own_fp32", {}),
                    ("chunk_fp32", dict(prefill_chunk=4)),
                    ("own_int8", dict(kv_dtype="int8"))):
        router = local_fleet(bundle, params, 2, n_slots=2, page_size=4,
                             max_len=16, **kw)
        routed = []
        for p in prompts:
            rid = router.submit(Request(prompt_ids=list(p),
                                        max_new_tokens=2))
            routed.append(router._records[rid].replica)
        choices[tag] = routed
        while router.has_work:
            router.step()
    assert choices["own_fp32"] == choices["chunk_fp32"] \
        == choices["own_int8"]


# ---- readiness + HTTP satellites -------------------------------------------

def test_readiness_gates():
    ok = {"ok": True, "draining": False, "n_slots": 4, "max_queue": 8,
          "queued": 0, "pages_free": 10}
    assert readiness(ok) == (True, [])
    assert readiness({**ok, "draining": True})[1] == ["draining"]
    assert readiness({**ok, "queued": 8})[1] == ["queue_depth"]
    assert readiness({**ok, "pages_free": 1})[1] == ["pool_headroom"]
    assert readiness({**ok, "ok": False})[1] == ["engine_dead"]
    assert readiness(ok, loop_age_s=9.0, heartbeat_timeout_s=2.0)[1] \
        == ["heartbeat_stale"]
    ready, reasons = readiness({**ok, "draining": True, "queued": 99})
    assert not ready and set(reasons) == {"draining", "queue_depth"}
    # no max_queue -> the 8x-slots default watermark
    assert readiness({**ok, "max_queue": None, "queued": 32})[1] \
        == ["queue_depth"]


@pytest.mark.stream
def test_http_readyz_retry_after_and_graceful_drain(llama):
    """The HTTP trio: /readyz flips 200 -> 503 (reason 'draining') when
    the engine drains; a post-drain submit gets 503 with a real
    Retry-After header + the float hint in the body; and
    worker.stop(drain=True) completes the in-flight request instead of
    failing it."""
    bundle, params = llama
    eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=16)
    server, worker = serve_http(eng, port=0)
    port = server.server_address[1]
    try:
        conn = HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("GET", "/readyz")
        resp = conn.getresponse()
        assert resp.status == 200
        assert json.loads(resp.read())["ready"] is True

        # one in-flight request, then drain mid-service
        import threading

        fut = worker.submit(Request(prompt_ids=[3, 17], max_new_tokens=4))
        stopper = threading.Thread(
            target=lambda: worker.stop(drain=True, timeout_s=30.0))
        stopper.start()
        fut["event"].wait(timeout=30)
        assert fut["error"] is None and fut["result"] is not None
        stopper.join(timeout=30)

        conn.request("GET", "/readyz")
        resp = conn.getresponse()
        assert resp.status == 503
        assert "draining" in json.loads(resp.read())["reasons"]

        conn.request("POST", "/generate", body=json.dumps(
            {"prompt_ids": [3, 17], "max_new_tokens": 2}),
            headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 503
        assert resp.getheader("Retry-After") is not None
        assert int(resp.getheader("Retry-After")) >= 1
        body = json.loads(resp.read())
        assert body["reason"] == "draining"
        assert body["retry_after_s"] > 0
        conn.close()
    finally:
        server.shutdown()
        worker.stop()


@pytest.mark.stream
def test_router_serves_http_unchanged(llama):
    """api.py over a FLEET: the router implements the engine surface, so
    POST /generate and /healthz work with zero HTTP-layer changes."""
    bundle, params = llama
    router = local_fleet(bundle, params, 2, n_slots=2, page_size=4,
                         max_len=16)
    server, worker = serve_http(router, port=0)
    port = server.server_address[1]
    try:
        conn = HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("POST", "/generate", body=json.dumps(
            {"prompt_ids": [3, 17, 42], "max_new_tokens": 4}),
            headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        got = json.loads(resp.read())
        want = _ref(bundle, params,
                    Request(prompt_ids=[3, 17, 42], max_new_tokens=4),
                    page_size=4, max_len=16)
        assert got["token_ids"] == want.token_ids
        conn.request("GET", "/healthz")
        health = json.loads(conn.getresponse().read())
        assert health["router"] is True and health["live_replicas"] == 2
        conn.close()
    finally:
        server.shutdown()
        worker.stop()


def test_mixed_page_size_fleet_rejected(llama):
    bundle, params = llama
    r0 = Replica("r0", FakeEngine(page_size=4))
    r1 = Replica("r1", FakeEngine(page_size=8))
    with pytest.raises(ValueError, match="page_size"):
        Router([r0, r1])
    with pytest.raises(ValueError, match="unique"):
        Router([Replica("x", FakeEngine()), Replica("x", FakeEngine())])
