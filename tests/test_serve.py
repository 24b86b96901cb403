"""Serving engine correctness: continuous-batching output must be
token-identical to the batch-1 sampler whatever the admission order,
co-residency, or slot reuse; KV residency must scale with allocated pages;
backpressure must refuse admission without corrupting running sequences.

Everything here runs debug-size models (2 layers, 64 wide) — each engine
is a handful of tiny compiles, so the suite stays inside tier-1.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_guide_tpu.models import get_model
from distributed_training_guide_tpu.models.sample import make_sampler
from distributed_training_guide_tpu.serve import (Request, ServeEngine,
                                                  kv_page_bytes)
from distributed_training_guide_tpu.serve.api import (generate_many,
                                                      serve_http,
                                                      throughput_stats)

pytestmark = pytest.mark.serve


@pytest.fixture(scope="module")
def llama():
    bundle = get_model("llama-debug", dtype=jnp.float32)
    return bundle, bundle.init(bundle.config, jax.random.key(0))


def _batch1(bundle, params, prompt, steps):
    """The batch-1 kv-cache reference (= the engine at n_slots=1, which
    test_sample.py pins against the independent full-recompute sampler)."""
    return make_sampler(bundle, kv_cache=True)(params, prompt, steps)


# ---- order invariance / continuous batching parity -------------------------

@pytest.mark.parametrize("name", ["llama-debug", "gpt2-debug", "moe-debug"])
def test_engine_matches_batch1_under_continuous_batching(name):
    """8 requests of different lengths through 3 slots: co-residency,
    eviction mid-flight, slot reuse — every request's tokens must equal its
    own batch-1 generation, in BOTH admission orders."""
    over = {"capacity_factor": 4.0} if name == "moe-debug" else {}
    bundle = get_model(name, dtype=jnp.float32, **over)
    params = bundle.init(bundle.config, jax.random.key(0))
    reqs = [Request(prompt_ids=[3 + i, 17, 42][:(i % 3) + 1],
                    max_new_tokens=3 + (i % 4), seed=i) for i in range(8)]
    expect = {i: _batch1(bundle, params, r.prompt_ids, r.max_new_tokens)
              for i, r in enumerate(reqs)}

    for order in (list(range(8)), [5, 2, 7, 0, 3, 6, 1, 4]):
        eng = ServeEngine(bundle, params, n_slots=3, page_size=4, max_len=16)
        res = generate_many(eng, [reqs[i] for i in order])
        for pos, i in enumerate(order):
            assert res[pos].token_ids == expect[i], (
                f"{name}: request {i} diverged when admitted at {pos}")


def test_engine_matches_independent_recompute_reference(llama):
    """Close the loop on the delegation: multi-slot engine output equals
    the FULL-RECOMPUTE sampler (a genuinely independent program — no kv
    cache, no paging), not just the batch-1 engine."""
    bundle, params = llama
    reqs = [Request(prompt_ids=[3, 17, 42, 7], max_new_tokens=6),
            Request(prompt_ids=[5, 6], max_new_tokens=8)]
    res = generate_many(
        ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=32),
        reqs)
    for r in res:
        assert r.token_ids == make_sampler(bundle)(
            params, r.prompt_ids, len(r.generated_ids))


def test_temperature_stream_is_admission_order_invariant(llama):
    """Sampling keys are fold_in(seed, position): a stochastic request
    draws the same tokens whichever slot/iteration it lands in."""
    bundle, params = llama
    reqs = [Request(prompt_ids=[3, 17], max_new_tokens=6, temperature=0.9,
                    top_k=40, top_p=0.9, seed=7),
            Request(prompt_ids=[9, 2, 5], max_new_tokens=6, temperature=0.7,
                    seed=8),
            Request(prompt_ids=[4], max_new_tokens=4, temperature=1.3,
                    seed=9)]
    a = generate_many(
        ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=16),
        reqs)
    b = generate_many(
        ServeEngine(bundle, params, n_slots=3, page_size=4, max_len=16),
        list(reversed(reqs)))
    for i in range(3):
        assert a[i].token_ids == b[2 - i].token_ids
    v = bundle.config.vocab_size
    assert all(0 <= t < v for r in a for t in r.generated_ids)


# ---- slot lifecycle ---------------------------------------------------------

def test_eos_evicts_early_and_frees_the_slot(llama):
    """Set eos to a token the greedy run is known to emit mid-stream: the
    engine must stop there (finish_reason="eos", eos included), free the
    slot, and the queued request behind it must still match batch-1."""
    bundle, params = llama
    prompt = [3, 17, 42, 7]
    full = _batch1(bundle, params, prompt, 6)
    eos = full[len(prompt) + 2]               # greedy emits it as token #3
    reqs = [Request(prompt_ids=prompt, max_new_tokens=6, eos_id=eos),
            Request(prompt_ids=[5, 6], max_new_tokens=8),
            Request(prompt_ids=[9, 2], max_new_tokens=4)]
    eng = ServeEngine(bundle, params, n_slots=1, page_size=4, max_len=16)
    res = generate_many(eng, reqs)
    assert res[0].finish_reason == "eos"
    assert res[0].token_ids == full[:len(prompt) + 3]
    assert res[1].finish_reason == "length"
    assert res[1].token_ids == _batch1(bundle, params, [5, 6], 8)
    assert res[2].token_ids == _batch1(bundle, params, [9, 2], 4)
    # every page reference was released: free + prefix-cache-retained ==
    # capacity (the full prompt page of request 0 stays cached for reuse)
    pool = eng.scheduler.pool
    assert pool.n_free + eng.scheduler.cache_pages_held() == pool.capacity
    assert eng.scheduler.cache_pages_held() == 1   # [3, 17, 42, 7] page


def test_backpressure_refuses_admission_never_corrupts(llama):
    """Pool sized well below the workload's worst case: optimistic
    admission over-admits, growth exhausts the pool, the youngest
    sequences are preempted and recomputed — and every request still
    finishes byte-identical to batch-1, with the pressure visible in the
    blocked/preempted stats and no page leaked at the end."""
    bundle, params = llama
    # each request: 3 prompt + 5 new = 8 tokens = 2 pages of 4; the pool's
    # 3 usable pages cannot hold three such sequences at once
    eng = ServeEngine(bundle, params, n_slots=4, page_size=4, max_len=8,
                      n_pages=4)
    reqs = [Request(prompt_ids=[3 + i, 17, 42], max_new_tokens=5, seed=i)
            for i in range(3)]
    res = generate_many(eng, reqs, max_iterations=500)
    for r in res:
        assert r.token_ids == _batch1(bundle, params, r.prompt_ids, 5)
    stats = eng.scheduler.stats
    assert stats["admission_blocked"] + stats["preempted"] > 0
    pool = eng.scheduler.pool
    assert pool.n_free + eng.scheduler.cache_pages_held() == pool.capacity


def test_impossible_request_refused_at_submit(llama):
    bundle, params = llama
    eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=16,
                      n_pages=3)
    with pytest.raises(ValueError, match="whole pool"):
        eng.submit(Request(prompt_ids=[1, 2, 3], max_new_tokens=10))
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(Request(prompt_ids=[1] * 10, max_new_tokens=10))
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(Request(prompt_ids=[]))


def test_unservable_configs_refused_up_front(llama):
    """Requests/configs that would crash mid-flight (seed past int32, a
    chunk program of no width) must refuse at submit / construction,
    before any slot or page is committed; the engine's own chunk size is
    an integer no wider than one slot's capacity."""
    bundle, params = llama
    eng = ServeEngine(bundle, params, n_slots=1, page_size=4, max_len=16)
    with pytest.raises(ValueError, match="seed"):
        eng.submit(Request(prompt_ids=[1], seed=2 ** 31))
    with pytest.raises(ValueError, match="top_k"):
        eng.submit(Request(prompt_ids=[1], top_k=2 ** 31))
    with pytest.raises(ValueError, match="vocab_size"):
        eng.submit(Request(prompt_ids=[bundle.config.vocab_size]))
    from distributed_training_guide_tpu.serve.engine import (
        DEFAULT_PREFILL_CHUNK, resolve_prefill_chunk)

    for bad in (0, -4):
        with pytest.raises(ValueError, match="prefill_chunk"):
            ServeEngine(bundle, params, n_slots=1, page_size=4, max_len=32,
                        prefill_chunk=bad)
    assert eng.prefill_chunk == 16          # min(the ceiling, 4 pages x 4)
    assert resolve_prefill_chunk(None, max_pages=256, page_size=16) \
        == DEFAULT_PREFILL_CHUNK == 512
    assert resolve_prefill_chunk(24, max_pages=4, page_size=4) == 24


def test_engine_thread_death_fails_waiters_loudly(llama, monkeypatch):
    """If the engine thread hits an unexpected error, pending HTTP waiters
    get a 500 (not an eternal hang), /healthz flips unhealthy, and new
    submits are refused with 503."""
    import http.client
    import json
    import time as _t

    bundle, params = llama
    eng = ServeEngine(bundle, params, n_slots=1, page_size=4, max_len=16)

    def boom(*a, **k):
        raise RuntimeError("injected engine fault")

    monkeypatch.setattr(eng, "step", boom)
    server, worker = serve_http(eng, port=0)
    port = server.server_address[1]
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request("POST", "/generate",
                     json.dumps({"prompt_ids": [3], "max_new_tokens": 2}))
        resp = conn.getresponse()
        assert resp.status == 500
        assert "injected engine fault" in json.loads(resp.read())["error"]
        deadline = _t.monotonic() + 10
        while worker.dead is None and _t.monotonic() < deadline:
            _t.sleep(0.01)
        conn.request("GET", "/healthz")
        assert json.loads(conn.getresponse().read())["ok"] is False
        conn.request("POST", "/generate",
                     json.dumps({"prompt_ids": [3], "max_new_tokens": 2}))
        assert conn.getresponse().status == 503
        conn.close()
    finally:
        server.shutdown()
        worker.stop()


def test_throughput_stats_shape(llama):
    bundle, params = llama
    eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=16)
    import time as _t

    t0 = _t.perf_counter()
    res = generate_many(eng, [Request(prompt_ids=[3, 17], max_new_tokens=4,
                                      seed=s) for s in range(2)])
    stats = throughput_stats(res, _t.perf_counter() - t0, eng)
    assert stats["generated_tokens"] == 8
    assert stats["tokens_per_s"] > 0
    assert 0 < stats["decode_occupancy"] <= 1.0
    assert stats["n_requests"] == 2


# ---- memory pin -------------------------------------------------------------

def test_kv_residency_scales_with_pages_not_slots_times_maxlen(llama):
    """The acceptance-criteria pin. (a) live buffers: the engine's resident
    KV bytes equal the page-pool formula and sit well under the dense
    n_slots x max_len cache; (b) lowered HLO: the compiled decode step's
    cache operands/results ARE the pool shape — the program carries no
    [n_slots, max_len] resident cache."""
    bundle, params = llama
    cfg = bundle.config
    n_slots, page, max_len = 8, 16, 256
    # pool sized at 1/4 of full residency: 32 usable pages + trash
    eng = ServeEngine(bundle, params, n_slots=n_slots, page_size=page,
                      max_len=max_len, n_pages=33)

    assert eng.kv_cache_bytes() == kv_page_bytes(cfg, page_size=page,
                                                 n_pages=33)
    # a dense cache: k and v of [L, n_slots, max_len, kv_heads, head_dim]
    dense_bytes = 2 * (cfg.num_layers * n_slots * max_len * cfg.num_kv_heads
                       * cfg.head_size * jnp.dtype(cfg.dtype).itemsize)
    assert eng.kv_cache_bytes() < dense_bytes / 3.5

    # (b) lower the ONE decode program and inspect its kv operands
    arr = eng.scheduler.decode_arrays()
    lowered = eng._decode_fn.lower(
        eng.params, eng.pages,
        jnp.asarray(arr["tokens"]), jnp.asarray(arr["lengths"]),
        jnp.asarray(arr["tables"]), jnp.asarray(arr["seeds"]),
        jnp.asarray(arr["temps"]), jnp.asarray(arr["top_ks"]),
        jnp.asarray(arr["top_ps"]), jnp.asarray(arr["actives"]))
    pool_shape = (cfg.num_layers, 33, page, cfg.num_kv_heads, cfg.head_size)
    avals = jax.tree.leaves(lowered.in_avals)
    assert sum(a.shape == pool_shape for a in avals) == 2   # k and v pools
    dense_shape = (cfg.num_layers, n_slots, max_len, cfg.num_kv_heads,
                   cfg.head_size)
    assert not any(a.shape == dense_shape for a in avals)
    out_avals = jax.tree.leaves(lowered.out_info)
    assert sum(tuple(a.shape) == pool_shape for a in out_avals) == 2

    # the under-provisioned pool still serves (backpressure, not OOM): 8
    # co-resident 40-token requests would need 8x3=24 pages of the 32
    reqs = [Request(prompt_ids=[3 + i, 5], max_new_tokens=38, seed=i)
            for i in range(8)]
    res = generate_many(eng, reqs)
    assert all(len(r.generated_ids) == 38 for r in res)


# ---- prefix sharing / copy-on-write ----------------------------------------

def _drain(eng, max_iters=3000):
    """Step the engine until idle, collecting every finished result."""
    out, it = [], 0
    while eng.has_work:
        out.extend(eng.step())
        it += 1
        assert it < max_iters, "engine stalled"
    return out


def _ref_engine(bundle, params, **kw):
    """A fresh batch-1 reference engine (no sharing — the independent
    baseline every feature must match token-for-token)."""
    return ServeEngine(bundle, params, n_slots=1, prefix_cache=False, **kw)


def _fresh(req):
    """A copy of the request without its assigned id (re-submittable)."""
    import dataclasses

    return dataclasses.replace(req, request_id=None)


def test_prefix_sharing_same_physical_pages_and_bytes(llama):
    """The acceptance pin: slots sharing a 2-page prefix hold refcounted
    references to the SAME physical pages; resident pages for n co-liers
    beat unshared by exactly the (n-1) * shared_pages the formula
    predicts; and everything still matches batch-1."""
    bundle, params = llama
    common = [9, 8, 7, 6, 5, 4, 3, 2]          # 2 full shared pages
    eng = ServeEngine(bundle, params, n_slots=4, page_size=4, max_len=32)
    # seed the cache: one request commits + registers the common prefix
    generate_many(eng, [Request(prompt_ids=common + [10], max_new_tokens=2)])
    assert eng.scheduler.cache_pages_held() == 2
    pool = eng.scheduler.pool
    base_used = pool.capacity - pool.n_free

    reqs = [Request(prompt_ids=common + [11 + i], max_new_tokens=8, seed=i)
            for i in range(4)]
    rids = [eng.submit(r) for r in reqs]
    eng.step()                                  # admit + prefill all four
    slots = [s for s in eng.scheduler.slots if s is not None]
    assert len(slots) == 4
    assert len({tuple(s.pages[:2]) for s in slots}) == 1, \
        "shared prefix must map to one physical page pair"
    for p in slots[0].pages[:2]:
        assert pool.refcount(p) == 5            # 4 slots + the cache
    # each 9-token prompt worst-cases 3 pages; with sharing the four
    # sequences added ONE private page each instead of three
    assert (pool.capacity - pool.n_free) - base_used == 4

    done = {r.request_id: r for r in _drain(eng)}
    stats = eng.scheduler.stats
    assert stats["prefix_hits"] >= 4
    assert stats["prefix_tokens_shared"] >= 4 * len(common)
    for rid, r in zip(rids, reqs):
        assert done[rid].token_ids == _batch1(bundle, params,
                                              r.prompt_ids, 8)
    assert pool.n_free + eng.scheduler.cache_pages_held() == pool.capacity


def test_cow_fork_on_mid_page_divergence(llama):
    """A prompt that diverges INSIDE a registered page (chunked mode
    unlocks mid-page reuse) forks that page copy-on-write: the fork stat
    fires, the shared source page keeps serving its original content, and
    both outputs stay token-identical to batch-1."""
    bundle, params = llama
    common8 = [9, 8, 7, 6, 5, 4, 3, 2]
    eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=32,
                      prefill_chunk=4)
    resA = generate_many(eng, [Request(prompt_ids=common8 + [1],
                                       max_new_tokens=3)])
    promptB = common8[:6] + [99]               # diverges in page 2
    resB = generate_many(eng, [Request(prompt_ids=promptB,
                                       max_new_tokens=5)])
    stats = eng.scheduler.stats
    assert stats["cow_forks"] == 1
    assert stats["prefix_tokens_shared"] >= 6  # 4 aligned + 2 into page 2
    assert resA[0].token_ids == _batch1(bundle, params, common8 + [1], 3)
    assert resB[0].token_ids == _batch1(bundle, params, promptB, 5)
    # the registered original still matches after the fork wrote nothing
    # into its page: a third request re-using the FULL original prefix
    resC = generate_many(eng, [Request(prompt_ids=common8 + [1],
                                       max_new_tokens=3)])
    assert resC[0].token_ids == resA[0].token_ids


def test_admission_eviction_cannot_stale_matched_prefix():
    """Regression pin: try_admit takes its share references on matched
    prefix pages BEFORE allocation pressure runs — cache eviction during
    the same admission must never hand a matched page back out as the
    slot's own private page (double-use) or crash sharing a dead node.
    Driven at the scheduler level with a pool squeezed to exactly the
    triggering state: cache-only refs + zero free pages."""
    from distributed_training_guide_tpu.serve import PagePool, Scheduler

    pool = PagePool(n_pages=4, page_size=4)          # 3 usable
    sched = Scheduler(n_slots=2, pool=pool, max_len=16,
                      max_pages_per_slot=4, prefix_cache=True)
    cached = pool.alloc(2)
    sched.cache.register(list(range(1, 9)), cached)  # 2 full pages
    pool.free(cached)                                # cache-only refs now
    [dummy] = pool.alloc(1)                          # free list: empty
    assert pool.n_free == 0

    sched.submit(Request(prompt_ids=list(range(1, 10)), max_new_tokens=2))
    adms = sched.try_admit()
    # matched pages' nodes are the only evictable thing; with the refs
    # taken first the eviction cannot free them, so the head must BLOCK
    # cleanly (not double-issue a matched page)
    assert adms == []
    assert sched.stats["admission_blocked"] == 1
    for slot in sched.slots:
        assert slot is None
    # releasing the unrelated page unblocks; the slot's pages are distinct
    pool.free([dummy])
    adms = sched.try_admit()
    assert len(adms) == 1
    pages = sched.slots[adms[0].slot_idx].pages
    assert len(set(pages)) == len(pages) == 3


class _Span:
    """``utils.trace.span`` for a test: name and statistics, when it closes."""

    def __init__(self, into, name, args):
        self.into, self.name, self.args = into, name, dict(args)

    def __enter__(self):
        return self

    def set_metadata(self, **args):
        self.args.update(args)

    def __exit__(self, *exc):
        self.into.append((self.name, self.args))
        return False


@pytest.mark.parametrize("blocked_by", ["pages", "slots"])
def test_an_admission_attempt_says_whether_it_admitted_and_what_blocked_it(
        monkeypatch, blocked_by):
    """``serve.admit`` is one span an ATTEMPT: a head that waits three
    steps leaves three spans with ``admitted`` 0 and ``blocked_by`` (for
    ``pages`` with what it needs, what is free and the headroom kept), then
    one with ``admitted`` 1 whose ``queue_ms`` is the wait it paid: a reader
    of queue wait counts each admitted request once."""
    from distributed_training_guide_tpu.serve import PagePool, Scheduler
    from distributed_training_guide_tpu.serve import scheduler as sched_mod
    from distributed_training_guide_tpu.utils.trace import ADMIT_BLOCKS

    assert blocked_by in ADMIT_BLOCKS
    spans, now = [], [0.0]
    monkeypatch.setattr(sched_mod, "span",
                        lambda name, **args: _Span(spans, name, args))
    pool = PagePool(n_pages=6, page_size=4)          # 5 usable
    sched = Scheduler(n_slots=2 if blocked_by == "pages" else 1, pool=pool,
                      max_len=16, max_pages_per_slot=4, prefix_cache=False,
                      clock=lambda: now[0])
    dummy = pool.alloc(3)                            # 2 left
    rid_a = sched.submit(Request(prompt_ids=list(range(1, 9)),
                                 max_new_tokens=1))
    [adm] = sched.try_admit()                        # takes both
    rid_b = sched.submit(Request(prompt_ids=list(range(20, 25)),
                                 max_new_tokens=2))
    for _ in range(3):                               # the head waits
        now[0] += 0.010
        assert sched.try_admit() == []
    if blocked_by == "pages":
        pool.free(dummy)
    else:                                            # the first reply ends
        sched.commit_tokens(adm.slot_idx, 8)
        assert sched.record_token(adm.slot_idx, 7, from_decode=False)
    now[0] += 0.010
    assert len(sched.try_admit()) == 1
    admits = [args for name, args in spans if name == "serve.admit"]
    assert [(a["request_id"], a["admitted"]) for a in admits] == [
        (rid_a, 1), (rid_b, 0), (rid_b, 0), (rid_b, 0), (rid_b, 1)]
    refused = [a for a in admits if not a["admitted"]]
    assert {a["blocked_by"] for a in refused} == {blocked_by}
    if blocked_by == "pages":
        assert all((a["need"], a["free"], a["headroom"]) == (2, 0, 0)
                   for a in refused)
    else:
        assert all("need" not in a for a in refused)
    # `admission_blocked` counts the pool's refusals, as it did
    assert sched.stats["admission_blocked"] == len(refused) * (
        blocked_by == "pages")
    assert all("blocked_by" not in a for a in admits if a["admitted"])
    # the wait, once a request: the spans with `admitted` 1
    waits = [a["queue_ms"] for a in admits if a["admitted"]]
    assert waits == [0.0, 40.0]
    assert len(waits) == sched.stats["admitted"] == 2
    assert [a["queue_ms"] for a in refused] == [10.0, 20.0, 30.0]


# ---- preemption-by-recompute ------------------------------------------------

def test_preemption_recompute_token_identity(llama):
    """Chaos-style pressure: a pool far below the worst case forces
    preemptions (visible in stats); every request — greedy AND sampled —
    still returns tokens identical to the batch-1 engine, and the pool
    balances to zero leaked references."""
    bundle, params = llama
    eng = ServeEngine(bundle, params, n_slots=4, page_size=4, max_len=16,
                      n_pages=7)
    reqs = [Request(prompt_ids=[3 + i, 17, 42][:1 + i % 3],
                    max_new_tokens=6 + (i % 5),
                    temperature=0.8 if i % 2 else 0.0, seed=i)
            for i in range(8)]
    res = generate_many(eng, reqs, max_iterations=3000)
    assert eng.scheduler.stats["preempted"] > 0
    ref_eng = _ref_engine(bundle, params, page_size=4, max_len=16)
    for got, req in zip(res, reqs):
        ref = generate_many(ref_eng, [_fresh(req)])[0]
        assert got.token_ids == ref.token_ids, \
            f"request seed={req.seed} diverged across preemption"
    pool = eng.scheduler.pool
    assert pool.n_free + eng.scheduler.cache_pages_held() == pool.capacity


def _cache_page_refs(sched) -> dict:
    """page -> number of prefix-cache references (one per node)."""
    refs: dict = {}
    if sched.cache is None:
        return refs
    stack = [sched.cache.root]
    while stack:
        node = stack.pop()
        for child in node.children.values():
            refs[child.page] = refs.get(child.page, 0) + 1
            stack.append(child)
    return refs


@pytest.mark.parametrize(
    "kv_dtype,weight_dtype",
    [(None, None),
     pytest.param("int8", None, marks=pytest.mark.kvquant),
     pytest.param(None, "int8", marks=pytest.mark.wquant)],
    ids=["fp32", "kv-int8", "w-int8"])
def test_scheduler_random_trace_invariants(llama, kv_dtype, weight_dtype):
    """Property-style trace over refcounted CoW pages: random
    submit/step events on a tight pool with chunked prefill, asserting
    after EVERY iteration that (a) page refcounts equal the number of
    holders (slots + cache nodes), (b) the trash page never enters a live
    table, (c) free + held pages balance to capacity, and (d) every
    completed request is token-identical to its batch-1 run. Re-run with
    the int8-quantized pool (the kvquant satellite): the allocator never
    sees dtypes, but the DEVICE side does — preempt/replay/CoW/commit all
    rewrite quantized bytes + scales, and the batch-1 oracle (itself
    int8) pins that those rewrites are bitwise. The THIRD run is the
    wquant satellite — int8 WEIGHTS over an fp32 pool: every program
    (prefill, decode, replay) reads the same quantized params, so the
    invariants and the batch-1 oracle must hold unchanged."""
    bundle, params = llama
    rng = np.random.default_rng(42)
    eng = ServeEngine(bundle, params, n_slots=3, page_size=4, max_len=16,
                      n_pages=7, prefill_chunk=4, kv_dtype=kv_dtype,
                      weight_dtype=weight_dtype)
    sched, pool = eng.scheduler, eng.scheduler.pool
    done, submitted = [], []
    for it in range(400):
        if rng.random() < 0.3 and len(submitted) < 20:
            n_prompt = int(rng.integers(1, 10))
            req = Request(
                prompt_ids=[int(rng.integers(3, 500))
                            for _ in range(n_prompt)],
                max_new_tokens=int(rng.integers(4, 17 - n_prompt)),
                temperature=float(rng.choice([0.0, 0.9])),
                seed=len(submitted))
            submitted.append((eng.submit(req), req))
        done.extend(eng.step())

        held: dict = {}
        for slot in sched.slots:
            if slot is None:
                continue
            assert 0 not in slot.pages, "trash page in a live table"
            assert len(set(slot.pages)) == len(slot.pages)
            assert slot.cache_len <= len(slot.pages) * eng.page_size
            for p in slot.pages:
                held[p] = held.get(p, 0) + 1
        for p, n in _cache_page_refs(sched).items():
            held[p] = held.get(p, 0) + n
        for p, n in held.items():
            assert pool.refcount(p) == n, \
                f"page {p}: {n} holders but refcount {pool.refcount(p)}"
            assert p not in pool._free_set
        assert pool.n_free + len(held) == pool.capacity
        if len(done) == len(submitted) and not eng.has_work and it > 100:
            break
    done.extend(_drain(eng))
    assert len(done) == len(submitted)
    assert sched.stats["preempted"] > 0        # the trace hit real pressure
    by_id = {r.request_id: r for r in done}
    # the int8 oracle must share the CHUNK SIZE: a chunk attends over
    # already-quantized history, so two chunkings of one prompt read
    # different roundings of it — under fp32 they agree to ~1e-7 (never
    # flips this trace), under int8 that difference is a 1-LSB cache
    # rounding that can. Token identity is program-relative, and the
    # scheduling-invariance claim is engine-config-relative — so the
    # reference runs the same chunk program (see serve/kv_pages.py
    # docstring).
    ref_eng = _ref_engine(bundle, params, page_size=4, max_len=16,
                          kv_dtype=kv_dtype, weight_dtype=weight_dtype,
                          prefill_chunk=4 if kv_dtype == "int8" else None)
    for rid, req in submitted:
        ref = generate_many(ref_eng, [_fresh(req)])[0]
        assert by_id[rid].token_ids == ref.token_ids


# ---- property traces over the grown surface (PR 9) -------------------------
# The scheduler invariant — refuse or cleanly preempt/evict, never corrupt
# — must survive every extension: streaming taps, deadlines, priorities,
# the sharded pool, and the disaggregated handoff. Random traces assert
# after EVERY iteration that (a) each page's refcount equals its holder
# count, (b) free + held + cache-only pages balance to capacity, (c) the
# trash page never enters a live table, and at the end that every
# completion is token-identical to batch-1 (deadline evictions: a strict
# prefix).


def _pool_invariants(pool, holder_maps):
    """holder_maps: iterables of {page: n_refs}. Assert refcount==holders
    and the capacity identity."""
    held: dict = {}
    for m in holder_maps:
        for p, n in m.items():
            held[p] = held.get(p, 0) + n
    for p, n in held.items():
        assert pool.refcount(p) == n, \
            f"page {p}: {n} holders but refcount {pool.refcount(p)}"
        assert p not in pool._free_set
    assert pool.n_free + len(held) == pool.capacity


def _slot_holders(sched, page_size):
    held: dict = {}
    for slot in sched.slots:
        if slot is None:
            continue
        assert 0 not in slot.pages, "trash page in a live table"
        assert len(set(slot.pages)) == len(slot.pages)
        assert slot.cache_len <= len(slot.pages) * page_size
        for p in slot.pages:
            held[p] = held.get(p, 0) + 1
    return held


def _check_completions(bundle, params, done, submitted, *, max_len):
    """Every finished request equals batch-1; deadline evictions must be
    a strict prefix of the batch-1 generation (clean, never garbage).
    The reference runs with the deadline STRIPPED — it is the
    deadline-free baseline, and a cold-compile reference engine could
    otherwise itself expire a 'racing' deadline and corrupt the oracle."""
    import dataclasses

    ref_eng = _ref_engine(bundle, params, page_size=4, max_len=max_len)
    by_id = {r.request_id: r for r in done}
    for rid, req in submitted:
        res = by_id[rid]
        baseline = dataclasses.replace(_fresh(req), deadline_s=None)
        ref = generate_many(ref_eng, [baseline])[0]
        if res.finish_reason == "deadline":
            n = len(res.generated_ids)
            assert res.generated_ids == ref.generated_ids[:n], \
                f"seed={req.seed}: deadline eviction returned garbage"
        else:
            assert res.token_ids == ref.token_ids, \
                f"seed={req.seed} diverged"


def _random_request(rng, n_submitted):
    n_prompt = int(rng.integers(1, 10))
    dl = rng.random()
    return Request(
        prompt_ids=[int(rng.integers(3, 500)) for _ in range(n_prompt)],
        max_new_tokens=int(rng.integers(4, 17 - n_prompt)),
        temperature=float(rng.choice([0.0, 0.9])),
        priority=int(rng.integers(0, 3)),
        # a third guaranteed-expired, a third racing, a third unbounded
        deadline_s=(1e-6 if dl < 0.33 else
                    float(rng.uniform(0.01, 0.1)) if dl < 0.66 else None),
        seed=n_submitted)


@pytest.mark.stream
def test_random_trace_stream_deadline_priority_sharded(llama,
                                                       eight_devices):
    """The grown monolith under pressure AND the sharded pool: random
    submits with priorities + deadlines, the streaming tap read every
    iteration (its prefixes must match the final tokens), pool
    invariants after every step, completions vs batch-1."""
    from distributed_training_guide_tpu.parallel import make_mesh, make_plan

    bundle, params = llama
    plan = make_plan("tp", make_mesh(tp=2, devices=eight_devices[:2]))
    rng = np.random.default_rng(7)
    eng = ServeEngine(bundle, params, n_slots=3, page_size=4, max_len=16,
                      n_pages=8, prefill_chunk=4, plan=plan, shard_kv=True)
    sched, pool = eng.scheduler, eng.scheduler.pool
    done, submitted, streamed = [], [], {}
    for it in range(250):
        if rng.random() < 0.3 and len(submitted) < 14:
            req = _random_request(rng, len(submitted))
            submitted.append((eng.submit(req), req))
        done.extend(eng.step())
        for rid, toks in eng.partial_tokens().items():
            prev = streamed.get(rid, [])
            assert toks[:len(prev)] == prev, "stream rewrote history"
            streamed[rid] = toks
        _pool_invariants(pool, [_slot_holders(sched, eng.page_size),
                                _cache_page_refs(sched)])
        if len(done) == len(submitted) and not eng.has_work and it > 80:
            break
    done.extend(_drain(eng))
    assert len(done) == len(submitted)
    assert sched.stats["deadline_expired"] > 0
    _check_completions(bundle, params, done, submitted, max_len=16)
    # streamed prefixes of completed requests match their final tokens
    by_id = {r.request_id: r for r in done}
    for rid, toks in streamed.items():
        assert by_id[rid].generated_ids[:len(toks)] == toks


@pytest.mark.disagg
def test_random_trace_disagg_handoff(llama):
    """The disaggregated pair under pressure: the same trace with the
    handoff in the holder accounting — a page in transit (released by
    the prefill scheduler, not yet adopted) is still exactly one
    reference. Preempt-requeue-replay must keep token identity."""
    bundle, params = llama
    rng = np.random.default_rng(11)
    from distributed_training_guide_tpu.serve.disagg import DisaggEngine

    eng = DisaggEngine(bundle, params, n_slots=3, n_prefill_slots=2,
                       page_size=4, max_len=16, n_pages=9,
                       prefill_chunk=4)
    done, submitted = [], []
    for it in range(400):
        if rng.random() < 0.3 and len(submitted) < 16:
            req = _random_request(rng, len(submitted))
            submitted.append((eng.submit(req), req))
        done.extend(eng.step())
        transit: dict = {}
        for h in eng.handoff.pending:
            assert 0 not in h.pages
            for p in h.pages:
                transit[p] = transit.get(p, 0) + 1
        _pool_invariants(eng.pool, [
            _slot_holders(eng.prefill.sched, eng.page_size),
            _slot_holders(eng.decode.sched, eng.page_size),
            transit, _cache_page_refs(eng.prefill.sched)])
        if len(done) == len(submitted) and not eng.has_work and it > 100:
            break
    done.extend(_drain(eng))
    assert len(done) == len(submitted)
    stats = eng.stats()
    assert stats["deadline_expired"] > 0
    assert stats["handoff_transfers"] > 0
    assert stats["handoff_bytes_copied"] == 0
    _check_completions(bundle, params, done, submitted, max_len=16)


# ---- the resident decode arrays ----------------------------------------------

# lanes a decode program carries forward on the device itself: they agree
# with the host wherever the host is not a block behind (always at K=1)
_ROLLING = ("tokens", "lengths", "actives", "budgets")
# the leading operands of each program after (params, pools), by kind
_OPERANDS = {
    "plain": ("tokens", "lengths", "tables", "seeds", "temps", "top_ks",
              "top_ps", "actives"),
    "spec": ("ids", "lengths", "tables", "seeds", "temps", "top_ks",
             "top_ps", "actives"),
    "horizon": ("tokens", "lengths", "tables", "seeds", "temps", "top_ks",
                "top_ps", "actives", "budgets", "eos_ids"),
}
_PATHS = ["monolith", "disagg", "window", "spec", "horizon4"]


def _path_engine(path, llama):
    """An engine of each decode path over a pool its requests outgrow."""
    from distributed_training_guide_tpu.serve.disagg import DisaggEngine

    bundle, params = llama
    tight = dict(n_slots=4, page_size=4, max_len=16, n_pages=7)
    if path == "disagg":
        return DisaggEngine(bundle, params, n_slots=4, n_prefill_slots=1,
                            page_size=4, max_len=16, n_pages=10,
                            prefill_chunk=4)
    if path == "window":        # two page classes, a table row of both
        bundle = get_model("mimo-v2-debug", dtype=jnp.float32)
        params = bundle.init(bundle.config, jax.random.key(0))
        return ServeEngine(bundle, params, n_slots=3, page_size=8,
                           max_len=48, n_pages=10, prefill_chunk=16)
    if path == "spec":
        return ServeEngine(bundle, params, speculate="ngram", spec_k=3,
                           **tight)
    if path == "horizon4":
        return ServeEngine(bundle, params, decode_horizon=4, **tight)
    return ServeEngine(bundle, params, **tight)


def _path_requests(path, temperature=None):
    """Eight requests that cross pages, finish at different steps and
    together want twice the pool: greedy and sampled by turns, or all at
    one ``temperature``."""
    if path == "window":
        shape = lambda i: ([3 + i, 17, 42, 9, 8, 7, 9, 8][:3 + i % 5],
                           24 + 3 * (i % 4))
    elif path == "spec":        # repetition, so the drafter drafts
        shape = lambda i: ([9, 8, 7, 9, 8, 7][:2 + i % 4] + [3 + i],
                           7 + (i % 3))
    else:
        shape = lambda i: ([3 + i, 17, 42][:1 + i % 3], 8 + (i % 5))
    reqs = []
    for i in range(8):
        prompt, n_new = shape(i)
        t = (0.8 if i % 2 else 0.0) if temperature is None else temperature
        reqs.append(Request(prompt_ids=prompt, max_new_tokens=n_new,
                            temperature=t, seed=i))
    return reqs


def _decode_side(eng):
    """``(holder of _dev, programs, scheduler)`` of the engine's decode
    half: the engine itself, or the disaggregated pair's decode engine."""
    dec = getattr(eng, "decode", None)
    return (eng, eng.programs, eng.scheduler) if dec is None \
        else (dec, dec.programs, dec.sched)


class _Checked:
    """A decode program that first holds its operands to the invariant:
    for every active slot each resident array is what a whole rebuild from
    the scheduler would upload at this instant. One lane the host does not
    know at a chunk step's dispatch, the ``tokens`` of a slot whose first
    token is sampled and still on the device: ``first`` keeps the device's
    value by request, and ``_run_checked`` holds it to the token that was
    booked."""

    def __init__(self, fn, kind, holder, sched, seen, first):
        self.fn, self.kind, self.holder = fn, kind, holder
        self.sched, self.seen, self.first = sched, seen, first

    def __getattr__(self, name):            # _cache_size, lower, ...
        return getattr(self.fn, name)

    def __call__(self, params, pools, *operands):
        sched = self.sched
        want = sched.decode_arrays()
        active = sched.active_indices()
        assert active
        # a program enqueued over one that is not booked yet: the host is
        # that block (a horizon) or that token (the plain program, in the
        # PIPELINED order: ServeEngine.step) behind on the lanes the device
        # carries, by construction. The unbooked one is in flight from the
        # step before, or went up in front of this one under the same
        # dispatch (a plain step that ENTERS the pipeline: the second program
        # of one engine step)
        seq = getattr(self.holder, "stats_seq", None)
        entering = seq is not None and self.seen.get("step") == (
            self.kind, seq)
        self.seen["step"] = (self.kind, seq)
        behind = entering or getattr(self.holder, "_inflight",
                                     None) is not None
        n_full = sched.max_pages
        for key, got in zip(_OPERANDS[self.kind], operands):
            if key == "ids" or (behind and key in _ROLLING):
                continue
            got = np.asarray(got)
            for i in active:
                slot = sched.slots[i]
                if key == "tokens" and not slot.generated:
                    assert self.kind == "plain"
                    self.first[slot.request.request_id] = int(got[i])
                    continue
                if key != "tables":
                    assert got[i] == want[key][i], (key, i, self.kind)
                    continue
                np.testing.assert_array_equal(
                    got[i, :n_full], want[key][i, :n_full], f"slot {i}")
                # the second class: the device may go on naming a page the
                # window has passed and the host has taken back
                # (Scheduler._release_window); never the other way round
                stale = got[i, n_full:] != want[key][i, n_full:]
                assert not (stale & (want[key][i, n_full:] != 0)).any(), i
        self.seen[self.kind] = self.seen.get(self.kind, 0) + 1
        return self.fn(params, pools, *operands)


def _run_checked(eng, reqs, monkeypatch):
    """The session through ``eng`` with every dispatch checked; returns
    ``(results, dispatches by kind, serve.build reasons -> arrays sent)``."""
    from distributed_training_guide_tpu.serve import engine as engine_mod

    holder, programs, sched = _decode_side(eng)
    seen, builds, first = {}, [], {}
    check = lambda fn, kind: _Checked(fn, kind, holder, sched, seen, first)
    programs._decode_fn = check(programs._decode_fn, "plain")
    verify_for, horizon_for = programs.verify_for, programs.horizon_for
    programs.verify_for = lambda t, greedy=False: check(
        verify_for(t, greedy=greedy), "spec")
    programs.horizon_for = lambda k: check(horizon_for(k), "horizon")
    upload = engine_mod.upload_decode_arrays

    def counted(dev, kind, sched, **kw):
        out = upload(dev, kind, sched, **kw)
        if out is not dev:
            whole = dev["kind"] != kind
            builds.append((dev["reason"] if dev["kind"] is None else
                           "kind" if whole else dev["stale"] or "lookahead",
                           "whole" if whole else "tables"))
        return out
    monkeypatch.setattr(engine_mod, "upload_decode_arrays", counted)
    res = generate_many(eng, reqs, max_iterations=3000)
    seen.pop("step", None)
    # the lanes the host could not check at their dispatch: the device's
    # value IS the first token that was then booked
    for r in res:
        if r.request_id in first:
            assert first[r.request_id] == r.generated_ids[0], r.request_id
    seen["first"] = len(first)
    return res, seen, builds


@pytest.mark.parametrize("path", _PATHS)
def test_resident_decode_arrays_are_what_a_whole_rebuild_would_upload(
        llama, monkeypatch, path):
    """Before EVERY dispatch of a session that crosses pages, admits,
    finishes, preempts and replays, on every decode path: the arrays on the
    device equal the scheduler's, slot by active slot, though a slot that
    only grew a page sent up its block tables alone."""
    eng = _path_engine(path, llama)
    res, seen, builds = _run_checked(eng, _path_requests(path), monkeypatch)
    assert len(res) == 8 and all(r.finish_reason == "length" for r in res)
    sched = _decode_side(eng)[2]
    assert sched.stats["preempted"] > 0, "the session never preempted"
    assert sched.stats["finished"] == 8
    # first tokens left on the device at their dispatch: the monolith's
    # plain path alone (the others keep the older order, by construction)
    n_first = seen.pop("first")
    assert (n_first > 0) == (path in ("monolith", "window")), (path, n_first)
    want = {"spec": {"spec", "plain"}, "horizon4": {"horizon", "plain"}}
    assert set(seen) == want.get(path, {"plain"}), seen
    # the mechanism engaged: growth sent the tables alone, every event that
    # changed the decoding set the whole set, and no other pairing exists
    by_reason = {}
    for reason, sent in builds:
        by_reason.setdefault(reason, set()).add(sent)
    assert by_reason.get("grown") == {"tables"}, by_reason
    assert by_reason.get("lookahead", {"tables"}) == {"tables"}
    assert all(sent == {"whole"} for reason, sent in by_reason.items()
               if reason not in ("grown", "lookahead")), by_reason
    assert {"preempted", "left"} & set(by_reason), by_reason
    # a reservation ahead of the next write (a horizon's, or the pipelined
    # plain step's: both orders of a plain step ran) sends the tables where
    # it gave a page, and this pool has none to give most of the time
    if path in ("monolith", "window"):
        assert eng.stats()["decode_steps_pipelined"] > 0
        assert eng.stats()["decode_steps_pipelined"] < seen["plain"]
    prefill = eng.prefill.sched if path == "disagg" else sched
    assert sched.pool.n_free + prefill.cache_pages_held() \
        == sched.pool.capacity


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
@pytest.mark.parametrize("path", _PATHS)
def test_tokens_are_those_of_an_engine_whose_every_build_is_whole(
        llama, monkeypatch, path, temperature):
    """The parent's behaviour, forced in the test: a slot that grew a page
    drops the whole resident set. The token ids are the same."""
    from distributed_training_guide_tpu.serve.engine import DecodeArrays

    reqs = _path_requests(path, temperature)
    got = generate_many(_path_engine(path, llama),
                        [_fresh(r) for r in reqs], max_iterations=3000)
    monkeypatch.setattr(DecodeArrays, "stale_tables", DecodeArrays.drop_dev)
    eng = _path_engine(path, llama)
    want = generate_many(eng, [_fresh(r) for r in reqs], max_iterations=3000)
    assert _decode_side(eng)[2].stats["preempted"] > 0
    assert [r.token_ids for r in got] == [r.token_ids for r in want]


# ---- chunked prefill --------------------------------------------------------

def test_chunked_prefill_interleaves_with_resident_decode(llama):
    """A long prompt fed in fixed-budget chunks must NOT stall a resident
    decode: the short request keeps generating while the long prompt
    streams in (~ceil(prompt/chunk) bounded iterations), and both match
    batch-1."""
    bundle, params = llama
    chunk = 8
    long_prompt = [3 + (i % 200) for i in range(60)]
    eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=128,
                      prefill_chunk=chunk)
    short = Request(prompt_ids=[5, 6], max_new_tokens=24, seed=1)
    rid_short = eng.submit(short)
    eng.step()                                 # short is decoding
    long_req = Request(prompt_ids=long_prompt, max_new_tokens=4, seed=2)
    rid_long = eng.submit(long_req)

    results = []
    iters_while_prefilling = 0
    short_tokens_during = 0
    it = 0
    while eng.has_work:
        s0 = eng.scheduler.slots[0]
        before = len(s0.generated) if s0 else None
        prefilling = any(s is not None and s.prefilling
                         for s in eng.scheduler.slots)
        results.extend(eng.step())
        if prefilling:
            iters_while_prefilling += 1
            s0 = eng.scheduler.slots[0]
            after = len(s0.generated) if s0 else before
            if before is not None and after is not None:
                short_tokens_during += after - before
        it += 1
        assert it < 500
    # the 60-token prompt needs ceil(60/8) = 8 chunk iterations (the first
    # rides the admission step, before the pre-step prefilling probe sees
    # it); the resident decode advanced through them instead of stalling
    # for one monolithic prefill
    assert iters_while_prefilling >= 7
    assert short_tokens_during >= 6

    by_id = {r.request_id: r for r in results}
    ref_eng = _ref_engine(bundle, params, page_size=4, max_len=128)
    for rid, req in ((rid_short, short), (rid_long, long_req)):
        ref = generate_many(ref_eng, [_fresh(req)])[0]
        assert by_id[rid].token_ids == ref.token_ids


@pytest.mark.parametrize("name", ["gpt2-debug", "neox-debug", "moe-debug"])
def test_chunked_prefill_across_families(name):
    """The multi-token chunk path exercises family-specific machinery
    (gpt2's learned position rows, neox's parallel residual, moe's routed
    FFN over T tokens) — a prompt cut into chunks of 3 must give the
    tokens of the engine's own size (here the whole prompt in one chunk)
    for each."""
    over = {"capacity_factor": 4.0} if name == "moe-debug" else {}
    bundle = get_model(name, dtype=jnp.float32, **over)
    params = bundle.init(bundle.config, jax.random.key(0))
    reqs = [Request(prompt_ids=[3 + i, 17, 42, 9, 11, 2, 8][:3 + i],
                    max_new_tokens=4, seed=i) for i in range(3)]
    chunked = generate_many(
        ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=16,
                    prefill_chunk=3), [_fresh(r) for r in reqs])
    whole = generate_many(
        ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=16),
        [_fresh(r) for r in reqs])
    for a, b in zip(chunked, whole):
        assert a.token_ids == b.token_ids


# ---- sharded weights --------------------------------------------------------

def test_engine_runs_on_tp_mesh(llama, eight_devices):
    """Sharded weights through the existing plans: tp=2 params, replicated
    pages — tokens must match the single-device engine exactly."""
    from distributed_training_guide_tpu.parallel import make_mesh, make_plan

    bundle, params = llama
    plan = make_plan("tp", make_mesh(tp=2, devices=eight_devices[:2]))
    reqs = [Request(prompt_ids=[3, 17, 42], max_new_tokens=5, seed=1),
            Request(prompt_ids=[5, 6], max_new_tokens=6, seed=2)]
    sharded = generate_many(
        ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=16,
                    plan=plan), reqs)
    single = generate_many(
        ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=16),
        reqs)
    for a, b in zip(sharded, single):
        assert a.token_ids == b.token_ids


# ---- HTTP endpoint ----------------------------------------------------------

def test_http_endpoint_concurrent_requests(llama):
    """Two clients hitting the endpoint concurrently co-batch in the
    engine thread; responses carry tokens + latency and match batch-1."""
    import http.client
    import json

    bundle, params = llama
    eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=16)
    server, worker = serve_http(eng, port=0)
    port = server.server_address[1]
    try:
        payloads = [{"prompt_ids": [3, 17, 42], "max_new_tokens": 5},
                    {"prompt_ids": [5, 6], "max_new_tokens": 6}]
        out = [None, None]

        def post(i):
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
            conn.request("POST", "/generate", json.dumps(payloads[i]))
            resp = conn.getresponse()
            out[i] = (resp.status, json.loads(resp.read()))
            conn.close()

        threads = [threading.Thread(target=post, args=(i,)) for i in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        for i, payload in enumerate(payloads):
            status, body = out[i]
            assert status == 200
            assert body["token_ids"] == _batch1(
                bundle, params, payload["prompt_ids"],
                payload["max_new_tokens"])
            assert body["finish_reason"] == "length"
            assert body["latency_s"] >= 0

        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("GET", "/healthz")
        health = json.loads(conn.getresponse().read())
        assert health["ok"] and health["n_slots"] == 2
        conn.request("POST", "/generate", json.dumps({"prompt_ids": []}))
        assert conn.getresponse().status == 400   # scheduler refusal -> 400
        conn.close()
    finally:
        server.shutdown()
        worker.stop()


def test_serve_cli_offline_batch(capsys):
    """python -m distributed_training_guide_tpu.serve hermetic path: one
    JSON line per request + the aggregate stats line."""
    import json

    from distributed_training_guide_tpu.serve.__main__ import main

    main(["-m", "llama-debug", "--prompt-ids", "3,17,42",
          "--prompt-ids", "5,6", "--steps", "4", "--n-slots", "2",
          "--page-size", "4", "--max-len", "16"])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    # first the device line (what the run is on, which attend resolved and
    # why), last the compile-cache use; the reports and results between
    assert lines[0]["device"]["platform"] == "cpu"
    assert lines[0]["attend"] == {"impl": "xla",
                                  "reason": "auto: backend is cpu, not tpu"}
    assert "kv_report" in lines[1]
    results = [l for l in lines if "token_ids" in l]
    assert len(results) == 2
    assert all(len(r["token_ids"]) == len(p) + 4
               for r, p in zip(results, ([3, 17, 42], [5, 6])))
    assert set(lines[-1]["compile_cache_use"]) == {"directory", "hits",
                                                   "misses"}
    stats = lines[-2]["stats"]
    assert stats["n_requests"] == 2 and stats["generated_tokens"] == 8
