"""Fused decode horizons (``decode_horizon=K``): ONE compiled program runs
K decode iterations as an in-device scan, so a steady decode pays one host
dispatch — and one [n_slots, K] readback — per K tokens.

The claims under test:
- K > 1 is TOKEN-IDENTICAL to K = 1 for every completion (sampling keys
  are fold_in(seed, absolute position), so the horizon changes when the
  host observes tokens, never which tokens exist) — greedy and sampled,
  fp32 and int8 KV, llama and moe.
- A lane that finishes mid-horizon (EOS or budget) emits a strict prefix
  and its remaining in-horizon writes land ONLY in the trash page.
- Scheduler events (preemption, deadline eviction) happen at horizon
  boundaries and replay/evict bitwise — the pool invariants hold after
  every iteration of a chaos trace at K=4.
- speculate + decode_horizon>1 is rejected loudly everywhere it could be
  configured.
- The lowered horizon program's only cache avals are pool-shaped in/out
  (fusing K steps costs zero extra pool memory).
- The dispatch-amortization gauges plumb through engine stats, kv_report,
  and the router aggregate; spec_acceptance_rate is OMITTED (not 0.0)
  when nothing was drafted.

Everything runs debug-size models, inside tier-1.
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_guide_tpu.models import get_model
from distributed_training_guide_tpu.serve import Request, ServeEngine
from distributed_training_guide_tpu.serve.api import generate_many
from distributed_training_guide_tpu.serve.disagg import DisaggEngine
from distributed_training_guide_tpu.utils import hlo as hlo_util

pytestmark = pytest.mark.multistep


@pytest.fixture(scope="module")
def llama():
    bundle = get_model("llama-debug", dtype=jnp.float32)
    return bundle, bundle.init(bundle.config, jax.random.key(0))


def _fresh(req):
    return dataclasses.replace(req, request_id=None)


def _ref_engine(bundle, params, **kw):
    return ServeEngine(bundle, params, n_slots=1, prefix_cache=False, **kw)


def _drain(eng, max_iters=3000):
    out, it = [], 0
    while eng.has_work:
        out.extend(eng.step())
        it += 1
        assert it < max_iters, "engine stalled"
    return out


# ---- token identity ---------------------------------------------------------

@pytest.mark.parametrize(
    "name,kv_dtype",
    [("llama-debug", None),
     pytest.param("llama-debug", "int8", marks=pytest.mark.kvquant),
     ("moe-debug", None),
     pytest.param("moe-debug", "int8", marks=pytest.mark.kvquant)],
    ids=["llama-fp32", "llama-kv8", "moe-fp32", "moe-kv8"])
def test_batch1_identity_grid(name, kv_dtype):
    """The construction claim, batch-1: K in {2, 5} against the K=1 run of
    the same engine config, greedy AND temperature>0. max_new_tokens=7
    makes every K hit a short FINAL horizon (budget-clamped), so the tail
    path is in the grid, not just the steady K-step."""
    over = {"capacity_factor": 4.0} if name == "moe-debug" else {}
    bundle = get_model(name, dtype=jnp.float32, **over)
    params = bundle.init(bundle.config, jax.random.key(0))
    reqs = [Request(prompt_ids=[5, 9, 13], max_new_tokens=7, seed=0),
            Request(prompt_ids=[5, 9, 13], max_new_tokens=7,
                    temperature=0.9, top_k=8, seed=1)]

    def run(k):
        eng = ServeEngine(bundle, params, n_slots=1, page_size=4,
                          max_len=16, kv_dtype=kv_dtype, decode_horizon=k)
        return [r.token_ids
                for r in generate_many(eng, [_fresh(r) for r in reqs])]

    want = run(1)
    for k in (2, 5):
        assert run(k) == want, f"{name}/kv={kv_dtype}: K={k} diverged"


def test_disagg_horizon_identity_and_gauges(llama):
    """The disaggregated decode engine under a horizon: token-identical to
    its own K=1 run, with the dispatch gauges showing the amortization."""
    bundle, params = llama
    reqs = [Request(prompt_ids=[3 + i, 17, 42], max_new_tokens=8,
                    temperature=0.8 if i % 2 else 0.0, seed=i)
            for i in range(4)]

    def run(k):
        eng = DisaggEngine(bundle, params, n_slots=2, n_prefill_slots=1,
                           page_size=4, max_len=16, decode_horizon=k)
        res = generate_many(eng, [_fresh(r) for r in reqs])
        return [r.token_ids for r in res], eng.stats()

    want, st1 = run(1)
    got, st4 = run(4)
    assert got == want
    assert st4["decode_horizon"] == 4 and st1["decode_horizon"] == 1
    assert st4["host_dispatches"] < st1["host_dispatches"]
    assert st4["tokens_per_dispatch"] > st1["tokens_per_dispatch"]
    assert st4["horizon_effective"] > 1.5
    rep = DisaggEngine(bundle, params, n_slots=2, n_prefill_slots=1,
                       page_size=4, max_len=16,
                       decode_horizon=4).kv_report()
    assert rep["decode_horizon"] == 4
    assert rep["dispatches_per_step"] == 0.25


# ---- mid-horizon finishes ---------------------------------------------------

def test_eos_mid_horizon_strict_prefix_and_trash_containment(llama):
    """EOS fires INSIDE a 5-step horizon: the result is the strict prefix
    of the eos-free greedy stream ending at the eos token, and every pool
    page the slot never owned is bitwise untouched afterwards — the dead
    lane's remaining in-horizon writes landed only in the trash page."""
    bundle, params = llama
    free = generate_many(
        ServeEngine(bundle, params, n_slots=1, page_size=4, max_len=32),
        [Request(prompt_ids=[5, 9, 13], max_new_tokens=10)])[0]
    # the eos must FIRST occur mid-stream (an earlier duplicate would
    # finish the request before the horizon even dispatches)
    idx = next(i for i in range(1, 10)
               if free.generated_ids[i] not in free.generated_ids[:i])
    eos = free.generated_ids[idx]        # dies mid-horizon-1

    eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=32,
                      decode_horizon=5)
    before_k = np.asarray(eng.pages["k"])
    before_v = np.asarray(eng.pages["v"])
    eng.submit(Request(prompt_ids=[5, 9, 13], max_new_tokens=10,
                       eos_id=eos))
    touched, done, it = set(), [], 0
    while eng.has_work:
        done.extend(eng.step())
        for slot in eng.scheduler.slots:
            if slot is not None:
                touched.update(slot.pages)
        it += 1
        assert it < 200
    [res] = done
    assert res.finish_reason == "eos"
    assert res.generated_ids == free.generated_ids[:idx + 1]
    after_k = np.asarray(eng.pages["k"])
    after_v = np.asarray(eng.pages["v"])
    for p in range(eng.scheduler.pool.n_pages):
        if p in touched or p == 0:       # page 0 IS the trash page
            continue
        assert np.array_equal(before_k[:, p], after_k[:, p]), \
            f"page {p} written past EOS outside the trash page"
        assert np.array_equal(before_v[:, p], after_v[:, p]), \
            f"page {p} written past EOS outside the trash page"


# ---- boundary events --------------------------------------------------------

def test_preemption_at_horizon_boundaries_replays_bitwise(llama):
    """A pool far below worst case under K=4: preemptions fire (at horizon
    boundaries — the only place host state is authoritative), and every
    request — greedy AND sampled — replays to tokens identical to the
    batch-1 K=1 reference, with zero leaked pages."""
    bundle, params = llama
    eng = ServeEngine(bundle, params, n_slots=4, page_size=4, max_len=16,
                      n_pages=7, decode_horizon=4)
    reqs = [Request(prompt_ids=[3 + i, 17, 42][:1 + i % 3],
                    max_new_tokens=6 + (i % 5),
                    temperature=0.8 if i % 2 else 0.0, seed=i)
            for i in range(8)]
    res = generate_many(eng, [_fresh(r) for r in reqs],
                        max_iterations=3000)
    assert eng.scheduler.stats["preempted"] > 0
    ref_eng = _ref_engine(bundle, params, page_size=4, max_len=16)
    for got, req in zip(res, reqs):
        ref = generate_many(ref_eng, [_fresh(req)])[0]
        assert got.token_ids == ref.token_ids, \
            f"seed={req.seed} diverged across horizon-boundary preemption"
    pool = eng.scheduler.pool
    assert pool.n_free + eng.scheduler.cache_pages_held() == pool.capacity


def test_deadline_eviction_at_horizon_boundary_is_strict_prefix(llama):
    """A deadline expiring mid-stream under K=4 evicts at the next horizon
    boundary: finish_reason 'deadline', tokens a strict prefix of the
    undeadlined run, and the co-resident request unaffected."""
    bundle, params = llama
    baseline = generate_many(
        _ref_engine(bundle, params, page_size=4, max_len=64),
        [Request(prompt_ids=[7, 11], max_new_tokens=60, seed=1)])[0]

    eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=64,
                      decode_horizon=4)
    keep = Request(prompt_ids=[5, 9, 13], max_new_tokens=8, seed=0)
    doomed = Request(prompt_ids=[7, 11], max_new_tokens=60,
                     deadline_s=0.05, seed=1)
    kid = eng.submit(keep)
    did = eng.submit(doomed)
    eng.step()                            # admit + first horizon
    time.sleep(0.08)                      # deadline passes mid-stream
    done = {r.request_id: r for r in _drain(eng)}
    assert done[did].finish_reason == "deadline"
    n = len(done[did].generated_ids)
    assert n < 60
    assert done[did].generated_ids == baseline.generated_ids[:n]
    ref = generate_many(_ref_engine(bundle, params, page_size=4,
                                    max_len=64), [_fresh(keep)])[0]
    assert done[kid].token_ids == ref.token_ids


def test_scheduler_chaos_trace_invariants_at_k4(llama):
    """The PR-3 property trace re-run under decode_horizon=4: random
    submit/step events on a tight pool with chunked prefill, asserting
    after EVERY iteration — including ones with a dispatched-but-unbooked
    horizon block in flight — that page refcounts equal holder counts,
    the trash page never enters a live table, free + held + cached pages
    balance to capacity, and every completion is token-identical to the
    K=1 batch-1 reference."""
    bundle, params = llama
    rng = np.random.default_rng(42)
    eng = ServeEngine(bundle, params, n_slots=3, page_size=4, max_len=16,
                      n_pages=7, prefill_chunk=4, decode_horizon=4)
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
            for p in slot.pages:
                held[p] = held.get(p, 0) + 1
        for p, n in _cache_page_refs(sched).items():
            held[p] = held.get(p, 0) + n
        for p, n in held.items():
            assert pool.refcount(p) == n, \
                f"page {p}: {n} holders but refcount {pool.refcount(p)}"
        assert pool.n_free + len(held) == pool.capacity
        if len(done) == len(submitted) and not eng.has_work and it > 100:
            break
    done.extend(_drain(eng))
    assert len(done) == len(submitted)
    assert sched.stats["preempted"] > 0        # the trace hit pressure
    by_id = {r.request_id: r for r in done}
    ref_eng = _ref_engine(bundle, params, page_size=4, max_len=16)
    for rid, req in submitted:
        ref = generate_many(ref_eng, [_fresh(req)])[0]
        assert by_id[rid].token_ids == ref.token_ids, f"seed={req.seed}"


def _cache_page_refs(sched) -> dict:
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


# ---- speculation exclusion --------------------------------------------------

def test_spec_plus_horizon_rejected_loudly(llama):
    """speculate= keeps K=1 this release: every path that could combine a
    drafter with a horizon>1 raises with an actionable message — ctor
    (both engines), set_decode_horizon under a live OR parked drafter,
    and set_speculation(True) under a horizon."""
    bundle, params = llama
    with pytest.raises(ValueError, match="decode_horizon"):
        ServeEngine(bundle, params, n_slots=1, page_size=4, max_len=16,
                    speculate="ngram", decode_horizon=2)
    with pytest.raises(ValueError, match="decode_horizon"):
        DisaggEngine(bundle, params, n_slots=2, n_prefill_slots=1,
                     page_size=4, max_len=16, speculate="ngram",
                     decode_horizon=2)
    eng = ServeEngine(bundle, params, n_slots=1, page_size=4, max_len=16,
                      speculate="ngram")
    with pytest.raises(ValueError, match="set_decode_horizon"):
        eng.set_decode_horizon(2)
    eng.set_speculation(False)            # parked, not gone
    with pytest.raises(ValueError, match="set_decode_horizon"):
        eng.set_decode_horizon(2)
    plain = ServeEngine(bundle, params, n_slots=1, page_size=4,
                        max_len=16, decode_horizon=4)
    with pytest.raises(ValueError, match="set_speculation"):
        plain.set_speculation(True)
    assert plain.set_decode_horizon(1) == 1   # and DOWN is always legal
    assert plain.set_decode_horizon(8) == 8


# ---- lowering pin -----------------------------------------------------------

def test_horizon_hlo_cache_avals_pool_shaped_only(llama):
    """The lowered K=4 horizon's cache tensors are exactly pool-shaped in
    and out — NO [K, ...pool] stacked cache anywhere (the scan's stacked
    output is only the [n_slots, K] token block), so fusing K steps costs
    zero extra pool memory."""
    bundle, params = llama
    cfg = bundle.config
    eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=16,
                      decode_horizon=4)
    arr = eng.scheduler.decode_arrays()
    lowered = eng.programs.horizon_for(4).lower(
        eng.params, eng.pages,
        jnp.asarray(arr["tokens"]), jnp.asarray(arr["lengths"]),
        jnp.asarray(arr["tables"]), jnp.asarray(arr["seeds"]),
        jnp.asarray(arr["temps"]), jnp.asarray(arr["top_ks"]),
        jnp.asarray(arr["top_ps"]), jnp.asarray(arr["actives"]),
        jnp.asarray(arr["budgets"]), jnp.asarray(arr["eos_ids"]),
        *eng.programs.lora_call_args(jnp.asarray(arr["adapters"])))
    text = lowered.as_text()
    pool_shape = (cfg.num_layers, eng.scheduler.pool.n_pages, 4,
                  cfg.num_kv_heads, cfg.head_size)
    assert hlo_util.has_aval(text, "f32", pool_shape), \
        "pool-shaped cache aval missing from the lowered horizon"
    assert not hlo_util.has_aval(text, "f32", (4,) + pool_shape), \
        "a K-stacked pool materialized in the horizon program"
    assert (hlo_util.has_aval(text, "i32", (2, 4))
            or hlo_util.has_aval(text, "s32", (2, 4))), \
        "[n_slots, K] token block missing from the lowered horizon"


# ---- gauge plumbing ---------------------------------------------------------

def test_stats_gauges_kv_report_and_spec_metric_omission(llama):
    """host_dispatches / tokens_per_dispatch / horizon_effective on engine
    stats; decode_horizon priced into kv_report; spec_acceptance_rate
    OMITTED — not 0.0 — when nothing was ever drafted."""
    bundle, params = llama
    eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=16,
                      decode_horizon=4)
    # 1 prefill token + 8 decode steps = exactly two K=4 horizons
    generate_many(eng, [Request(prompt_ids=[5, 9, 13],
                                max_new_tokens=9, seed=0)])
    st = eng.stats()
    assert st["decode_horizon"] == 4
    assert st["host_dispatches"] == 2
    assert st["horizon_effective"] == 4.0
    assert st["tokens_per_dispatch"] == 4.0
    assert "spec_acceptance_rate" not in st, \
        "acceptance must be omitted, not 0.0, when nothing was drafted"
    rep = eng.kv_report()
    assert rep["decode_horizon"] == 4
    assert rep["dispatches_per_step"] == 0.25
    assert rep["horizon_block_bytes"] == 2 * 4 * 4


def test_router_aggregates_horizon_gauges(llama):
    """The fleet level: raw host_dispatches/horizon_ksum SUM across
    replicas and the ratios re-derive from the sums (averaging the
    per-replica ratios would be wrong under uneven traffic); the fleet
    spec_acceptance_rate stays omitted when no replica drafted."""
    from distributed_training_guide_tpu.serve.router import Replica, Router
    bundle, params = llama
    engines = [ServeEngine(bundle, params, n_slots=2, page_size=4,
                           max_len=16, decode_horizon=k) for k in (2, 4)]
    for i, eng in enumerate(engines):
        generate_many(eng, [Request(prompt_ids=[5 + i, 9, 13],
                                    max_new_tokens=9, seed=i)])
    router = Router([Replica(f"r{i}", e) for i, e in enumerate(engines)])
    st = router.stats()
    want_disp = sum(e.stats()["host_dispatches"] for e in engines)
    want_ksum = sum(e.horizon_ksum for e in engines)
    assert st["host_dispatches"] == want_disp
    assert st["horizon_ksum"] == want_ksum
    assert st["horizon_effective"] == round(want_ksum / want_disp, 3)
    assert st["tokens_per_dispatch"] == round(
        sum(e.stats()["decode_tokens"] for e in engines) / want_disp, 3)
    assert "spec_acceptance_rate" not in st
