"""Speculative decoding (serve/spec.py + engine verify path).

The load-bearing property is EXACTNESS: acceptance is coupled to the
target sampler's own deterministic fold_in(seed, position) draws, so
spec-on output must be TOKEN-IDENTICAL to spec-off — greedy and
temperature > 0 alike, for every family, across preemption/replay,
deadline eviction, the tp=2 sharded pool, and the disaggregated pair.
Every test here therefore compares full token streams, never
distributions, and the rollback discipline (lengths retreat, dead k/v
overwritten in place, lookahead pages kept) is pinned by the same pool
invariants the rest of the serve suite enforces.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_guide_tpu.models import get_model
from distributed_training_guide_tpu.serve import (NgramDrafter, Request,
                                                  ServeEngine)
from distributed_training_guide_tpu.serve.api import generate_many
from distributed_training_guide_tpu.serve.spec import DraftModelDrafter
from test_serve import (_cache_page_refs, _check_completions, _drain,
                        _fresh, _pool_invariants, _random_request,
                        _ref_engine, _slot_holders)

pytestmark = [pytest.mark.serve, pytest.mark.spec]


@pytest.fixture(scope="module")
def llama():
    bundle = get_model("llama-debug", dtype=jnp.float32)
    return bundle, bundle.init(bundle.config, jax.random.key(0))


# a prompt with internal repetition: the n-gram drafter finds matches and
# the trace actually exercises acceptance, not just the empty-draft path
_REPETITIVE = [9, 8, 7, 9, 8, 7, 9, 8, 7, 9, 8, 7]


def _make_repetitive(req):
    """Swap a random request's prompt for an equal-LENGTH repetitive one
    (lengths drive the trace's budget math — only the content changes)."""
    return dataclasses.replace(req,
                               prompt_ids=_REPETITIVE[:len(req.prompt_ids)])


def _spec_reqs(n, max_new=10):
    return [Request(prompt_ids=_REPETITIVE[:3 + (i % 5)] + [3 + i],
                    max_new_tokens=max_new,
                    temperature=0.0 if i % 2 == 0 else 0.9,
                    top_k=0 if i % 3 else 8, seed=i) for i in range(n)]


# ---- the drafter interface --------------------------------------------------

def test_ngram_drafter_proposals():
    d = NgramDrafter(k=4, max_n=3, min_n=1)
    # trigram suffix [1,2,3] recurs; candidates are what followed it
    ctx = [1, 2, 3, 4, 5, 6, 1, 2, 3]
    assert d.propose(0, ctx, 4) == [4, 5, 6, 1]
    # period-1 cycle: the nearest match truncates at the context end, so
    # the drafter must walk back to an occurrence with a FULL continuation
    assert d.propose(0, [5] * 12, 4) == [5, 5, 5, 5]
    # budget clipping and the no-match case
    assert d.propose(0, ctx, 2) == [4, 5]
    assert d.propose(0, [1, 2, 3, 4], 4) == []
    assert d.propose(0, ctx, 0) == []
    with pytest.raises(ValueError, match="k must be"):
        NgramDrafter(k=0)


def test_lookahead_growth_clamps_never_preempts():
    """ensure_lookahead is opportunistic: with a co-active decode it
    leaves that slot's imminent mandatory-growth page alone (clamping
    the drafts to zero rather than draining the pool into a later
    preemption), and once the neighbor leaves, the same request grows
    freely. Nobody is ever preempted for speculation."""
    from distributed_training_guide_tpu.serve import PagePool, Scheduler

    pool = PagePool(n_pages=4, page_size=4)          # 3 usable
    sched = Scheduler(n_slots=2, pool=pool, max_len=16,
                      max_pages_per_slot=4, prefix_cache=False)
    sched.submit(Request(prompt_ids=[1, 2, 3], max_new_tokens=8))
    sched.submit(Request(prompt_ids=[4, 5, 6], max_new_tokens=1))
    for adm in sched.try_admit():
        sched.commit_tokens(adm.slot_idx, 3)
    # slot 0 wants positions 3..9 (3 pages); 1 page free, but slot 1 is
    # a co-active decode whose mandatory next-write page that free page
    # must remain available for — clamp, don't drain
    assert pool.n_free == 1
    granted = sched.ensure_lookahead(0, 6)
    assert granted == 0
    assert sched.stats["spec_lookahead_clamped"] == 1
    assert sched.stats["preempted"] == 0
    assert all(s is not None for s in sched.slots), "clamp must not evict"
    # slot 1 finishes (max_new=1): its page frees, no co-active decode
    # remains, and the same lookahead now grows for real
    assert sched.record_token(1, 42, from_decode=True) is not None
    granted = sched.ensure_lookahead(0, 6)
    assert granted == 6                  # 3 pages cover positions 0..11
    assert sched.stats["preempted"] == 0
    assert pool.n_free + sum(len(s.pages) for s in sched.slots
                             if s is not None) == pool.capacity


def test_empty_draft_iterations_take_plain_path(llama):
    """A drafter with nothing to propose must not pay the padded
    [S, k+1] verify forward: the iteration falls back to the plain
    single-token program (spec_steps counts verify iterations only),
    and output is unchanged."""
    from distributed_training_guide_tpu.serve import Drafter

    class NullDrafter(Drafter):
        k = 4

        def propose(self, slot_idx, context, budget):
            return []

    bundle, params = llama
    reqs = [Request(prompt_ids=[3, 17, 42], max_new_tokens=8, seed=s)
            for s in range(2)]
    off = generate_many(
        ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=32),
        [_fresh(r) for r in reqs])
    eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=32,
                      speculate=NullDrafter())
    on = generate_many(eng, [_fresh(r) for r in reqs])
    for a, b in zip(off, on):
        assert a.token_ids == b.token_ids
    assert eng.spec["spec_steps"] == 0, "verify ran with nothing drafted"
    assert eng.decode_steps > 0


def test_draft_flash_ineligible_geometry_refused(llama, monkeypatch):
    """attend_impl='flash' with a draft geometry the compiled kernel
    cannot take (the DRAFT model's head_size/page_size, not the
    target's) refuses at construction — not with a Mosaic-gate
    ValueError inside the first draft forward of a live iteration."""
    bundle, params = llama
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="attend_impl='flash'"):
        DraftModelDrafter(bundle, params, n_slots=2, max_len=32, k=3,
                          page_size=4, attend_impl="flash")
    # 'auto' resolves per-shape (gather for ineligible geometry) and
    # must keep constructing
    DraftModelDrafter(bundle, params, n_slots=2, max_len=32, k=3,
                      page_size=4, attend_impl="auto")


def test_drafter_slot_mismatch_refused(llama):
    """A per-slot-stateful drafter smaller than the engine's decode
    batch refuses at construction, not with an IndexError on the first
    speculative iteration."""
    bundle, params = llama
    drafter = DraftModelDrafter(bundle, params, n_slots=2, max_len=32,
                                k=3, page_size=4)
    with pytest.raises(ValueError, match="slots"):
        ServeEngine(bundle, params, n_slots=4, page_size=4, max_len=32,
                    speculate=drafter)
    with pytest.raises(ValueError, match="speculate must be"):
        ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=32,
                    speculate="beam")


# ---- exact acceptance: spec-on == spec-off ---------------------------------

@pytest.mark.parametrize("name", ["llama-debug", "gpt2-debug", "neox-debug",
                                  "moe-debug"])
def test_spec_greedy_and_sampled_identity_across_families(name):
    """The acceptance pin: spec-on output equals the spec-off engine's
    token-for-token — greedy AND temperature > 0 (the coupled acceptance
    emits the target sampler's own draws) — for all four families."""
    over = {"capacity_factor": 4.0} if name == "moe-debug" else {}
    # The drafter is handed prompt + generated, so the suffix it matches
    # always ENDS in a token the model just emitted: no prompt, however
    # repetitive, makes it fire unless that token occurred before. With
    # random weights (and a compiler free to round differently) the only
    # prompt that guarantees it is one holding EVERY token id, so the
    # vocabulary is cut to what a prompt can hold: whatever the model
    # emits then has an earlier occurrence with a continuation after it.
    vocab = 40
    bundle = get_model(name, dtype=jnp.float32, vocab_size=vocab, **over)
    params = bundle.init(bundle.config, jax.random.key(0))
    reqs = _spec_reqs(5) + [
        Request(prompt_ids=list(range(vocab)), max_new_tokens=10,
                temperature=t, seed=11) for t in (0.0, 0.9)]
    off = generate_many(
        ServeEngine(bundle, params, n_slots=3, page_size=4, max_len=64),
        [_fresh(r) for r in reqs])
    eng = ServeEngine(bundle, params, n_slots=3, page_size=4, max_len=64,
                      speculate="ngram", spec_k=3)
    on = generate_many(eng, [_fresh(r) for r in reqs])
    for a, b in zip(off, on):
        assert a.token_ids == b.token_ids, f"{name}: spec-on diverged"
    st = eng.stats()
    assert st["spec_tokens_drafted"] > 0, "the trace never speculated"
    assert st["spec_tokens_accepted"] >= 0
    pool = eng.scheduler.pool
    assert pool.n_free + eng.scheduler.cache_pages_held() == pool.capacity


def test_spec_draft_model_identity_and_acceptance(llama):
    """Self-draft (draft model == target): greedy drafts equal the
    target's greedy draws, so acceptance is ~1 and the verify emits
    full k+1 runs; output still equals spec-off exactly. Slot reuse
    across requests exercises the drafter's sync-by-context reseat."""
    bundle, params = llama
    reqs = [Request(prompt_ids=[3 + i, 17, 42], max_new_tokens=12, seed=i)
            for i in range(6)]
    off = generate_many(
        ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=32),
        [_fresh(r) for r in reqs])
    drafter = DraftModelDrafter(bundle, params, n_slots=2, max_len=32,
                                k=4, page_size=4)
    eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=32,
                      speculate=drafter)
    on = generate_many(eng, [_fresh(r) for r in reqs])
    for a, b in zip(off, on):
        assert a.token_ids == b.token_ids
    st = eng.stats()
    assert st["spec_acceptance_rate"] > 0.9     # greedy self-draft
    assert st["decode_tokens_per_step"] > 2.0   # real amortization
    assert st["resyncs"] > 0                    # slots were re-seated
    # mixed temperatures still exact (drafts are greedy guesses at a
    # stochastic stream — low acceptance, same tokens)
    mixed = _spec_reqs(4)
    off2 = generate_many(
        ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=32),
        [_fresh(r) for r in mixed])
    on2 = generate_many(eng, [_fresh(r) for r in mixed])
    for a, b in zip(off2, on2):
        assert a.token_ids == b.token_ids


def test_spec_preemption_recompute_identity(llama):
    """Pool pressure under speculation: lookahead growth competes with
    mandatory growth, preemptions fire, and the post-preemption REPLAY
    falls back to the plain decode program (bitwise cache recompute)
    while other slots keep speculating between replays. Every request
    must still match the spec-off batch-1 reference."""
    bundle, params = llama
    eng = ServeEngine(bundle, params, n_slots=4, page_size=4, max_len=16,
                      n_pages=7, speculate="ngram", spec_k=3)
    reqs = [Request(prompt_ids=_REPETITIVE[:1 + i % 3],
                    max_new_tokens=6 + (i % 5),
                    temperature=0.8 if i % 2 else 0.0, seed=i)
            for i in range(8)]
    res = generate_many(eng, reqs, max_iterations=3000)
    assert eng.scheduler.stats["preempted"] > 0
    ref_eng = _ref_engine(bundle, params, page_size=4, max_len=16)
    for got, req in zip(res, reqs):
        ref = generate_many(ref_eng, [_fresh(req)])[0]
        assert got.token_ids == ref.token_ids, \
            f"seed={req.seed} diverged across preemption under spec"
    pool = eng.scheduler.pool
    assert pool.n_free + eng.scheduler.cache_pages_held() == pool.capacity


@pytest.mark.paged_multitok
def test_spec_flash_family_identity_and_no_downgrade(llama):
    """The block_q=T acceptance pin: (a) 'auto' under speculation is no
    longer downgraded at construction — the engine keeps one attend
    family because the kernel covers decode AND verify, not because it
    retreated to gather; (b) on the FLASH family end-to-end (flash
    decode + flash verify + flash empty-draft fallback), spec-on is
    token-identical to spec-off — greedy and temperature > 0 — the
    identity that used to require the downgrade now holds by
    construction."""
    bundle, params = llama
    eng_auto = ServeEngine(bundle, params, n_slots=2, page_size=4,
                           max_len=32, speculate="ngram", spec_k=3)
    assert eng_auto.attend_impl == "auto", \
        "the construction-time downgrade block is back"
    assert eng_auto.programs.attend_impl == "auto"

    reqs = _spec_reqs(4)                      # greedy + temp>0 mix
    off = generate_many(
        ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=32,
                    attend_impl="flash"),
        [_fresh(r) for r in reqs])
    eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=32,
                      attend_impl="flash", speculate="ngram", spec_k=3)
    on = generate_many(eng, [_fresh(r) for r in reqs])
    for a, b in zip(off, on):
        assert a.token_ids == b.token_ids, "flash-family spec-on diverged"
    assert eng.spec["tokens_drafted"] > 0, "the trace never speculated"


# ---- boundary events mid-speculation (satellite) ---------------------------

def test_deadline_priority_eviction_mid_speculation(llama):
    """A slot evicted by deadline (or displaced by priority) while the
    drafter holds speculative state for it: the eviction is a clean
    iteration-boundary event — the returned tokens are a STRICT PREFIX
    of the batch-1 reference (never a rejected draft), and the pool
    balances after every iteration."""
    bundle, params = llama
    rng = np.random.default_rng(23)
    eng = ServeEngine(bundle, params, n_slots=3, page_size=4, max_len=16,
                      n_pages=8, speculate="ngram", spec_k=3)
    sched, pool = eng.scheduler, eng.scheduler.pool
    done, submitted = [], []
    for it in range(300):
        if rng.random() < 0.35 and len(submitted) < 14:
            req = _make_repetitive(_random_request(rng, len(submitted)))
            submitted.append((eng.submit(req), req))
        done.extend(eng.step())
        _pool_invariants(pool, [_slot_holders(sched, eng.page_size),
                                _cache_page_refs(sched)])
        if len(done) == len(submitted) and not eng.has_work and it > 80:
            break
    done.extend(_drain(eng))
    assert len(done) == len(submitted)
    assert sched.stats["deadline_expired"] > 0
    assert eng.spec["tokens_drafted"] > 0, "the trace never speculated"
    _check_completions(bundle, params, done, submitted, max_len=16)


def test_spec_random_trace_disagg(llama):
    """The disaggregated pair with decode-side speculation under the
    same random trace as test_serve's: speculate/rollback events join
    admit/handoff/evict/preempt, and every pool invariant (refcount ==
    holders incl. in-transit handoffs, capacity identity, no trash page
    live) holds after every iteration."""
    from distributed_training_guide_tpu.serve.disagg import DisaggEngine

    bundle, params = llama
    rng = np.random.default_rng(31)
    eng = DisaggEngine(bundle, params, n_slots=3, n_prefill_slots=2,
                       page_size=4, max_len=16, n_pages=9,
                       prefill_chunk=4, speculate="ngram", spec_k=3)
    done, submitted = [], []
    for it in range(400):
        if rng.random() < 0.3 and len(submitted) < 16:
            req = _make_repetitive(_random_request(rng, len(submitted)))
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
    assert eng.decode.spec["tokens_drafted"] > 0
    assert eng.stats()["handoff_bytes_copied"] == 0
    _check_completions(bundle, params, done, submitted, max_len=16)


def test_spec_sharded_tp2_trace(llama, eight_devices):
    """Speculation over the tp=2 SHARDED pool: the verify program's
    multi-token attend runs per chip inside the manual region exactly as
    the chunk program does. Short random trace — invariants every
    iteration, completions vs batch-1."""
    from distributed_training_guide_tpu.parallel import make_mesh, make_plan

    bundle, params = llama
    plan = make_plan("tp", make_mesh(tp=2, devices=eight_devices[:2]))
    rng = np.random.default_rng(17)
    eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=16,
                      n_pages=8, plan=plan, shard_kv=True,
                      speculate="ngram", spec_k=3)
    sched, pool = eng.scheduler, eng.scheduler.pool
    done, submitted = [], []
    for it in range(200):
        if rng.random() < 0.35 and len(submitted) < 8:
            req = dataclasses.replace(
                _make_repetitive(_random_request(rng, len(submitted))),
                deadline_s=None)
            submitted.append((eng.submit(req), req))
        done.extend(eng.step())
        _pool_invariants(pool, [_slot_holders(sched, eng.page_size),
                                _cache_page_refs(sched)])
        if len(done) == len(submitted) and not eng.has_work and it > 60:
            break
    done.extend(_drain(eng))
    assert len(done) == len(submitted)
    assert eng.spec["tokens_drafted"] > 0
    _check_completions(bundle, params, done, submitted, max_len=16)


@pytest.mark.slow
def test_spec_sharded_tp2_grid(llama, eight_devices):
    """The >=2-device spec grid (slow): tp=2 sharded pool x {ngram,
    self-draft} x mixed temperatures, full identity vs the unsharded
    spec-off engine."""
    from distributed_training_guide_tpu.parallel import make_mesh, make_plan

    bundle, params = llama
    plan = make_plan("tp", make_mesh(tp=2, devices=eight_devices[:2]))
    reqs = _spec_reqs(6, max_new=12)
    off = generate_many(
        ServeEngine(bundle, params, n_slots=3, page_size=4, max_len=32),
        [_fresh(r) for r in reqs])
    for speculate in ("ngram",
                      DraftModelDrafter(bundle, params, n_slots=3,
                                        max_len=32, k=3, page_size=4)):
        eng = ServeEngine(bundle, params, n_slots=3, page_size=4,
                          max_len=32, plan=plan, shard_kv=True,
                          speculate=speculate, spec_k=3)
        on = generate_many(eng, [_fresh(r) for r in reqs])
        for a, b in zip(off, on):
            assert a.token_ids == b.token_ids
        assert eng.spec["spec_steps"] > 0


# ---- stats / streaming plumbing (satellites) -------------------------------

def test_spec_and_cache_stats_surface(llama):
    """stats() (and therefore /healthz, which serves it verbatim) must
    expose the speculation counters AND the prefix-cache pressure pair —
    eviction count + cached-page BYTES (satellite: a thrashing cache
    previously looked healthy because only the hit rate was visible)."""
    from distributed_training_guide_tpu.serve import kv_page_bytes
    from distributed_training_guide_tpu.serve.api import (_EngineWorker,
                                                          throughput_stats)
    import time as _t

    bundle, params = llama
    eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=16,
                      speculate="ngram", spec_k=3)
    t0 = _t.perf_counter()
    res = generate_many(eng, [Request(prompt_ids=_REPETITIVE[:8],
                                      max_new_tokens=6, seed=s)
                              for s in range(3)])
    st = eng.stats()
    for key in ("spec_steps", "spec_tokens_drafted", "spec_tokens_accepted",
                "spec_tokens_rejected",
                "decode_tokens_per_step", "cache_evicted_pages",
                "pages_cached_bytes", "spec_lookahead_clamped"):
        assert key in st, f"stats() lost {key}"
    # the rate exists exactly when something was drafted (a 0/0 rate is
    # omitted, not reported as 0.0)
    assert ("spec_acceptance_rate" in st) == (st["spec_tokens_drafted"] > 0)
    assert st["pages_cached_bytes"] == st["pages_cached"] * kv_page_bytes(
        bundle.config, page_size=4)
    assert st["pages_cached"] > 0 and st["pages_cached_bytes"] > 0
    # the worker snapshot (what /healthz serves) carries the same keys
    worker = _EngineWorker(eng)
    assert ("spec_acceptance_rate" in worker.stats()) == \
        ("spec_acceptance_rate" in st)
    assert "pages_cached_bytes" in worker.stats()
    # and the batch-level aggregate forwards the speculation block
    agg = throughput_stats(res, _t.perf_counter() - t0, eng)
    assert agg["spec_tokens_drafted"] == st["spec_tokens_drafted"]
    assert agg["decode_tokens_per_step"] == st["decode_tokens_per_step"]


def test_spec_accepted_run_flushes_per_iteration(llama):
    """Streaming under speculation: an iteration that accepts a run of
    drafts appends the WHOLE run to partial_tokens() at that boundary
    (grow-only lists — the dedup-by-count consumer sees a multi-token
    delta, never a rewrite)."""
    bundle, params = llama
    drafter = DraftModelDrafter(bundle, params, n_slots=1, max_len=32,
                                k=4, page_size=4)
    eng = ServeEngine(bundle, params, n_slots=1, page_size=4, max_len=32,
                      speculate=drafter)
    rid = eng.submit(Request(prompt_ids=[3, 17, 42], max_new_tokens=12))
    prev, deltas = [], []
    while eng.has_work:
        eng.step()
        toks = eng.partial_tokens().get(rid, prev)
        assert toks[:len(prev)] == prev, "stream rewrote history"
        deltas.append(len(toks) - len(prev))
        prev = toks
    assert max(deltas) > 1, "no multi-token flush despite acceptance"
