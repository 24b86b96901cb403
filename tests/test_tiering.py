"""Tiered KV (serve/tiering.py): host-RAM spill for prefix pages,
preempted sequences and idle adapters, plus the router's fleet-wide
prefix directory.

The contract under test is the pool discipline extended one tier down:
- preemption SPILLS the victim's live pages and resume is
  scatter-and-seat — token-bitwise vs the never-preempted batch-1
  reference (greedy AND temp>0, fp32 AND int8 pools: the int8 payload
  and its fp32 scale rows ride together), with NO re-prefill (pinned by
  prefill-call count);
- the extended capacity audit holds after EVERY iteration: the HBM
  identity (free + distinct held pages == capacity, refcount == holder
  count) is UNCHANGED by tiering — a spilled page freed its HBM slot at
  spill time — and the tier audits its own ledger (bytes_used ==
  sum(record bytes) <= budget, spilled_pages == sum(record pages));
- a fleet-directory hit on a cold replica seats the prefix with zero
  prefill forward passes over the pulled pages; any torn/stalled pull
  frame degrades to an ordinary cache miss (refuse-never-corrupt);
- adapter-namespaced prefix keys never cross tenants through the
  directory; adapter spill/restore round-trips bitwise;
- a generation swap carries the host tier when the payload-seat path is
  legal and drops it when replay is forced.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_guide_tpu.models import get_model
from distributed_training_guide_tpu.serve import Request, ServeEngine
from distributed_training_guide_tpu.serve.api import generate_many
from distributed_training_guide_tpu.serve.kv_pages import pool_audit
from distributed_training_guide_tpu.serve.tiering import (HostTier,
                                                          prefix_digest,
                                                          pull_prefix)
from distributed_training_guide_tpu.utils import faults

pytestmark = pytest.mark.tiering


@pytest.fixture(scope="module")
def llama():
    bundle = get_model("llama-debug", dtype=jnp.float32)
    return bundle, bundle.init(bundle.config, jax.random.key(0))


def _fresh(req):
    return dataclasses.replace(req, request_id=None)


def _ref_engine(bundle, params, **kw):
    return ServeEngine(bundle, params, n_slots=1, prefix_cache=False, **kw)


def _slot_holders(sched) -> dict:
    held: dict = {}
    for slot in sched.slots:
        if slot is None:
            continue
        assert 0 not in slot.pages, "trash page in a live table"
        for p in slot.pages:
            held[p] = held.get(p, 0) + 1
    return held


def _cache_refs(sched) -> dict:
    """page -> prefix-cache references, across EVERY adapter namespace."""
    refs: dict = {}
    if sched.cache is None:
        return refs
    stack = list(sched.cache._roots.values())
    while stack:
        node = stack.pop()
        for child in node.children.values():
            refs[child.page] = refs.get(child.page, 0) + 1
            stack.append(child)
    return refs


def _audit(eng) -> None:
    """The extended per-iteration audit: HBM identity + tier ledger."""
    sched = eng.scheduler
    pool_audit(sched.pool, [_slot_holders(sched), _cache_refs(sched)],
               tier=eng.host_tier)


# ---- preempt-spill-restore -------------------------------------------------

@pytest.mark.chaos
@pytest.mark.parametrize("kv_dtype", [
    None, pytest.param("int8", marks=pytest.mark.kvquant)])
def test_preempt_spill_restore_bitwise_identity(llama, kv_dtype):
    """The acceptance pin: a pool far below worst case forces real
    preemptions; with the host tier attached the victims' live pages
    spill and resume is scatter-and-seat — every request (greedy AND
    sampled) is token-bitwise vs batch-1, NO preempted sequence that
    restore-hits re-prefills (prefill calls == admissions + restore
    MISSES only), and the extended audit holds after every iteration."""
    bundle, params = llama
    eng = ServeEngine(bundle, params, n_slots=4, page_size=4, max_len=16,
                      n_pages=7, kv_dtype=kv_dtype,
                      host_tier_bytes=1 << 20)
    reqs = [Request(prompt_ids=[3 + i, 17, 42][:1 + i % 3],
                    max_new_tokens=6 + (i % 5),
                    temperature=0.8 if i % 2 else 0.0, seed=i)
            for i in range(8)]
    ids = [eng.submit(_fresh(r)) for r in reqs]
    done, it = {}, 0
    while eng.has_work:
        for res in eng.step():
            done[res.request_id] = res
        _audit(eng)
        it += 1
        assert it < 3000, "engine stalled"
    st = eng.stats()
    assert eng.scheduler.stats["preempted"] > 0   # real pressure
    assert st["restore_hits"] > 0                 # real spill-restores
    # resume is scatter-and-seat, not re-prefill: one prefill chunk per
    # ADMISSION (every prompt here fits the engine's own chunk size), plus one only for each preempted entry whose restore
    # missed (which then re-admits through the recompute path)
    assert st["prefill_calls"] == len(reqs) + st["restore_misses"]
    ref_eng = _ref_engine(bundle, params, page_size=4, max_len=16,
                          kv_dtype=kv_dtype)
    for rid, req in zip(ids, reqs):
        ref = generate_many(ref_eng, [_fresh(req)])[0]
        assert done[rid].token_ids == ref.token_ids, \
            f"seed={req.seed} diverged across spill-restore"
    _audit(eng)                                   # drained and balanced


def test_stats_report_and_gauges_expose_tier(llama):
    """Observability satellite: the tier gauges ride stats() (the
    /healthz payload) and the kv_report grows host-tier rows."""
    bundle, params = llama
    eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=16,
                      host_tier_bytes=1 << 16)
    st = eng.stats()
    for key in ("host_tier_bytes", "host_tier_budget_bytes",
                "spilled_pages", "restore_hits", "restore_misses",
                "prefill_calls"):
        assert key in st, key
    assert st["host_tier_budget_bytes"] == 1 << 16
    rep = eng.kv_report()
    assert rep["host_tier_budget_bytes"] == 1 << 16
    assert "host_tier_page_capacity" in rep


# ---- HostTier ledger discipline --------------------------------------------

def test_host_tier_budget_lru_and_audit():
    """Unit discipline: byte budget is a hard ceiling (oversized put
    rejected, LRU evicted to fit), get touches recency, take consumes,
    and the ledger audits throughout."""
    rec = {"k": np.arange(10, dtype=np.float32)}      # 40 bytes
    tier = HostTier(budget_bytes=100)
    assert tier.put(("a",), rec, pages=1)
    assert tier.put(("b",), rec, pages=1)
    tier.audit()
    assert not tier.put(("big",), {"k": np.zeros(64, np.float32)})
    assert tier.counters["spill_rejects"] == 1
    tier.get(("a",))                                  # a is now MRU
    assert tier.put(("c",), rec, pages=1)             # evicts b (LRU)
    assert tier.get(("b",)) is None
    assert tier.counters["evictions"] == 1
    assert tier.spilled_pages == 2 and tier.bytes_used == 80
    taken = tier.take(("a",))
    assert np.array_equal(taken.payload["k"], rec["k"])
    assert tier.get(("a",)) is None and len(tier) == 1
    tier.audit()


# ---- fleet directory: zero-prefill pulls, torn frames, tenant isolation ----

def _warm_prefix():
    return [3 + (i % 60) for i in range(24)]          # 6 full pages


_FLEET_KW = dict(n_slots=2, page_size=4, max_len=64, prefill_chunk=4,
                 host_tier_bytes=1 << 20, share_programs=False)


def _warm_and_drain(bundle, params):
    """A 2-replica fleet with the shared prefix committed on one replica
    that then DRAINS — the next request for that prefix must land on the
    cold sibling (drained replicas stay live, so they remain legal pull
    SOURCES). Independent programs keep prefill counters per-replica."""
    from distributed_training_guide_tpu.serve.router import local_fleet

    fleet = local_fleet(bundle, params, 2, **_FLEET_KW)
    generate_many(fleet, [Request(prompt_ids=_warm_prefix() + [5],
                                  max_new_tokens=3)])
    fleet.step()                       # stats snapshot -> directory
    warm = [n for n, (_, keys) in fleet._directory.items() if keys][0]
    fleet.replicas[warm].drain()
    return fleet, warm


def _prefill_calls(fleet):
    return {n: r.engine.programs.prefill_calls
            for n, r in fleet.replicas.items()}


def test_directory_pull_seats_prefix_with_zero_prefill(llama):
    """The acceptance pin: a directory hit on a cold replica pulls the
    committed pages over the wire and seats them — the pulled replica
    runs exactly as many prefill forwards as a warm-LOCAL engine (the
    one residual chunk past the last full page; literally zero passes
    over the pulled pages), strictly fewer than the cold re-prefill."""
    bundle, params = llama
    probe = Request(prompt_ids=_warm_prefix() + [8], max_new_tokens=3)
    fleet, warm = _warm_and_drain(bundle, params)
    pc0 = _prefill_calls(fleet)
    res = generate_many(fleet, [_fresh(probe)])
    pc1 = _prefill_calls(fleet)
    dst = [n for n in fleet.replicas if n != warm][0]
    assert fleet.counters["directory_pulls"] == 1
    assert fleet.counters["directory_pull_hits"] == 1
    assert pc1[warm] == pc0[warm], "pull must only READ the source"
    pulled_calls = pc1[dst] - pc0[dst]

    warm_ctl = ServeEngine(bundle, params, n_slots=2, page_size=4,
                           max_len=64, prefill_chunk=4)
    generate_many(warm_ctl, [Request(prompt_ids=_warm_prefix() + [5],
                                     max_new_tokens=3)])
    c0 = warm_ctl.programs.prefill_calls
    warm_res = generate_many(warm_ctl, [_fresh(probe)])
    warm_calls = warm_ctl.programs.prefill_calls - c0

    cold_ctl = ServeEngine(bundle, params, n_slots=2, page_size=4,
                           max_len=64, prefill_chunk=4)
    cold_res = generate_many(cold_ctl, [_fresh(probe)])
    cold_calls = cold_ctl.programs.prefill_calls

    assert pulled_calls == warm_calls < cold_calls
    assert res[0].token_ids == warm_res[0].token_ids \
        == cold_res[0].token_ids
    for r in fleet.replicas.values():
        _audit(r.engine)


@pytest.mark.chaos
def test_torn_directory_pull_degrades_to_clean_reprefill(llama,
                                                         monkeypatch):
    """A pull frame torn on the wire (sender crash -> CRC NAK) is an
    ordinary cache miss, never corruption: the routed replica re-prefills
    the full prompt, tokens stay identical to the cold reference, and
    both replicas audit clean after every iteration."""
    bundle, params = llama
    # router xfer ids count from 1 -> the FIRST pull is the torn one
    monkeypatch.setenv(faults.ENV_HANDOFF_CRASH_XFER, "1")
    probe = Request(prompt_ids=_warm_prefix() + [8], max_new_tokens=3)
    fleet, warm = _warm_and_drain(bundle, params)
    pc0 = _prefill_calls(fleet)
    fleet.submit(_fresh(probe))
    done, it = [], 0
    while fleet.has_work:
        done.extend(fleet.step())
        for r in fleet.replicas.values():
            _audit(r.engine)
        it += 1
        assert it < 2000
    assert fleet.counters["directory_pulls"] == 1
    assert fleet.counters["directory_pull_hits"] == 0
    assert fleet.counters["directory_pull_failures"] == 1
    dst = [n for n in fleet.replicas if n != warm][0]
    cold_ctl = ServeEngine(bundle, params, n_slots=2, page_size=4,
                           max_len=64, prefill_chunk=4)
    cold_res = generate_many(cold_ctl, [_fresh(probe)])
    # the plain miss: full cold re-prefill, identical tokens
    assert (_prefill_calls(fleet)[dst] - pc0[dst]
            == cold_ctl.programs.prefill_calls)
    assert done[0].token_ids == cold_res[0].token_ids


@pytest.mark.chaos
@pytest.mark.parametrize("knob,xfer,reason", [
    (faults.ENV_HANDOFF_CRASH_XFER, 5, "dropped_nak"),
    (faults.ENV_HANDOFF_TIMEOUT_XFER, 6, "dropped_timeout"),
])
def test_pull_prefix_wire_faults_leave_dst_cold(llama, monkeypatch,
                                                knob, xfer, reason):
    """Both wire failure modes at the pull primitive: torn bytes and a
    stalled receiver end with ok=False, NOTHING half-seated on the
    destination, and the destination still serves the request identical
    to its own cold reference."""
    bundle, params = llama
    tokens = _warm_prefix() + [8]
    kw = dict(n_slots=2, page_size=4, max_len=64, prefill_chunk=4)
    src = ServeEngine(bundle, params, host_tier_bytes=1 << 20, **kw)
    generate_many(src, [Request(prompt_ids=_warm_prefix() + [5],
                                max_new_tokens=3)])
    dst = ServeEngine(bundle, params, host_tier_bytes=1 << 20, **kw)
    monkeypatch.setenv(knob, str(xfer))
    out = pull_prefix(src, dst, tokens, xfer_id=xfer, ack_timeout_s=0.2)
    assert out["ok"] is False and out["reason"] == reason
    assert dst.scheduler.cache.chain_depth(tokens) == 0
    _audit(dst)
    monkeypatch.delenv(knob)
    got = generate_many(dst, [Request(prompt_ids=tokens,
                                      max_new_tokens=3)])[0]
    ref = generate_many(
        ServeEngine(bundle, params, **kw),
        [Request(prompt_ids=tokens, max_new_tokens=3)])[0]
    assert got.token_ids == ref.token_ids


def test_adapter_namespaced_prefix_keys_never_cross_tenants(llama):
    """Tenant isolation through the directory: the prefix key is salted
    by adapter id, so tenant A's committed chain is invisible to a base
    (or other-tenant) request — a cross-tenant pull finds the source
    COLD, and a matching-tenant pull seats only under that namespace."""
    from distributed_training_guide_tpu.models.lora import lora_bundle
    from distributed_training_guide_tpu.serve.tiering import \
        cache_prefix_keys

    bundle, params = llama
    tokens = _warm_prefix() + [8]
    assert prefix_digest(tokens, 0) != prefix_digest(tokens, 1)

    wrapped = lora_bundle(bundle, rank=4)
    shapes = jax.eval_shape(
        lambda: wrapped.init(wrapped.config, jax.random.key(0)))["lora"]
    leaves, treedef = jax.tree.flatten(shapes)
    adapter = jax.tree.unflatten(treedef, [
        0.2 * jax.random.normal(k, leaf.shape, jnp.float32)
        for k, leaf in zip(jax.random.split(jax.random.key(1),
                                            len(leaves)), leaves)])
    kw = dict(n_slots=2, page_size=4, max_len=64, prefill_chunk=4,
              max_adapters=2, adapter_rank=4, host_tier_bytes=1 << 20)
    src = ServeEngine(bundle, params, **kw)
    slot = src.publish_adapter(adapter, name="tenant")
    generate_many(src, [Request(prompt_ids=_warm_prefix() + [5],
                                max_new_tokens=3, adapter_id=slot)])
    keys = cache_prefix_keys(src.scheduler.cache)
    assert prefix_digest(_warm_prefix(), slot).hex() in keys
    assert prefix_digest(_warm_prefix(), 0).hex() not in keys

    dst = ServeEngine(bundle, params, **kw)
    # cross-tenant: the base namespace must NOT see tenant pages
    out = pull_prefix(src, dst, tokens, adapter_id=0)
    assert out["ok"] is False and out["reason"] == "src_cold"
    assert dst.scheduler.cache.chain_depth(tokens, ns=0) == 0
    # matching tenant: seats, and ONLY under the tenant namespace
    out = pull_prefix(src, dst, tokens, adapter_id=slot)
    assert out["ok"] and out["pages"] == 6
    assert dst.scheduler.cache.chain_depth(tokens, ns=slot) == 6
    assert dst.scheduler.cache.chain_depth(tokens, ns=0) == 0
    _audit(dst)


# ---- adapter spill past max_adapters ---------------------------------------

def test_adapter_spill_restore_roundtrip_bitwise(llama):
    """AdapterPool eviction under pressure spills the idle tenant's A/B
    rows to the host tier; restore_adapter re-seats them through the
    compiled insert — the stacks rows land bitwise what the spill
    gathered, with no fleet republish."""
    from distributed_training_guide_tpu.models.lora import lora_bundle

    bundle, params = llama
    wrapped = lora_bundle(bundle, rank=4)
    shapes = jax.eval_shape(
        lambda: wrapped.init(wrapped.config, jax.random.key(0)))["lora"]
    leaves, treedef = jax.tree.flatten(shapes)

    def adapter(seed):
        keys = jax.random.split(jax.random.key(seed), len(leaves))
        return jax.tree.unflatten(treedef, [
            0.2 * jax.random.normal(k, leaf.shape, jnp.float32)
            for k, leaf in zip(keys, leaves)])

    eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=16,
                      max_adapters=3, adapter_rank=4,
                      host_tier_bytes=1 << 24)
    s1 = eng.publish_adapter(adapter(1), name="t1")
    rows1 = {t: {leaf: np.asarray(pair[leaf][:, s1])
                 for leaf in ("a", "b")}
             for t, pair in eng.programs.adapter_stacks.items()}
    eng.publish_adapter(adapter(2), name="t2")
    eng.publish_adapter(adapter(3), name="t3")  # pool full -> evicts t1 (LRU)
    assert eng.programs.adapter_pool.stats["spill_evictions"] == 1
    assert eng.host_tier.get(("adapter", "t1")) is not None

    back = eng.restore_adapter("t1")
    assert back is not None
    assert eng.host_tier.get(("adapter", "t1")) is None  # consumed
    for t, pair in eng.programs.adapter_stacks.items():
        for leaf in ("a", "b"):
            assert np.array_equal(np.asarray(pair[leaf][:, back]),
                                  rows1[t][leaf]), (t, leaf)
    # unknown tenants restore to None, not garbage
    assert eng.restore_adapter("never-spilled") is None


# ---- generation swaps -------------------------------------------------------

def test_generation_swap_carries_and_drops_tier(llama):
    """Elastic seam: a payload-compatible swap CARRIES the host tier's
    records into the new generation (budget threaded through
    new_generation); a forced-replay swap DROPS them — old-policy k/v
    must not survive a seat path that recomputes."""
    from distributed_training_guide_tpu.serve.elastic import (
        new_generation, swap_generation)

    bundle, params = llama

    def seeded_engine():
        eng = ServeEngine(bundle, params, n_slots=2, page_size=4,
                          max_len=16, host_tier_bytes=1 << 20)
        payload = eng.gather_pages([1])
        assert eng.host_tier.put(("prefix", 0, (3, 17, 42, 7)), payload,
                                 pages=1)
        return eng

    old = seeded_engine()
    new = new_generation(old, n_slots=4)
    assert new.host_tier.budget_bytes == old.host_tier.budget_bytes
    _, stats = swap_generation(old, new)
    assert stats["tier_records_carried"] == 1
    assert stats["tier_records_dropped"] == 0
    assert new.host_tier.get(("prefix", 0, (3, 17, 42, 7))) is not None
    assert len(old.host_tier) == 0
    new.host_tier.audit()

    old2 = seeded_engine()
    new2 = new_generation(old2)
    _, stats2 = swap_generation(old2, new2, force_replay=True)
    assert stats2["tier_records_carried"] == 0
    assert stats2["tier_records_dropped"] == 1
    assert len(new2.host_tier) == 0 and len(old2.host_tier) == 0


# ---- disaggregated pair -----------------------------------------------------

@pytest.mark.disagg
def test_disagg_preempt_spill_restore_identity(llama):
    """The same preempt-spill-restore contract through the
    prefill/decode split: decode-side preemptions spill from the decode
    pool, the facade restores ahead of re-admission, and every request
    is token-identical whether its restore HIT (scatter-and-seat) or
    MISSED (the refuse-don't-corrupt fallback re-prefills)."""
    from distributed_training_guide_tpu.serve.disagg import DisaggEngine

    bundle, params = llama
    reqs = [Request(prompt_ids=[3 + i, 17, 42][:1 + i % 3],
                    max_new_tokens=6 + (i % 5),
                    temperature=0.8 if i % 2 else 0.0, seed=i)
            for i in range(8)]
    eng = DisaggEngine(bundle, params, n_slots=4, page_size=4, max_len=16,
                       n_pages=7, n_prefill_pages=9,
                       transport="cross_host", host_tier_bytes=1 << 20)
    res = generate_many(eng, reqs, max_iterations=3000)
    s = eng.stats()
    assert s["preempted"] > 0
    assert s["restore_hits"] + s["restore_misses"] > 0
    eng.host_tier.audit()
    assert eng.decode_pool.n_free == eng.decode_pool.capacity
    ref_eng = _ref_engine(bundle, params, page_size=4, max_len=16)
    for got, req in zip(res, reqs):
        ref = generate_many(ref_eng, [_fresh(req)])[0]
        assert got.token_ids == ref.token_ids, \
            f"seed={req.seed} diverged through the disagg spill path"
