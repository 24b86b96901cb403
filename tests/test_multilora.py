"""Batched multi-LoRA serving (serve/adapters.py + the grouped-GEMM lora
decode path in models/llama.py).

The contract under test:

- **identity**: an adapter decoded solo equals the same request decoded
  co-resident with other tenants; adapter 0 equals today's engine
  BITWISE (greedy and temp>0, spec-on and spec-off); a tenant's pooled
  decode matches a dedicated engine built from the merged weights.
- **retrace-free tenancy**: insert / republish / evict never retrace —
  the adapter stacks and per-slot ids are program ARGUMENTS, and the
  insert is one cached jit with a traced slot index. Pinned by
  ``jit_cache_sizes`` staying flat across churn, and by the lowered
  decode containing no dense per-adapter ``W + scale*A@B`` merge.
- **pool discipline**: the kv_pages lifecycle on adapter slots —
  refcounted by in-flight requests, LRU eviction only among idle
  tenants, slot 0 reserved as the zero adapter, loud refusals.
- **isolation**: prefix-cache pages are namespaced per adapter slot; a
  recycled slot id never serves the old tenant's cached prefixes.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_guide_tpu.models import get_model
from distributed_training_guide_tpu.models.lora import (lora_bundle,
                                                        mask_optimizer,
                                                        merge_lora)
from distributed_training_guide_tpu.serve.adapters import (
    AdapterPool, adapter_nbytes, adapter_pool_bytes, adapter_shapes,
    validate_adapter_params)
from distributed_training_guide_tpu.serve.api import generate_many
from distributed_training_guide_tpu.serve.engine import ServeEngine
from distributed_training_guide_tpu.serve.scheduler import (RefusalError,
                                                            Request)
from distributed_training_guide_tpu.utils import hlo as hlo_util

pytestmark = [pytest.mark.serve, pytest.mark.multilora]

RANK = 4


@pytest.fixture(scope="module")
def llama():
    bundle = get_model("llama-debug", dtype=jnp.float32)
    return bundle, bundle.init(bundle.config, jax.random.key(0))


@pytest.fixture(scope="module")
def wrapped(llama):
    return lora_bundle(llama[0], rank=RANK)


def _adapter(wrapped_bundle, seed: int, scale: float = 0.2) -> dict:
    """A NONTRIVIAL adapter payload: both factors random (the training
    init zeroes B, which would make every identity test vacuous)."""
    shapes = jax.eval_shape(
        lambda: wrapped_bundle.init(wrapped_bundle.config,
                                    jax.random.key(0)))["lora"]
    leaves, treedef = jax.tree.flatten(shapes)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(treedef, [
        scale * jax.random.normal(k, leaf.shape, jnp.float32)
        for k, leaf in zip(keys, leaves)])


def _reqs(specs):
    """Fresh Request objects per engine (results carry identity)."""
    return [Request(**spec) for spec in [dict(s) for s in specs]]


MIXED_SPECS = (
    # greedy and stochastic lanes for base and tenant traffic in ONE
    # batch — the bitwise pins below always cover both sampling paths
    dict(prompt_ids=[3, 5, 7, 11], max_new_tokens=8, seed=0),
    dict(prompt_ids=[4, 6, 8, 12], max_new_tokens=8, seed=1,
         temperature=0.8, top_k=5),
)


def _tokens(engine, specs):
    return [r.token_ids for r in generate_many(engine, _reqs(specs))]


# ---------------------------------------------------------------------------
# pool discipline
# ---------------------------------------------------------------------------

def test_adapter_pool_discipline():
    pool = AdapterPool(4, rank=8)
    assert pool.capacity == 3 and pool.n_free == 3 and pool.n_live == 0
    assert pool.scale == 2.0                      # alpha 16 / rank 8
    assert pool.is_live(0)                        # the zero adapter
    assert not pool.is_live(True)                 # bools are not slots
    assert not pool.is_live(1)

    a = pool.alloc("a")
    b = pool.alloc("b")
    c = pool.alloc("c")
    assert sorted([a, b, c]) == [1, 2, 3]
    assert pool.live_slots() == [1, 2, 3] and pool.n_free == 0
    assert pool.name_of(a) == "a"

    # refcounts: retain/release symmetric, loud on misuse
    pool.retain(a)
    assert pool.refcount(a) == 1
    pool.release(a)
    with pytest.raises(ValueError, match="double release"):
        pool.release(a)
    pool.retain(0)                                # no-op, never raises
    pool.release(0)
    with pytest.raises(ValueError):
        pool.retain(4)                            # out of range
    pool.evict(b)
    with pytest.raises(ValueError, match="not live"):
        pool.retain(b)

    # evict refuses while referenced; slot 0 never evictable
    pool.retain(a)
    with pytest.raises(ValueError, match="in-flight"):
        pool.evict(a)
    with pytest.raises(ValueError, match="never evictable"):
        pool.evict(0)

    # pressure: a is referenced, c idle -> LRU evicts c, not a
    d = pool.alloc("d")
    assert d == b                                 # the freed slot first
    pool.mark_update(d)                           # d most recently used
    e = pool.alloc("e")                           # pressure: no free slot
    assert e == c                                 # LRU idle tenant
    assert pool.stats["lru_evictions"] == 1
    pool.retain(d)
    f = pool.alloc("f")                           # only e is idle now
    assert f == e
    pool.release(a)
    pool.release(d)
    assert pool.alloc("g") in (a, d)              # idle again
    assert pool.stats["inserts"] == 7


def test_adapter_pool_alloc_none_when_all_referenced():
    pool = AdapterPool(3, rank=4)
    a, b = pool.alloc("a"), pool.alloc("b")
    pool.retain(a)
    pool.retain(b)
    before = dict(pool.stats)
    assert pool.alloc("c") is None                # nothing mutated
    assert dict(pool.stats) == before
    assert pool.live_slots() == sorted([a, b])


def test_adapter_pool_validation():
    with pytest.raises(ValueError, match="max_adapters"):
        AdapterPool(1, rank=4)
    with pytest.raises(ValueError, match="unknown adapter targets"):
        AdapterPool(4, rank=4, targets=("wq", "nope"))


def test_validate_adapter_params_loud(llama, wrapped):
    bundle, _ = llama
    shapes = adapter_shapes(bundle.config, rank=RANK, bundle=bundle)
    good = _adapter(wrapped, 1)
    validate_adapter_params(shapes, good)
    with pytest.raises(ValueError, match="target"):
        validate_adapter_params(shapes, {"wq": good["wq"]})
    bad_leaf = {t: dict(v) for t, v in good.items()}
    bad_leaf["wq"] = {"a": good["wq"]["a"]}
    with pytest.raises(ValueError):
        validate_adapter_params(shapes, bad_leaf)
    bad_shape = {t: dict(v) for t, v in good.items()}
    bad_shape["wq"]["a"] = good["wq"]["a"][:, :, :-1]
    with pytest.raises(ValueError, match="shape"):
        validate_adapter_params(shapes, bad_shape)
    bad_dtype = {t: dict(v) for t, v in good.items()}
    bad_dtype["wq"]["a"] = good["wq"]["a"].astype(jnp.int32)
    with pytest.raises(ValueError):
        validate_adapter_params(shapes, bad_dtype)


def test_adapter_bytes_arithmetic(llama):
    bundle, _ = llama
    cfg = bundle.config
    shapes = adapter_shapes(cfg, rank=RANK, bundle=bundle)
    manual = sum(
        int(np.prod(shapes[t]["a"])) + int(np.prod(shapes[t]["b"]))
        for t in shapes) * 4
    assert adapter_nbytes(cfg, rank=RANK, bundle=bundle) == manual
    assert adapter_pool_bytes(cfg, max_adapters=8, rank=RANK,
                              bundle=bundle) == 8 * manual


# ---------------------------------------------------------------------------
# identity pins
# ---------------------------------------------------------------------------

def test_zero_adapter_is_base_engine_bitwise(llama):
    """A pooled engine serving only adapter-0 traffic is bitwise
    today's engine — greedy AND temp>0, spec-off and spec-on."""
    bundle, params = llama
    kw = dict(n_slots=2, page_size=8, max_len=48)
    plain = _tokens(ServeEngine(bundle, params, **kw), MIXED_SPECS)
    pooled = _tokens(ServeEngine(bundle, params, max_adapters=4,
                                 adapter_rank=RANK, **kw), MIXED_SPECS)
    assert pooled == plain
    spec_kw = dict(kw, speculate="ngram", spec_k=4)
    plain_spec = _tokens(ServeEngine(bundle, params, **spec_kw),
                         MIXED_SPECS)
    pooled_spec = _tokens(ServeEngine(bundle, params, max_adapters=4,
                                      adapter_rank=RANK, **spec_kw),
                          MIXED_SPECS)
    assert plain_spec == plain                    # spec identity, base
    assert pooled_spec == plain                   # ...and pooled


def test_adapter_matches_merged_engine(llama, wrapped):
    """A pooled tenant decode equals a dedicated engine built from the
    merged weights (greedy and temp>0) — the pooled grouped-GEMM delta
    IS ``W + scale*A@B``, just never materialized."""
    bundle, params = llama
    payload = _adapter(wrapped, 7)
    kw = dict(n_slots=2, page_size=8, max_len=48)
    eng = ServeEngine(bundle, params, max_adapters=4, adapter_rank=RANK,
                      **kw)
    slot = eng.publish_adapter(payload, name="tenant")
    specs = [dict(s, adapter_id=slot) for s in MIXED_SPECS]
    pooled = _tokens(eng, specs)
    merged = merge_lora(wrapped, {"base": params, "lora": payload})
    ref = _tokens(ServeEngine(bundle, merged, **kw), MIXED_SPECS)
    assert pooled == ref


def test_solo_equals_coresident(llama, wrapped):
    """Adapter-batch-of-1 == the same request co-resident with another
    tenant and base traffic: no cross-slot leakage, no batch-shape
    dependence in the delta."""
    bundle, params = llama
    eng = ServeEngine(bundle, params, n_slots=4, page_size=8, max_len=48,
                      max_adapters=4, adapter_rank=RANK)
    s1 = eng.publish_adapter(_adapter(wrapped, 1), name="a")
    s2 = eng.publish_adapter(_adapter(wrapped, 2), name="b")
    probe = dict(prompt_ids=[9, 13, 17], max_new_tokens=8, seed=3,
                 temperature=0.7, top_k=8, adapter_id=s1)
    solo = _tokens(eng, [probe])
    mixed_specs = [
        probe,
        dict(prompt_ids=[2, 4, 6], max_new_tokens=8, seed=4,
             adapter_id=s2),
        dict(prompt_ids=[5, 10, 15], max_new_tokens=8, seed=5),
    ]
    mixed = _tokens(eng, mixed_specs)
    assert mixed[0] == solo[0]
    # and the base request in the mixed batch matches a plain engine
    base_ref = _tokens(
        ServeEngine(bundle, params, n_slots=4, page_size=8, max_len=48),
        [mixed_specs[2]])
    assert mixed[2] == base_ref[0]


def test_spec_identity_with_adapters(llama, wrapped):
    """spec-on == spec-off for tenant traffic: the verify program
    applies the same grouped deltas as decode, so exact acceptance
    keeps multi-LoRA streams bitwise."""
    bundle, params = llama
    payload = _adapter(wrapped, 11)
    kw = dict(n_slots=2, page_size=8, max_len=64,
              max_adapters=4, adapter_rank=RANK)
    prompt = [7, 11, 13, 7, 11, 13, 7, 11, 13]
    specs = [dict(prompt_ids=prompt, max_new_tokens=16, seed=0),
             dict(prompt_ids=prompt, max_new_tokens=16, seed=1,
                  temperature=0.8, top_k=5)]

    eng_off = ServeEngine(bundle, params, **kw)
    slot = eng_off.publish_adapter(payload, name="t")
    tenant_specs = [dict(s, adapter_id=slot) for s in specs]
    off = _tokens(eng_off, tenant_specs)

    eng_on = ServeEngine(bundle, params, speculate="ngram", spec_k=4,
                         **kw)
    assert eng_on.publish_adapter(payload, name="t") == slot
    on = _tokens(eng_on, tenant_specs)
    assert on == off
    assert eng_on.spec["spec_steps"] > 0          # speculation actually ran


def test_multilora_under_int8_weights(llama, wrapped):
    """The pool composes with block-quantized base weights: adapter-0
    stays bitwise the plain int8 engine, and a tenant's fp32 delta
    rides the int8 base (solo == co-resident there too)."""
    bundle, params = llama
    kw = dict(n_slots=2, page_size=8, max_len=48, weight_dtype="int8")
    plain = _tokens(ServeEngine(bundle, params, **kw), MIXED_SPECS)
    eng = ServeEngine(bundle, params, max_adapters=4, adapter_rank=RANK,
                      **kw)
    assert _tokens(eng, MIXED_SPECS) == plain     # adapter 0 == base
    slot = eng.publish_adapter(_adapter(wrapped, 5), name="t")
    probe = dict(prompt_ids=[9, 13, 17], max_new_tokens=8, seed=2,
                 adapter_id=slot)
    solo = _tokens(eng, [probe])
    assert solo[0] != plain[0][:len(solo[0])]     # the delta is live
    mixed = _tokens(eng, [probe, MIXED_SPECS[0]])
    assert mixed[0] == solo[0]


# ---------------------------------------------------------------------------
# admission + refusals
# ---------------------------------------------------------------------------

def test_unknown_adapter_refused(llama):
    bundle, params = llama
    plain = ServeEngine(bundle, params, n_slots=2, page_size=8,
                        max_len=32)
    with pytest.raises(RefusalError) as exc:
        plain.submit(Request(prompt_ids=[3], max_new_tokens=2,
                             adapter_id=1))
    assert exc.value.reason == "unknown_adapter"

    pooled = ServeEngine(bundle, params, n_slots=2, page_size=8,
                         max_len=32, max_adapters=4, adapter_rank=RANK)
    with pytest.raises(RefusalError) as exc:
        pooled.submit(Request(prompt_ids=[3], max_new_tokens=2,
                              adapter_id=3))
    assert exc.value.reason == "unknown_adapter"
    assert exc.value.http_status == 404
    with pytest.raises(RefusalError) as exc:
        pooled.submit(Request(prompt_ids=[3], max_new_tokens=2,
                              adapter_id="fast"))
    assert exc.value.reason == "bad_params"
    with pytest.raises(RefusalError) as exc:
        pooled.submit(Request(prompt_ids=[3], max_new_tokens=2,
                              adapter_id=True))
    assert exc.value.reason == "bad_params"
    assert pooled.stats()["refused"]["unknown_adapter"] == 1


def test_scheduler_refcount_lifecycle(llama, wrapped):
    """In-flight requests hold their tenant's slot: evict refuses
    mid-stream and succeeds after drain; drain_queue releases queued
    holders too."""
    bundle, params = llama
    eng = ServeEngine(bundle, params, n_slots=2, page_size=8, max_len=48,
                      max_adapters=4, adapter_rank=RANK)
    slot = eng.publish_adapter(_adapter(wrapped, 3), name="t")
    pool = eng.adapter_pool
    eng.submit(Request(prompt_ids=[3, 5], max_new_tokens=12,
                       adapter_id=slot))
    eng.submit(Request(prompt_ids=[4, 6], max_new_tokens=12,
                       adapter_id=slot))
    assert pool.refcount(slot) == 2
    eng.step()
    with pytest.raises(ValueError, match="in-flight"):
        eng.evict_adapter(slot)
    while eng.has_work:
        eng.step()
    assert pool.refcount(slot) == 0
    assert eng.stats()["adapter_requests"] == {slot: 2}
    eng.evict_adapter(slot)
    assert not pool.is_live(slot)


# ---------------------------------------------------------------------------
# retrace-free tenancy
# ---------------------------------------------------------------------------

def test_jit_caches_flat_across_adapter_churn(llama, wrapped):
    """Insert / republish / evict with a CONSTANT workload: every jit
    cache size stays exactly flat — tenancy is data, not programs.
    (prefix_cache off: the cache's own hit-path commit entry is a
    pre-existing, adapter-independent retrace.)"""
    bundle, params = llama
    eng = ServeEngine(bundle, params, n_slots=2, page_size=8, max_len=48,
                      prefix_cache=False, max_adapters=4,
                      adapter_rank=RANK)
    payloads = [_adapter(wrapped, s) for s in (1, 2, 3)]
    s1 = eng.publish_adapter(payloads[0], name="t0")

    def run():
        specs = [dict(prompt_ids=[3, 5, 7], max_new_tokens=6, seed=0),
                 dict(prompt_ids=[3, 5, 8], max_new_tokens=6, seed=1,
                      temperature=0.8, top_k=5, adapter_id=s1)]
        return _tokens(eng, specs)

    run()
    run()                                         # both admission paths warm
    sizes0 = dict(eng.programs.jit_cache_sizes())
    assert sizes0.get("adapter_insert") == 1
    for i, payload in enumerate(payloads):
        fresh = eng.publish_adapter(payload, name=f"t{i + 1}")
        eng.publish_adapter(payloads[0], slot=s1)  # republish in place
        eng.evict_adapter(fresh)
        run()
        assert dict(eng.programs.jit_cache_sizes()) == sizes0, \
            f"adapter churn round {i} retraced"


def test_decode_hlo_no_merged_weight_materialization(llama, wrapped):
    """The lowered pooled decode contains the stacked factors and NO
    dense per-adapter merged projection: the delta flows through the
    ragged grouped GEMM at rank width, never through a ``[G, in, out]``
    (or per-slot ``[S, in, out]``) weight tensor."""
    bundle, params = llama
    cfg = bundle.config
    # n_slots chosen to collide with NO model dim (llama-debug has 2
    # layers, so n_slots=2 would alias the stacked base weight [L, e, h])
    n_slots, max_adapters = 3, 4
    eng = ServeEngine(bundle, params, n_slots=n_slots, page_size=8,
                      max_len=32, max_adapters=max_adapters,
                      adapter_rank=RANK)
    eng.publish_adapter(_adapter(wrapped, 1), name="t")
    arr = eng.scheduler.decode_arrays()
    lora_args = eng.programs.lora_call_args(arr["adapters"])
    text = eng._decode_fn.lower(
        eng.params, eng.pages,
        jnp.asarray(arr["tokens"]), jnp.asarray(arr["lengths"]),
        jnp.asarray(arr["tables"]), jnp.asarray(arr["seeds"]),
        jnp.asarray(arr["temps"]), jnp.asarray(arr["top_ks"]),
        jnp.asarray(arr["top_ps"]), jnp.asarray(arr["actives"]),
        *lora_args).as_text()
    e = cfg.hidden_size
    hq = cfg.num_heads * cfg.head_size
    hkv = cfg.num_kv_heads * cfg.head_size
    l = cfg.num_layers
    # the stacked factors ARE in the program (the lora path is live)...
    assert hlo_util.has_aval(text, "f32", (l, max_adapters, e, RANK))
    assert hlo_util.has_aval(text, "f32", (l, max_adapters, RANK, hq))
    # ...but no merged per-adapter (or per-slot) projection ever exists
    for fan_out in (hq, hkv):
        assert not hlo_util.has_shape_run(text, (max_adapters, e, fan_out))
        assert not hlo_util.has_shape_run(text, (n_slots, e, fan_out))


# ---------------------------------------------------------------------------
# prefix-cache namespacing
# ---------------------------------------------------------------------------

def test_prefix_cache_namespaced_per_adapter(llama, wrapped):
    """The same prompt under two tenants shares NOTHING: cached pages
    hold k/v computed under one adapter's deltas. Same-tenant reuse
    still hits; a recycled slot id starts from an empty namespace."""
    bundle, params = llama
    eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=48,
                      max_adapters=4, adapter_rank=RANK)
    slot = eng.publish_adapter(_adapter(wrapped, 1), name="a")
    prompt = list(range(3, 3 + 12))               # 3 full pages cacheable

    def one(adapter_id):
        return generate_many(eng, [Request(
            prompt_ids=prompt, max_new_tokens=2, adapter_id=adapter_id)])

    one(0)
    assert eng.stats()["prefix_hits"] == 0
    one(0)                                        # same tenant: hit
    assert eng.stats()["prefix_hits"] == 1
    one(slot)                                     # other tenant: MISS
    assert eng.stats()["prefix_hits"] == 1
    one(slot)                                     # its own namespace: hit
    assert eng.stats()["prefix_hits"] == 2
    # recycling the slot id drops the namespace with its pages
    held = eng.scheduler.cache_pages_held()
    assert held > 0
    eng.evict_adapter(slot)
    assert eng.scheduler.cache_pages_held() < held
    new_slot = eng.publish_adapter(_adapter(wrapped, 2), name="b")
    assert new_slot == slot                       # the recycled id
    one(new_slot)                                 # must NOT hit a's pages
    assert eng.stats()["prefix_hits"] == 2


# ---------------------------------------------------------------------------
# stats + reports
# ---------------------------------------------------------------------------

def test_engine_stats_and_adapter_report(llama, wrapped):
    bundle, params = llama
    eng = ServeEngine(bundle, params, n_slots=2, page_size=8, max_len=32,
                      max_adapters=4, adapter_rank=RANK)
    s0 = eng.stats()
    seq0 = s0["stats_seq"]
    assert s0["adapter_slots"] == 4 and s0["adapter_capacity"] == 3
    assert s0["adapters_live"] == 0 and s0["adapter_occupancy"] == 0.0
    slot = eng.publish_adapter(_adapter(wrapped, 1), name="t")
    generate_many(eng, [Request(prompt_ids=[3], max_new_tokens=2,
                                adapter_id=slot),
                        Request(prompt_ids=[4], max_new_tokens=2)])
    s1 = eng.stats()
    assert s1["adapters_live"] == 1
    assert s1["adapter_occupancy"] == round(1 / 3, 3)
    assert s1["adapter_inserts"] == 1 and s1["adapter_publishes"] == 1
    assert s1["adapter_requests"] == {slot: 1, 0: 1}
    assert s1["stats_seq"] > seq0                 # the seq is unchanged

    rep = eng.adapter_report()
    per = adapter_nbytes(bundle.config, rank=RANK, bundle=bundle)
    assert rep["bytes_per_adapter"] == per
    assert rep["pool_bytes"] == 4 * per
    assert rep["publish_payload_bytes"] == per
    assert rep["max_adapters"] == 4 and rep["rank"] == RANK

    # a pool-less engine publishes NO adapter keys (stats shape is
    # backward compatible)
    plain = ServeEngine(bundle, params, n_slots=2, page_size=8,
                        max_len=32)
    assert "adapter_slots" not in plain.stats()
    assert plain.adapter_report() == {}


def test_publish_adapter_busy_refusal_and_force(llama, wrapped):
    bundle, params = llama
    eng = ServeEngine(bundle, params, n_slots=2, page_size=8, max_len=48,
                      max_adapters=4, adapter_rank=RANK)
    payload = _adapter(wrapped, 1)
    eng.submit(Request(prompt_ids=[3, 5], max_new_tokens=8))
    eng.step()
    with pytest.raises(RuntimeError, match="in flight"):
        eng.publish_adapter(payload, name="t")
    assert eng.adapter_pool.n_live == 0           # nothing was mutated
    slot = eng.publish_adapter(payload, name="t", force=True)
    assert eng.adapter_pool.is_live(slot)
    while eng.has_work:
        eng.step()


def test_pool_exhaustion_raises(llama, wrapped):
    bundle, params = llama
    eng = ServeEngine(bundle, params, n_slots=2, page_size=8, max_len=32,
                      max_adapters=3, adapter_rank=RANK)
    a = eng.publish_adapter(_adapter(wrapped, 1), name="a")
    b = eng.publish_adapter(_adapter(wrapped, 2), name="b")
    # both tenants referenced -> a third insert has nowhere to land
    eng.adapter_pool.retain(a)
    eng.adapter_pool.retain(b)
    with pytest.raises(RuntimeError, match="exhausted"):
        eng.publish_adapter(_adapter(wrapped, 3), name="c")
    eng.adapter_pool.release(a)
    # idle tenant a gets LRU-recycled now
    c = eng.publish_adapter(_adapter(wrapped, 3), name="c")
    assert c == a
    assert eng.adapter_pool.stats["lru_evictions"] == 1
    eng.adapter_pool.release(b)


# ---------------------------------------------------------------------------
# disaggregated pair
# ---------------------------------------------------------------------------

@pytest.mark.disagg
def test_disagg_adapters_end_to_end(llama, wrapped):
    from distributed_training_guide_tpu.serve.disagg import DisaggEngine

    bundle, params = llama
    payload = _adapter(wrapped, 7)
    eng = DisaggEngine(bundle, params, n_slots=2, n_prefill_slots=1,
                       page_size=8, max_len=48, max_adapters=4,
                       adapter_rank=RANK)
    slot = eng.publish_adapter(payload, name="t")
    specs = [dict(s, adapter_id=slot) for s in MIXED_SPECS]
    got = _tokens(eng, specs)
    merged = merge_lora(wrapped, {"base": params, "lora": payload})
    ref = _tokens(ServeEngine(bundle, merged, n_slots=2, page_size=8,
                              max_len=48), MIXED_SPECS)
    assert got == ref
    s = eng.stats()
    assert s["adapters_live"] == 1 and s["adapter_publishes"] == 1
    assert s["adapter_requests"] == {slot: 2}
    assert eng.adapter_pool.refcount(slot) == 0   # handoff net-neutral
    assert eng.adapter_report()["max_adapters"] == 4
    eng.close()


# ---------------------------------------------------------------------------
# fleet + post-training publish
# ---------------------------------------------------------------------------

def test_post_trained_adapter_publishes_to_fleet(llama):
    """The post seam end to end: TRAIN a toy adapter (masked optimizer,
    base frozen), publish it into a 2-replica fleet as a pool insert,
    and the fleet's tenant decode matches a dedicated merged engine.
    A busy replica refuses the WHOLE publish (all-or-nothing)."""
    from distributed_training_guide_tpu.parallel import make_mesh, make_plan
    from distributed_training_guide_tpu.post.loop import (
        adapter_payload, publish_trained_adapter)
    from distributed_training_guide_tpu.serve.router import local_fleet
    from distributed_training_guide_tpu.train import Trainer, adamw_cosine

    bundle, _ = llama
    wrapped4 = lora_bundle(bundle, rank=RANK)
    trainer = Trainer(bundle=wrapped4,
                      optimizer=mask_optimizer(adamw_cosine(1e-2)),
                      plan=make_plan("single",
                                     make_mesh(devices=jax.devices()[:1])),
                      donate=False)
    state = trainer.init_state(0)
    batch = {k: jnp.asarray(np.random.RandomState(0)
                            .randint(0, 64, (2, 16)))
             for k in ("input_ids", "labels")}
    for _ in range(2):
        state, metrics = trainer.step_fn(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    payload = adapter_payload(state.params)
    assert any(np.abs(np.asarray(leaf)).max() > 0
               for leaf in jax.tree.leaves(payload)), "adapter untrained"

    base_params = state.params["base"]
    fleet = local_fleet(bundle, base_params, n_replicas=2, n_slots=2,
                        page_size=8, max_len=48, max_adapters=4,
                        adapter_rank=RANK)
    slot = publish_trained_adapter(fleet, state, name="tenant")
    specs = [dict(prompt_ids=[3, 5, 7, 11], max_new_tokens=8, seed=0,
                  adapter_id=slot)]
    got = _tokens(fleet, specs)
    merged = merge_lora(wrapped4, state.params)
    ref = _tokens(ServeEngine(bundle, merged, n_slots=2, page_size=8,
                              max_len=48),
                  [dict(specs[0], adapter_id=0)])
    assert got == ref
    s = fleet.stats()
    assert s["adapters_live"] == 1                # shared pool, counted once
    assert s["adapter_requests"].get(slot) == 1

    # busy replica -> the whole publish refuses, pool untouched
    fleet.submit(Request(prompt_ids=[4, 6], max_new_tokens=16))
    fleet.step()
    inserts_before = fleet.stats()["adapter_inserts"]
    with pytest.raises(RuntimeError, match="in-flight"):
        publish_trained_adapter(fleet, state, name="again")
    assert fleet.stats()["adapter_inserts"] == inserts_before
    while fleet.has_work:
        fleet.step()
    fleet.close()


def test_adapter_payload_requires_lora_state():
    from distributed_training_guide_tpu.post.loop import adapter_payload

    with pytest.raises(ValueError, match="lora"):
        adapter_payload({"wte": np.zeros(3)})


# ---------------------------------------------------------------------------
# loadgen profile
# ---------------------------------------------------------------------------

def test_zipf_adapter_mix_scenario():
    from distributed_training_guide_tpu.serve.loadgen import (
        adapter_mix_scenario, zipf_weights)

    w = zipf_weights(4, 1.1)
    assert pytest.approx(sum(w)) == 1.0
    assert w == sorted(w, reverse=True)           # rank 1 hottest
    with pytest.raises(ValueError):
        zipf_weights(0)

    scen = adapter_mix_scenario(max_len=64, n_adapters=4,
                                base_share=0.25)
    assert scen.adapter_ids == (0, 1, 2, 3, 4)
    assert pytest.approx(sum(scen.adapter_weights)) == 1.0
    assert scen.adapter_weights[0] == 0.25
    import random as random_mod
    rng = random_mod.Random(0)
    drawn = [scen.sample(rng, vocab=64, index=i).adapter_id
             for i in range(300)]
    counts = {a: drawn.count(a) for a in set(drawn)}
    assert set(counts) <= {0, 1, 2, 3, 4}
    assert counts[1] > counts[4]                  # Zipf head beats tail
    # determinism: the same seed replays the same tenancy
    rng2 = random_mod.Random(0)
    assert drawn == [scen.sample(rng2, vocab=64, index=i).adapter_id
                     for i in range(300)]


# ---------------------------------------------------------------------------
# sharded grid
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_multilora_tp2_matches_single_device(llama, wrapped,
                                             eight_devices):
    """The pooled decode on a tp=2 mesh (sharded KV pool, replicated
    adapter stacks) is token-identical to the single-device engine for
    mixed tenant traffic."""
    from distributed_training_guide_tpu.parallel import make_mesh, make_plan

    bundle, params = llama
    payload = _adapter(wrapped, 7)
    kw = dict(n_slots=2, page_size=8, max_len=48, max_adapters=4,
              adapter_rank=RANK)
    single = ServeEngine(bundle, params, **kw)
    slot = single.publish_adapter(payload, name="t")
    specs = [dict(MIXED_SPECS[0], adapter_id=slot), MIXED_SPECS[1]]
    want = _tokens(single, specs)

    plan = make_plan("tp", make_mesh(tp=2, devices=eight_devices[:2]))
    sharded = ServeEngine(bundle, params, plan=plan, shard_kv=True, **kw)
    assert sharded.publish_adapter(payload, name="t") == slot
    assert _tokens(sharded, specs) == want
