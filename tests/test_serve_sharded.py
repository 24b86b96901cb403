"""Sharded page pool (serve/sharding.py): token identity of the
kv-head-sharded pool vs the replicated batch-1 reference, the compiled-HLO
pin that no chip holds a full-kv-head pool tensor, the rules-table
mechanics, and the construction-time contract checks.

All on llama-debug (4 q heads, 2 kv heads) over a tp=2 slice of the
virtual 8-device CPU mesh — the 2 kv heads split one per chip.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_training_guide_tpu.models import get_model
from distributed_training_guide_tpu.parallel import make_mesh, make_plan
from distributed_training_guide_tpu.serve import Request, ServeEngine
from distributed_training_guide_tpu.serve.api import generate_many
from distributed_training_guide_tpu.serve.sharding import (
    match_partition_rules, SERVE_KV_RULES)
from distributed_training_guide_tpu.utils import hlo as hlo_util

pytestmark = pytest.mark.serve


@pytest.fixture(scope="module")
def llama():
    bundle = get_model("llama-debug", dtype=jnp.float32)
    return bundle, bundle.init(bundle.config, jax.random.key(0))


@pytest.fixture(scope="module")
def tp2_plan(eight_devices):
    return make_plan("tp", make_mesh(tp=2, devices=eight_devices[:2]))


def _fresh(req):
    return dataclasses.replace(req, request_id=None)


# ---- the rules table --------------------------------------------------------

def test_match_partition_rules_mechanics():
    """Pool leaves match the kv-head rule, bookkeeping arrays replicate,
    scalars replicate regardless, and an unmatched leaf fails loudly."""
    tree = {"pages": {"k": np.zeros((2, 5, 4, 2, 16)),
                      "v": np.zeros((2, 5, 4, 2, 16))},
            "tables": np.zeros((3, 4), np.int32),
            "temps": np.zeros(3, np.float32),
            "scalar": np.float32(1.0)}
    specs = match_partition_rules(SERVE_KV_RULES + ((r"scalar", P("tp")),),
                                  tree)
    assert specs["pages"]["k"] == P(None, None, None, "tp", None)
    assert specs["pages"]["v"] == P(None, None, None, "tp", None)
    assert specs["tables"] == P()
    assert specs["temps"] == P()
    assert specs["scalar"] == P()      # scalars never partition
    with pytest.raises(ValueError, match="no serve partition rule"):
        match_partition_rules(SERVE_KV_RULES,
                              {"mystery": np.zeros((4, 4))})


def test_shard_kv_contract_validated_at_construction(llama, eight_devices):
    """Every unservable sharded config refuses at engine construction:
    no plan, tp=1, a non-tp active axis, tp not dividing the kv heads."""
    bundle, params = llama
    with pytest.raises(ValueError, match="needs a plan"):
        ServeEngine(bundle, params, shard_kv=True)
    with pytest.raises(ValueError, match="tp > 1"):
        ServeEngine(bundle, params, shard_kv=True, plan=make_plan(
            "tp", make_mesh(devices=eight_devices[:1])))
    with pytest.raises(ValueError, match="tp-only"):
        ServeEngine(bundle, params, shard_kv=True, plan=make_plan(
            "tp_fsdp", make_mesh(tp=2, fsdp=2,
                                 devices=eight_devices[:4])))
    with pytest.raises(ValueError, match="num_kv_heads"):
        # llama-debug has 2 kv heads: tp=4 divides num_heads (4) only
        ServeEngine(bundle, params, shard_kv=True, plan=make_plan(
            "tp", make_mesh(tp=4, devices=eight_devices[:4])))


# ---- token identity ---------------------------------------------------------

def test_sharded_pool_token_identity(llama, tp2_plan):
    """The acceptance pin, first half: decode over per-chip pool slices
    is token-identical to the replicated single-device engine — greedy
    AND sampled, across co-residency and slot reuse."""
    bundle, params = llama
    reqs = [Request(prompt_ids=[3 + i, 17, 42][:(i % 3) + 1],
                    max_new_tokens=3 + (i % 4),
                    temperature=0.9 if i % 2 else 0.0, seed=i)
            for i in range(6)]
    sharded = generate_many(
        ServeEngine(bundle, params, n_slots=3, page_size=4, max_len=16,
                    plan=tp2_plan, shard_kv=True),
        [_fresh(r) for r in reqs])
    single = generate_many(
        ServeEngine(bundle, params, n_slots=3, page_size=4, max_len=16),
        [_fresh(r) for r in reqs])
    for a, b in zip(sharded, single):
        assert a.token_ids == b.token_ids


def test_sharded_chunked_prefill_and_cow(llama, tp2_plan):
    """Chunked prefill, prefix sharing, and the CoW fork all run their
    pool work inside the manual region: mid-page divergence under the
    sharded pool stays token-identical and forks exactly once."""
    bundle, params = llama
    common8 = [9, 8, 7, 6, 5, 4, 3, 2]
    eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=32,
                      plan=tp2_plan, shard_kv=True, prefill_chunk=4)
    res_a = generate_many(eng, [Request(prompt_ids=common8 + [1],
                                        max_new_tokens=3)])
    prompt_b = common8[:6] + [99]
    res_b = generate_many(eng, [Request(prompt_ids=prompt_b,
                                        max_new_tokens=5)])
    assert eng.scheduler.stats["cow_forks"] == 1
    ref = ServeEngine(bundle, params, n_slots=1, page_size=4, max_len=32,
                      prefix_cache=False)
    assert res_a[0].token_ids == generate_many(
        ref, [Request(prompt_ids=common8 + [1], max_new_tokens=3)]
    )[0].token_ids
    assert res_b[0].token_ids == generate_many(
        ref, [Request(prompt_ids=prompt_b, max_new_tokens=5)])[0].token_ids
    pool = eng.scheduler.pool
    assert pool.n_free + eng.scheduler.cache_pages_held() == pool.capacity


@pytest.mark.flash_decode
def test_sharded_flash_kernel_parity(llama, tp2_plan):
    """The Pallas flash-decode kernel runs PER CHIP inside the manual
    region (interpret mode here — the point is the per-chip pool slice
    wiring, hkv_local=1): tokens must equal the replicated xla engine."""
    bundle, params = llama
    reqs = [Request(prompt_ids=[3, 17, 42], max_new_tokens=5, seed=1),
            Request(prompt_ids=[5, 6], max_new_tokens=4, seed=2)]
    flash = generate_many(
        ServeEngine(bundle, params, n_slots=2, page_size=8, max_len=32,
                    plan=tp2_plan, shard_kv=True, attend_impl="flash"),
        [_fresh(r) for r in reqs])
    xla = generate_many(
        ServeEngine(bundle, params, n_slots=2, page_size=8, max_len=32),
        [_fresh(r) for r in reqs])
    for a, b in zip(flash, xla):
        assert a.token_ids == b.token_ids


# ---- the HLO pin ------------------------------------------------------------

def test_sharded_pool_compiled_hlo_pin(llama, tp2_plan):
    """The acceptance pin, second half: the lowered+partitioned decode
    program's cache avals are the PER-CHIP pool shape (kvh/tp) — the
    full-kv-head pool tensor appears on no shard, neither as the [L,...]
    pool nor as a per-layer slice (an all-gather around the manual
    region would reintroduce it)."""
    bundle, params = llama
    cfg = bundle.config
    eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=16,
                      n_pages=9, plan=tp2_plan, shard_kv=True)
    arr = eng.scheduler.decode_arrays()
    hlo = eng._decode_fn.lower(
        eng.params, eng.pages,
        jnp.asarray(arr["tokens"]), jnp.asarray(arr["lengths"]),
        jnp.asarray(arr["tables"]), jnp.asarray(arr["seeds"]),
        jnp.asarray(arr["temps"]), jnp.asarray(arr["top_ks"]),
        jnp.asarray(arr["top_ps"]), jnp.asarray(arr["actives"])
    ).compile().as_text()
    kvh, hd = cfg.num_kv_heads, cfg.head_size
    local = (cfg.num_layers, 9, 4, kvh // 2, hd)
    assert hlo_util.has_aval(hlo, "f32", local), \
        "per-chip (kvh/tp) pool slice missing from the compiled decode"
    for full in ((cfg.num_layers, 9, 4, kvh, hd), (9, 4, kvh, hd)):
        assert not hlo_util.has_aval(hlo, "f32", full), \
            f"full-kv-head pool tensor f32{list(full)} on a shard"
    # and the device arrays themselves are per-chip: each chip's resident
    # share of the pool is 1/2 of the global bytes
    shard_bytes = [
        np.prod(s.data.shape) * 4
        for s in eng.pages["k"].addressable_shards]
    assert all(b == eng.pages["k"].nbytes // 2 for b in shard_bytes)
