"""Int8 serve-plane weights (``weight_dtype="int8"``): block-wise
quantized params (serve/weights.py) dequantized inside the matmul loop
(ops/quantized_matmul.py).

What is pinned here, and why these meters:

- ROUND-TRIP + MATMUL PARITY with documented bounds: per-element
  quantization error is <= scale/2 = that block's absmax/254 (~0.4% of
  the block absmax). The standard-form quantized matmul computes each
  output column from ONE dequantized ``[K, bs]`` block, the identical
  contraction ``x @ dequant(w)`` performs — parity is 1e-5, not a
  quantization bound. The transpose form (tied lm_head) accumulates per
  block, so its bound is loose only in summation order (1e-4). The
  interpret-mode Pallas kernel reads the SAME bytes as the XLA scan —
  their difference is kernel error, not quantization.
- FORWARD PARITY split in two: int8-vs-fp logits stay inside LOGIT_ATOL
  across the llama feature grid (GQA, sliding window, softcap), and
  int8-vs-SNAPPED-fp (the same rounded weights served from fp storage)
  stays inside machine-epsilon territory — the storage path must add
  nothing beyond the rounding it stores.
- BYTE + HLO PINS: llama-debug int8 weights (scales included) are
  0.2847x the fp32 tree — comfortably past the >= 1.9x-smaller
  acceptance pin (<= 0.53x) — and analytic ``weight_bytes_by_dtype``
  matches the resident arrays byte for byte, publish payloads included.
  The lowered decode contains NO f32 aval of any full weight-tensor
  shape: dequant transients are one trailing block wide by construction
  (``weight_block_size`` keeps >= 2 blocks per leaf).
- PUBLISH: an fp-layout publish re-quantizes under ONE compiled program
  — decode-after-publish is bitwise equal to a fresh engine built from
  the published params and every jit cache size stays flat; a stale
  layout fails loudly naming the leaf.
- FLEET: ``weight_dtype`` is baked into the shared ModelPrograms like
  ``kv_dtype`` (rejected as a generation-swap override), routers refuse
  mixed-precision fleets at construction AND add_replica (the
  all-or-nothing publish contract), and ``spawn_like`` clones inherit
  the fleet's weight_dtype + kv_dtype — the cold-start bugfix pin.
- QUALITY METERS: spec acceptance under int8 weights within 0.02 of the
  snapped-fp control (the same meter kvq runs for pages; the rounding's
  own effect on this random-init model is recorded ungated by bench's
  wq_spec_accept), and the QLoRA loop — int8-snapped frozen base + fp
  LoRA (post.qlora_base, arXiv:2305.14314) — tracks the fp lora_only
  control's reward trajectory while publishing retrace-free. The int8
  random-trace re-run lives in test_serve.py (parameterized).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_guide_tpu.models import get_model
from distributed_training_guide_tpu.models.llama import LlamaConfig
from distributed_training_guide_tpu.models import llama as llama_mod
from distributed_training_guide_tpu.ops.quantized_matmul import (
    quantized_matmul, quantized_matmul_eligible, quantized_take)
from distributed_training_guide_tpu.serve.api import generate_many
from distributed_training_guide_tpu.serve.engine import ServeEngine
from distributed_training_guide_tpu.serve.scheduler import Request
from distributed_training_guide_tpu.serve.weights import (
    WEIGHT_BLOCK, is_quantizable_path, params_nbytes, store_weights,
    weight_block_size, weight_bytes_by_dtype, weight_dtype_name,
    weight_tree_bytes)
from distributed_training_guide_tpu.train.precision import (
    Quantized, dequantize_blockwise, quantize_blockwise)
from distributed_training_guide_tpu.utils import hlo as hlo_util

pytestmark = [pytest.mark.serve, pytest.mark.wquant]

# documented bound for int8-vs-fp LOGITS on N(0, 0.02) random-init params
# (block absmax/254 per weight compounds through 2 layers to <~1e-2
# observed; 5e-2 is the same ~5x margin the kv-quant grid uses)
LOGIT_ATOL = 5e-2
# int8-vs-snapped-fp: same rounded weights, fp32 compute both sides — the
# storage path may only differ in summation order (the transpose form's
# per-block accumulator)
MECHANISM_ATOL = 1e-4


@pytest.fixture(scope="module")
def llama():
    bundle = get_model("llama-debug", dtype=jnp.float32)
    return bundle, bundle.init(bundle.config, jax.random.key(0))


def _fresh(req):
    return dataclasses.replace(req, request_id=None)


def _snapped(params):
    """The same int8 grid served from fp storage (quantize -> dequantize
    of exactly the leaves store_weights selects)."""
    from distributed_training_guide_tpu.post import qlora_base

    return qlora_base(params)


# ---- policy: names, block sizes, leaf selection -----------------------------

def test_weight_dtype_name_block_size_and_leaf_selection():
    cfg = get_model("llama-debug", dtype=jnp.float32).config
    assert weight_dtype_name(cfg, None) == "fp32"     # param_dtype inherit
    assert weight_dtype_name(cfg, "float32") == "fp32"
    assert weight_dtype_name(cfg, "bfloat16") == "bf16"
    assert weight_dtype_name(cfg, "int8") == "int8"
    with pytest.raises(ValueError, match="weight_dtype"):
        weight_dtype_name(cfg, "fp8")
    # block clamp: every leaf must split into >= 2 blocks (the per-leaf
    # no-full-fp32-transient guarantee)
    assert weight_block_size(512) == WEIGHT_BLOCK
    assert weight_block_size(64) == WEIGHT_BLOCK
    assert weight_block_size(48) == 24
    assert weight_block_size(3) == 1
    assert is_quantizable_path("layers/attn/wq")
    assert is_quantizable_path("embed/embedding")
    assert is_quantizable_path("lm_head")
    assert not is_quantizable_path("layers/input_norm")
    assert not is_quantizable_path("final_norm")
    # non-llama families refuse before compile, never serve half-quantized
    with pytest.raises(ValueError, match="llama family only"):
        store_weights({"w": jnp.ones((4, 4))}, "int8", family="gpt2")
    with pytest.raises(ValueError, match="llama family only"):
        weight_tree_bytes({"w": jax.ShapeDtypeStruct((4, 4), jnp.float32)},
                          "int8", "moe")


def test_store_weights_layout_and_roundtrip_bound(llama):
    """int8 selects exactly the projection leaves; norms keep their param
    dtype; every quantized leaf's round-trip error obeys the per-block
    absmax/254 bound."""
    bundle, params = llama
    stored = store_weights(params, "int8", family="llama")
    for proj in ("wq", "wk", "wv", "wo"):
        assert isinstance(stored["layers"]["attn"][proj], Quantized)
    for proj in ("gate", "up", "down"):
        assert isinstance(stored["layers"]["mlp"][proj], Quantized)
    assert isinstance(stored["embed"]["embedding"], Quantized)
    assert isinstance(stored["lm_head"], Quantized)
    for norm in ("input_norm", "post_attn_norm"):
        leaf = stored["layers"][norm]
        assert not isinstance(leaf, Quantized)
        assert leaf.dtype == params["layers"][norm].dtype
    qt = stored["layers"]["mlp"]["gate"]           # [L, 64, 128], bs=32
    assert qt.q.dtype == jnp.int8 and qt.q.shape == (2, 64, 128)
    assert qt.scale.dtype == jnp.float32 and qt.scale.shape == (2, 64, 4)
    src = np.asarray(params["layers"]["mlp"]["gate"], np.float32)
    back = np.asarray(dequantize_blockwise(qt))
    amax = np.abs(src.reshape(2, 64, 4, 32)).max(-1, keepdims=True)
    bound = np.broadcast_to(amax / 254 + 1e-9, (2, 64, 4, 32))
    np.testing.assert_array_less(np.abs(back - src).reshape(bound.shape),
                                 bound)
    # fp32/bf16 are plain storage casts of inexact leaves
    bf = store_weights(params, "bf16", family="llama")
    assert bf["lm_head"].dtype == jnp.bfloat16


def test_weight_bytes_tables_match_resident_and_ratio_pin(llama):
    """Analytic bytes == actual resident bytes for every dtype row, and
    the int8 row clears the acceptance pin: >= 1.9x smaller than fp32
    (ratio <= 0.53), publish payloads shrinking with it."""
    bundle, params = llama
    shapes = jax.eval_shape(lambda: bundle.init(bundle.config,
                                                jax.random.key(0)))
    table = weight_bytes_by_dtype(shapes, "llama")
    assert set(table) == {"fp32", "bf16", "int8"}
    for name in ("fp32", "bf16", "int8"):
        stored = store_weights(params, name, family="llama")
        assert params_nbytes(stored) == table[name], name
    assert table["int8"] / table["fp32"] <= 0.53   # 1.9x-smaller pin
    assert table["bf16"] == table["fp32"] // 2
    # no int8 row without a leaf-selection rule for the family
    assert "int8" not in weight_bytes_by_dtype(shapes, "gpt2")
    eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=16,
                      weight_dtype="int8")
    rep = eng.weight_report()
    assert rep["weight_dtype"] == "int8"
    assert rep["weight_bytes"] == table["int8"] == eng.weight_bytes()
    assert rep["bytes_vs_fp32"] <= 0.53
    assert rep["publish_payload_bytes"] == table["int8"]
    assert rep["publish_payload_bytes_fp"] == table["fp32"]
    fp_eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=16)
    assert fp_eng.weight_report()["weight_dtype"] == "fp32"
    assert fp_eng.weight_bytes() / eng.weight_bytes() >= 1.9


# ---- quantized matmul -------------------------------------------------------

def test_quantized_matmul_standard_transpose_take_and_errors():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((5, 64)), jnp.float32)
    for k, n, bs in [(64, 64, 32), (64, 512, 32), (64, 33, 32), (7, 10, 5)]:
        w = rng.standard_normal((k, n)).astype(np.float32)
        qt = quantize_blockwise(jnp.asarray(w), block_size=bs)
        xk = jnp.asarray(rng.standard_normal((5, k)), jnp.float32)
        want = np.asarray(xk @ dequantize_blockwise(qt))
        got = np.asarray(quantized_matmul(xk, qt, impl="xla"))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # transpose form (tied lm_head): blocks tile the CONTRACTED axis,
    # scale factors out per block — parity bound is summation order only
    wt = rng.standard_normal((48, 64)).astype(np.float32)
    qtt = quantize_blockwise(jnp.asarray(wt), block_size=32)
    want = np.asarray(x @ dequantize_blockwise(qtt).T)
    got = np.asarray(quantized_matmul(x, qtt, transpose=True, impl="xla"))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=MECHANISM_ATOL)
    # leading dims flatten and restore
    x3 = x.reshape(1, 5, 64)
    qe = quantize_blockwise(jnp.asarray(
        rng.standard_normal((64, 96)).astype(np.float32)), block_size=32)
    assert quantized_matmul(x3, qe).shape == (1, 5, 96)
    # embedding gather dequantizes only the gathered rows
    table = quantize_blockwise(jnp.asarray(
        rng.standard_normal((32, 48)).astype(np.float32)), block_size=16)
    ids = jnp.asarray([[3, 31, 0]])
    np.testing.assert_allclose(
        np.asarray(quantized_take(table, ids)),
        np.asarray(dequantize_blockwise(table))[np.asarray(ids)],
        rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match="impl"):
        quantized_matmul(x, qtt, impl="cuda")
    with pytest.raises(ValueError, match="2-D"):
        quantized_matmul(x, Quantized(q=jnp.zeros((2, 4, 64), jnp.int8),
                                      scale=jnp.ones((2, 4, 2))))
    with pytest.raises(ValueError, match="contraction mismatch"):
        quantized_matmul(x, qe.__class__(q=qe.q[:32], scale=qe.scale[:32]))

    class _SqrtShim:
        def __init__(self, qt):
            self.q, self.scale, self.sqrt_domain = qt.q, qt.scale, True

    with pytest.raises(ValueError, match="sqrt_domain"):
        quantized_matmul(x, _SqrtShim(qtt))


def test_quantized_matmul_pallas_interpret_parity_and_eligibility():
    """The interpret-mode kernel reads the same int8 bytes + scale
    columns as the XLA scan — parity is kernel correctness. Eligibility
    mirrors the TPU int8 tile floor: lane-dim blocks (bs % 128) over an
    int8-tileable contraction dim (K % 32), no padded tail."""
    rng = np.random.default_rng(1)
    w = rng.standard_normal((64, 256)).astype(np.float32)
    qt = quantize_blockwise(jnp.asarray(w), block_size=128)
    assert quantized_matmul_eligible(qt)
    assert not quantized_matmul_eligible(qt, transpose=True)  # XLA carries it
    x = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
    ref = np.asarray(quantized_matmul(x, qt, impl="xla"))
    got = np.asarray(quantized_matmul(x, qt, impl="pallas", interpret=True))
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)
    # bs=32 blocks are under the 128 lane tile; K=7 breaks the int8
    # sublane; a padded tail block can't ride the BlockSpec grid
    assert not quantized_matmul_eligible(
        quantize_blockwise(jnp.asarray(w), block_size=32))
    assert not quantized_matmul_eligible(quantize_blockwise(
        jnp.asarray(rng.standard_normal((7, 256)), jnp.float32),
        block_size=128))
    with pytest.raises(NotImplementedError, match="transpose"):
        quantized_matmul(x, quantize_blockwise(jnp.asarray(w.T).astype(
            jnp.float32), block_size=32), transpose=True, impl="pallas")


# ---- forward parity grid ----------------------------------------------------

def _variant(**kw):
    base = dict(vocab_size=96, hidden_size=64, intermediate_size=96,
                num_layers=2, num_heads=4, num_kv_heads=2,
                max_position_embeddings=32, dtype=jnp.float32,
                param_dtype=jnp.float32)
    base.update(kw)
    return LlamaConfig(**base)


FORWARD_GRID = [
    ("gqa4-2", _variant()),
    ("gqa8-1", _variant(num_heads=8, num_kv_heads=1)),
    ("window", _variant(sliding_window=5)),
    ("softcap", _variant(attn_logit_softcap=20.0, final_logit_softcap=30.0,
                         query_pre_attn_scalar=16.0)),
    ("tied", _variant(tie_word_embeddings=True)),
]


@pytest.mark.parametrize("name,cfg", FORWARD_GRID, ids=[n for n, _ in
                                                        FORWARD_GRID])
def test_int8_forward_parity_grid(name, cfg):
    """Full-forward logits across the llama feature grid: int8-vs-fp
    inside the documented quantization bound, and int8-vs-snapped-fp
    inside summation-order epsilon — the storage path adds nothing
    beyond the rounding it stores."""
    params = llama_mod.init(cfg, jax.random.key(2))
    stored = store_weights(params, "int8", family="llama")
    ids = jnp.asarray([[5, 11, 3, 60, 8, 1, 44, 9]])
    fp = np.asarray(llama_mod.apply(cfg, params, ids))
    q8 = np.asarray(llama_mod.apply(cfg, stored, ids))
    snap = np.asarray(llama_mod.apply(cfg, _snapped(params), ids))
    assert float(np.max(np.abs(q8 - fp))) < LOGIT_ATOL
    assert float(np.max(np.abs(q8 - snap))) < MECHANISM_ATOL


# ---- engine-level pins ------------------------------------------------------

def test_int8_engine_batch1_spec_and_chunk_identity(llama):
    """Engine invariants WITHIN the int8-weights config: co-batched
    completions equal their batch-1 runs, spec-on == spec-off (verify
    reads the same quantized params as decode), and the chunked-prefill
    program agrees with its own batch-1 twin."""
    bundle, params = llama
    reqs = [Request(prompt_ids=[3 + i, 17, 42], max_new_tokens=8,
                    temperature=0.9 if i % 2 else 0.0, seed=i)
            for i in range(4)]
    eng = ServeEngine(bundle, params, n_slots=4, page_size=4, max_len=32,
                      weight_dtype="int8")
    res = generate_many(eng, reqs)
    ref = ServeEngine(bundle, params, n_slots=1, page_size=4, max_len=32,
                      weight_dtype="int8")
    for r, req in zip(res, reqs):
        assert r.token_ids == generate_many(ref, [_fresh(req)])[0].token_ids
    assert eng.weight_dtype == "int8"
    # spec-on == spec-off under quantized weights
    block = [7, 11, 13, 17, 19, 23, 29, 31]
    sreqs = [Request(prompt_ids=(block * 6)[:48] + [40 + i],
                     max_new_tokens=24, seed=i) for i in range(3)]

    def run(speculate):
        e = ServeEngine(bundle, params, n_slots=3, page_size=8, max_len=128,
                        weight_dtype="int8", speculate=speculate, spec_k=6)
        return [r.token_ids
                for r in generate_many(e, [_fresh(r) for r in sreqs])]

    assert run("ngram") == run(None), "spec-on != spec-off under int8"
    # chunked prefill, program-relative identity (same config both sides)
    creqs = [Request(prompt_ids=[3 + (j % 40) for j in range(12)],
                     max_new_tokens=6, seed=9)]
    chunk = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=32,
                        prefill_chunk=4, weight_dtype="int8")
    cref = ServeEngine(bundle, params, n_slots=1, page_size=4, max_len=32,
                       prefill_chunk=4, prefix_cache=False,
                       weight_dtype="int8")
    assert ([r.token_ids for r in generate_many(chunk, creqs)]
            == [r.token_ids
                for r in generate_many(cref, [_fresh(creqs[0])])])


def test_int8_spec_acceptance_meter_vs_snapped_fp(llama):
    """THE quality meter: acceptance on
    the lookup-friendly workload under int8 weights within 0.02 of the
    snapped-fp control — same rounded policy, fp storage — so the gated
    variable is the storage + in-kernel-dequant path, not the rounding
    (whose effect on this random-init model bench records ungated)."""
    bundle, params = llama
    block = [7, 11, 13, 17, 19, 23, 29, 31]
    prompt = (block * 6)[:48]
    reqs = [Request(prompt_ids=prompt + [40 + i], max_new_tokens=48,
                    seed=i) for i in range(4)]

    def run(p, weight_dtype):
        eng = ServeEngine(bundle, p, n_slots=4, page_size=8, max_len=128,
                          weight_dtype=weight_dtype, speculate="ngram",
                          spec_k=6)
        generate_many(eng, [_fresh(r) for r in reqs])
        return eng.stats()["spec_acceptance_rate"]

    acc8 = run(params, "int8")
    acc_snap = run(_snapped(params), None)
    assert acc8 > 0.0
    assert abs(acc8 - acc_snap) <= 0.02, \
        f"int8 weight storage moved spec acceptance by " \
        f"{acc8 - acc_snap:+.3f} vs the snapped-fp control"


def test_int8_decode_hlo_no_fp32_weight_avals(llama):
    """The lowered decode never materializes a full fp32 weight tensor:
    no f32 aval of any stacked projection / embed / lm_head shape (the
    dequant transient is one trailing block wide), with the int8
    payloads present as s8/i8 avals."""
    bundle, params = llama
    cfg = bundle.config
    eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=16,
                      weight_dtype="int8")
    arr = eng.scheduler.decode_arrays()
    text = eng._decode_fn.lower(
        eng.params, eng.pages,
        jnp.asarray(arr["tokens"]), jnp.asarray(arr["lengths"]),
        jnp.asarray(arr["tables"]), jnp.asarray(arr["seeds"]),
        jnp.asarray(arr["temps"]), jnp.asarray(arr["top_ks"]),
        jnp.asarray(arr["top_ps"]), jnp.asarray(arr["actives"])).as_text()
    e, f, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    hq = cfg.num_heads * cfg.head_size
    hkv = cfg.num_kv_heads * cfg.head_size
    l = cfg.num_layers
    full_weight_shapes = [
        (l, e, hq), (l, e, hkv), (l, hq, e),     # wq / wk|wv / wo stacks
        (l, e, f), (l, f, e),                    # gate|up / down stacks
        (v, e), (e, v),                          # embed / lm_head
    ]
    for shape in full_weight_shapes:
        assert not hlo_util.has_aval(text, "f32", shape), \
            f"full fp32 weight aval {shape} in the int8 decode"
    assert (hlo_util.has_aval(text, "i8", (l, e, hq))
            or hlo_util.has_aval(text, "s8", (l, e, hq))), \
        "int8 weight payload aval missing from the lowered decode"
    assert isinstance(eng.params["lm_head"], Quantized)


def test_publish_fp_requant_bitwise_vs_fresh_and_cache_flat(llama):
    """The trainer->engine seam under quantized storage: an fp-layout
    publish re-quantizes through one compiled program — decode after the
    publish is bitwise a fresh int8 engine built from the published
    params, jit caches stay flat, and a stale layout fails loudly."""
    bundle, params = llama
    p1 = bundle.init(bundle.config, jax.random.key(7))
    reqs = [Request(prompt_ids=[3 + i, 17, 42], max_new_tokens=8, seed=i)
            for i in range(3)]
    eng = ServeEngine(bundle, params, n_slots=3, page_size=4, max_len=32,
                      weight_dtype="int8")
    generate_many(eng, [_fresh(r) for r in reqs])          # warm everything
    sizes0 = eng.programs.jit_cache_sizes()
    count0 = eng.programs.publish_count
    assert eng.publish_params(p1) == count0 + 1            # fp layout
    assert eng.programs.jit_cache_sizes() == sizes0, \
        "fp publish retraced a serving program"
    got = [r.token_ids for r in generate_many(eng, [_fresh(r)
                                                    for r in reqs])]
    fresh = ServeEngine(bundle, p1, n_slots=3, page_size=4, max_len=32,
                        weight_dtype="int8")
    want = [r.token_ids for r in generate_many(fresh, [_fresh(r)
                                                       for r in reqs])]
    assert got == want, "publish->decode != fresh engine on the params"
    assert eng.programs.jit_cache_sizes() == sizes0
    # second fp publish reuses the same requant program
    eng.publish_params(params)
    assert eng.programs.jit_cache_sizes() == sizes0
    # the compiled (quantized) layout publishes through the classic path
    eng.publish_params(store_weights(p1, "int8", family="llama"))
    # a stale fp layout fails loudly, naming the leaf
    bad = jax.tree.map(lambda x: x, p1)
    bad["lm_head"] = bad["lm_head"][:, :-1]
    with pytest.raises(ValueError, match="fp publish layout expects"):
        eng.publish_params(bad)
    wrong_dtype = jax.tree.map(lambda x: x.astype(jnp.bfloat16), p1)
    with pytest.raises(ValueError, match="fp publish layout expects"):
        eng.publish_params(wrong_dtype)


def test_weight_dtype_baked_router_agreement_and_spawn_inherits(llama):
    """weight_dtype rides the shared ModelPrograms exactly like kv_dtype:
    a generation swap cannot override it, a router refuses a
    mixed-precision fleet (construction and add_replica), and spawn_like
    cold-start clones inherit the fleet's weight_dtype AND kv_dtype —
    the bugfix pin for control-plane scale-ups."""
    from distributed_training_guide_tpu.serve.elastic import (new_generation,
                                                              spawn_like)
    from distributed_training_guide_tpu.serve.router import (Replica, Router,
                                                             local_fleet)

    bundle, params = llama
    eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=16,
                      weight_dtype="int8")
    with pytest.raises(ValueError, match="baked"):
        new_generation(eng, weight_dtype="bf16")
    with pytest.raises(ValueError, match="baked"):
        new_generation(eng, weight_dtype=None)
    fp_eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=16)
    with pytest.raises(ValueError, match="disagree on weight_dtype"):
        Router([Replica("a", eng), Replica("b", fp_eng)])
    router = local_fleet(bundle, params, n_replicas=2, n_slots=2,
                         page_size=4, max_len=16, weight_dtype="int8")
    assert router.weight_dtype == "int8"
    with pytest.raises(ValueError, match="weight_dtype"):
        router.add_replica(Replica("odd-one", fp_eng))
    # the spawn-inherits-config pin: the clone shares the fleet's
    # programs, so both storage dtypes carry over without restating them
    spawned = spawn_like(router, name="r9")
    assert spawned.engine.weight_dtype == "int8"
    assert spawned.engine.kv_dtype == router.kv_dtype
    assert spawned.engine.programs is \
        next(iter(router.replicas.values())).engine.programs
    router.add_replica(spawned)                    # and it is routable
    assert "r9" in router.replicas


def test_qlora_base_idempotent_and_loop_tracks_fp_control(llama):
    """QLoRA (arXiv:2305.14314): (a) qlora_base snaps the base onto the
    SAME int8 grid the engine stores — requantizing the snapped base
    reproduces payload and scales bitwise, so adapters train against the
    policy actually served; (b) the lora_only loop over an int8-weights
    engine publishes retrace-free and its reward trajectory stays within
    the documented noise floor of the fp lora_only control (0.1 at this
    rollout count — the band-reward std over 12x8 sampled tokens)."""
    from distributed_training_guide_tpu.models.lora import lora_bundle
    from distributed_training_guide_tpu.post import (PostTrainingLoop,
                                                     ProgrammaticScorer,
                                                     band_reward,
                                                     merged_params,
                                                     qlora_base)
    from distributed_training_guide_tpu.train.optimizer import adamw_cosine
    from distributed_training_guide_tpu.train.step import Trainer

    bundle, params = llama
    snapped = qlora_base(params)
    s1 = store_weights(params, "int8", family="llama")
    s2 = store_weights(snapped, "int8", family="llama")
    for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    norm = snapped["final_norm"]
    np.testing.assert_array_equal(np.asarray(norm),       # passthrough
                                  np.asarray(params["final_norm"]))

    def arm(quantized):
        wrapped = lora_bundle(bundle, rank=4, alpha=8.0)
        init = wrapped.init(wrapped.config, jax.random.key(0))
        if quantized:
            init = {"base": qlora_base(init["base"]), "lora": init["lora"]}
        trainer = Trainer(bundle=wrapped, optimizer=adamw_cosine(0.1),
                          lora_only=True, guard_policy="skip")
        state = trainer.init_state_from_params(init)
        engine = ServeEngine(bundle, merged_params(trainer, state),
                             n_slots=4, page_size=16, max_len=32,
                             weight_dtype="int8" if quantized else None)
        loop = PostTrainingLoop(
            trainer, engine, ProgrammaticScorer(band_reward(64)),
            [[3, 10, 17]] * 12, state=state, max_new_tokens=8,
            temperature=1.0, base_seed=0)
        loop.run(1)                          # iteration 0 pays the compiles
        sizes0 = engine.programs.jit_cache_sizes()
        hist = loop.history + loop.run(2)
        assert engine.programs.jit_cache_sizes() == sizes0, \
            "a QLoRA publish retraced a serving program"
        assert loop.publishes == 3
        return [m["reward_mean"] for m in hist]

    qlora_traj = arm(quantized=True)
    fp_traj = arm(quantized=False)
    assert all(np.isfinite(qlora_traj))
    gap = max(abs(a - b) for a, b in zip(qlora_traj, fp_traj))
    assert gap <= 0.1, \
        f"QLoRA reward trajectory drifted {gap:.3f} from the fp control " \
        f"(trajectories {qlora_traj} vs {fp_traj})"


@pytest.mark.slow
def test_int8_weights_sharded_tp2(llama, eight_devices):
    """tp=2 over quantized params: the int8 payload inherits its leaf's
    sharding, scales shard their trailing block axis only when every
    shard holds whole blocks — and the sharded engine stays
    token-identical to the replicated int8 engine."""
    from distributed_training_guide_tpu.parallel import make_mesh, make_plan

    bundle, params = llama
    plan = make_plan("tp", make_mesh(tp=2, devices=eight_devices[:2]))
    reqs = [Request(prompt_ids=[3 + i, 17, 42], max_new_tokens=6, seed=i)
            for i in range(3)]
    eng = ServeEngine(bundle, params, n_slots=3, page_size=4, max_len=32,
                      plan=plan, weight_dtype="int8")
    res = generate_many(eng, [_fresh(r) for r in reqs])
    repl = ServeEngine(bundle, params, n_slots=3, page_size=4, max_len=32,
                      weight_dtype="int8")
    ref = generate_many(repl, [_fresh(r) for r in reqs])
    assert [r.token_ids for r in res] == [r.token_ids for r in ref]
    sharded = [leaf for leaf in jax.tree.leaves(
        eng.params, is_leaf=lambda x: isinstance(x, Quantized))
        if isinstance(leaf, Quantized)
        and leaf.q.addressable_shards[0].data.shape != leaf.q.shape]
    assert sharded, "tp plan left every quantized payload replicated"
    for leaf in sharded:
        qshard = leaf.q.addressable_shards[0].data.shape
        sshard = leaf.scale.addressable_shards[0].data.shape
        d, nb = leaf.q.shape[-1], leaf.scale.shape[-1]
        bs = -(-d // nb)
        if qshard[-1] != leaf.q.shape[-1]:     # trailing-sharded payload
            assert qshard[-1] % bs == 0, \
                "a shard split a quantization block"
            assert sshard[-1] == nb // (d // qshard[-1])
