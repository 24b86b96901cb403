"""Quantized KV pages (serve/kv_pages.py ``kv_dtype="int8"``): int8
payloads with per-(position, kv-head) absmax scales as first-class pool
state.

What is pinned here, and why these meters:

- ATTEND PARITY with documented error bounds: int8-vs-fp32 attention over
  the same context, across the serving feature grid (GQA, windows,
  softcap, shuffled physical layouts). The per-element quantization error
  is <= scale/2 = absmax/254 (~0.4% of each vector's absmax); for
  standard-normal k/v the observed attend error is <~1e-2 absolute — the
  grid asserts 5e-2, a ~5x margin. The INT8 flash kernel (in-tile
  dequant) must match the int8 gather path to 1e-5 — those two read the
  SAME quantized bytes, so their difference is pure kernel error, not
  quantization.
- SCALE LIFECYCLE: scales ride page identity — CoW forks copy them,
  commits/scatters write them beside their payload, the sharded pool
  splits them on the kv-head axis. A dst page with stale scales would
  dequantize garbage, which is why the fork pin checks BOTH leaves.
- BYTE + HLO PINS: the int8 pool (scales included) is <= 0.55x the fp32
  pool (0.3125x at head_dim 16: 1 payload byte + 4/16 scale bytes per
  element vs 4); the lowered decode's pool avals are int8 in AND out
  with no fp32 pool-shaped tensor anywhere in the program.
- QUALITY METER: spec-decoding acceptance is a sensitive function of KV
  fidelity (a perturbed verify logit breaks a drafted run immediately,
  long before evals would move). Acceptance under the int8 pool must be
  within 0.02 of the fp32-KV control on the lookup-friendly workload.
- ENGINE INVARIANTS carry over because quantization is pure per token
  (one absmax scale per written vector — never a function of co-resident
  page content): batch-1 identity, spec-on == spec-off, preemption
  replay, the disaggregated handoff, and the tp=2 sharded pool are all
  re-pinned under int8. The int8 random-trace re-run lives in
  test_serve.py (parameterized).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_guide_tpu.models import get_model
from distributed_training_guide_tpu.ops.paged_decode import (
    paged_decode_eligible, paged_flash_decode)
from distributed_training_guide_tpu.serve.api import generate_many
from distributed_training_guide_tpu.serve.engine import ServeEngine
from distributed_training_guide_tpu.serve.kv_pages import (
    copy_pages, dequantize_kv, init_pages, kv_dtype_name,
    kv_page_bytes, paged_attend, quantize_kv)
from distributed_training_guide_tpu.serve.scheduler import Request
from distributed_training_guide_tpu.train.precision import Quantized
from distributed_training_guide_tpu.utils import hlo as hlo_util
from tests.test_paged_decode import stacked_pool

pytestmark = [pytest.mark.serve, pytest.mark.kvquant]

ATTEND_ATOL = 5e-2   # documented bound for N(0,1) k/v — see module docstring


@pytest.fixture(scope="module")
def llama():
    bundle = get_model("llama-debug", dtype=jnp.float32)
    return bundle, bundle.init(bundle.config, jax.random.key(0))


def _fresh(req):
    return dataclasses.replace(req, request_id=None)


# ---- quantization grain ----------------------------------------------------

def test_quantize_kv_roundtrip_bound_and_shapes():
    """One fp32 scale per (position, kv-head) vector; round-trip error is
    bounded by scale/2 per element, scale = that vector's absmax/127."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((3, 5, 2, 16)).astype(np.float32)
    x[0, 0, 0] *= 100.0          # an outlier vector costs only ITS block
    qt = quantize_kv(jnp.asarray(x))
    assert qt.q.shape == x.shape and qt.q.dtype == jnp.int8
    assert qt.scale.shape == x.shape[:-1] + (1,)
    back = np.asarray(dequantize_kv(qt))
    amax = np.abs(x).max(axis=-1, keepdims=True)
    np.testing.assert_array_less(
        np.abs(back - x), np.broadcast_to(amax / 254 + 1e-7, x.shape))


def test_quantize_kv_is_pure_per_token():
    """The bitwise-replay foundation: a vector's quantization never
    depends on what else is in the page — re-quantizing the same value
    yields the same bytes whatever wrote it first."""
    rng = np.random.default_rng(1)
    x = rng.standard_normal((4, 2, 16)).astype(np.float32)
    a = quantize_kv(jnp.asarray(x))
    b = quantize_kv(jnp.asarray(x[1:2]))
    np.testing.assert_array_equal(np.asarray(a.q[1:2]), np.asarray(b.q))
    np.testing.assert_array_equal(np.asarray(a.scale[1:2]),
                                  np.asarray(b.scale))


def test_kv_page_bytes_int8_includes_scales():
    cfg = get_model("llama-debug", dtype=jnp.float32).config
    fp32 = kv_page_bytes(cfg, page_size=16)
    int8 = kv_page_bytes(cfg, page_size=16, kv_dtype="int8")
    # per (position, head): head_size payload bytes + 4 scale bytes
    expect = (cfg.num_layers * 2 * 16 * cfg.num_kv_heads
              * (cfg.head_size + 4))
    assert int8 == expect
    assert int8 / fp32 <= 0.55            # the acceptance-criteria pin
    assert kv_dtype_name(cfg, None) == "fp32"
    with pytest.raises(ValueError, match="kv_dtype"):
        kv_dtype_name(cfg, "fp8")


def test_int8_pool_real_nbytes_vs_fp32():
    """The device arrays themselves (payload + scales summed) obey the
    same <= 0.55x pin as the formula — the formula can't silently drift
    from what is actually resident."""
    cfg = get_model("llama-debug", dtype=jnp.float32).config
    p8 = init_pages(cfg, 6, 8, kv_dtype="int8")
    p32 = init_pages(cfg, 6, 8)
    nb8 = sum(x.nbytes for x in jax.tree.leaves(p8))
    nb32 = sum(x.nbytes for x in jax.tree.leaves(p32))
    assert nb8 / nb32 <= 0.55
    assert nb8 == kv_page_bytes(cfg, page_size=8, n_pages=6,
                                kv_dtype="int8")
    assert isinstance(p8["k"], Quantized)
    assert p8["k"].q.dtype == jnp.int8
    assert p8["k"].scale.dtype == jnp.float32


# ---- attend parity grid ----------------------------------------------------

def _paged_state(rng, *, s, m, page, n_pages, hkv, d, lengths):
    """Shuffled physical layout with a filled history, fp32 + int8 twins."""
    phys = rng.permutation(np.arange(1, n_pages))
    tables = np.zeros((s, m), np.int32)
    for i in range(s):
        tables[i] = phys[i * m:(i + 1) * m]
    kp = np.zeros((n_pages, page, hkv, d), np.float32)
    vp = np.zeros((n_pages, page, hkv, d), np.float32)
    ctx = rng.standard_normal((s, m * page, hkv, d)).astype(np.float32)
    vctx = rng.standard_normal((s, m * page, hkv, d)).astype(np.float32)
    for i in range(s):
        for t in range(int(lengths[i])):
            kp[tables[i, t // page], t % page] = ctx[i, t]
            vp[tables[i, t // page], t % page] = vctx[i, t]
    return tables, kp, vp


LAYER = 1      # of the three-layer pools ``stacked_pool`` makes


GRID = [
    dict(),                                    # plain causal
    dict(window=5),                            # SWA across pages
    dict(softcap=20.0),                        # Gemma-2 softcap
    dict(window=8, scale=0.25, softcap=50.0),  # full Gemma-2 decode
]


@pytest.mark.parametrize("hq,hkv", [(4, 2), (8, 1)])
@pytest.mark.parametrize("kw", GRID, ids=lambda kw: "-".join(kw) or "causal")
def test_int8_attend_parity_vs_fp32(hq, hkv, kw):
    """int8 gather attend vs the fp32 gather attend over the same context
    stays inside the documented quantization bound across the feature
    grid and shuffled layouts; the scatter writes quantized bytes +
    scales at the same (page, offset) the fp32 path writes."""
    rng = np.random.default_rng(3)
    s, m, page, n_pages, d = 3, 4, 4, 16, 16
    lengths = np.array([5, 0, 11], np.int32)
    tables, kp, vp = _paged_state(rng, s=s, m=m, page=page, n_pages=n_pages,
                                  hkv=hkv, d=d, lengths=lengths)
    q = rng.standard_normal((s, 1, hq, d)).astype(np.float32)
    k_new = rng.standard_normal((s, 1, hkv, d)).astype(np.float32)
    v_new = rng.standard_normal((s, 1, hkv, d)).astype(np.float32)
    out32, _ = paged_attend(
        jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
        stacked_pool(kp, LAYER), stacked_pool(vp, LAYER), LAYER,
        jnp.asarray(tables), jnp.asarray(lengths), **kw)
    out8, (nkp, nvp) = paged_attend(
        jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
        quantize_kv(stacked_pool(kp, LAYER)),
        quantize_kv(stacked_pool(vp, LAYER)), LAYER,
        jnp.asarray(tables), jnp.asarray(lengths), **kw)
    assert float(jnp.max(jnp.abs(out32 - out8))) < ATTEND_ATOL
    # the new token's quantized write landed beside its scale
    i, n = 0, int(lengths[0])
    want = quantize_kv(jnp.asarray(k_new))[0][i, 0]
    np.testing.assert_array_equal(
        np.asarray(nkp.q[LAYER, tables[i, n // page], n % page]),
        np.asarray(want))


def test_int8_flash_kernel_matches_int8_gather():
    """The in-kernel dequant reads the SAME quantized bytes as the gather
    dequant — parity at 1e-5 is kernel correctness, quantization error
    cancels. Grid includes window/scale/softcap and zero-length slots."""
    rng = np.random.default_rng(4)
    s, m, page, n_pages, hq, hkv, d = 4, 4, 4, 20, 4, 2, 16
    lengths = np.array([4, 0, 9, 15], np.int32)
    tables, kp, vp = _paged_state(rng, s=s, m=m, page=page, n_pages=n_pages,
                                  hkv=hkv, d=d, lengths=lengths)
    kq, vq = (quantize_kv(stacked_pool(x, LAYER)) for x in (kp, vp))
    q = rng.standard_normal((s, 1, hq, d)).astype(np.float32)
    k_new = rng.standard_normal((s, 1, hkv, d)).astype(np.float32)
    v_new = rng.standard_normal((s, 1, hkv, d)).astype(np.float32)
    for kw in (dict(), dict(window=6, scale=0.3, softcap=30.0)):
        outs = {}
        for impl in ("flash", "xla"):
            attn, (nkp, nvp) = paged_attend(
                jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
                kq, vq, LAYER, jnp.asarray(tables), jnp.asarray(lengths),
                impl=impl, **kw)
            outs[impl] = (np.asarray(attn), np.asarray(nkp.q),
                          np.asarray(nkp.scale))
        np.testing.assert_allclose(outs["flash"][0], outs["xla"][0],
                                   rtol=1e-5, atol=1e-5)
        # the quantized scatter is shared: payload AND scales bitwise
        np.testing.assert_array_equal(outs["flash"][1], outs["xla"][1])
        np.testing.assert_array_equal(outs["flash"][2], outs["xla"][2])
        # and against the fp32 XLA reference the int8 KERNEL stays inside
        # the documented quantization bound (the acceptance-criteria pin)
        ref32, _ = paged_attend(
            jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
            stacked_pool(kp, LAYER), stacked_pool(vp, LAYER), LAYER,
            jnp.asarray(tables), jnp.asarray(lengths), impl="xla", **kw)
        assert float(np.max(np.abs(outs["flash"][0]
                                   - np.asarray(ref32)))) < ATTEND_ATOL


@pytest.mark.parametrize("impl,head_dim,page,backend,want", [
    ("auto", 128, 16, "tpu", "flash"),
    ("auto", 64, 16, "tpu", "xla"),       # head_dim fails the kernel's gate
    ("auto", 128, 16, "cpu", "xla"),      # off-TPU the gather path is faster
    ("flash", 16, 16, "cpu", "flash"),    # interpreted: any shape
    ("xla", 128, 16, "tpu", "xla"),
    ("flash", 64, 16, "tpu", ValueError),  # forced + ineligible: loud
    ("pallas", 128, 16, "tpu", ValueError),
])
def test_resolve_attend_impl(monkeypatch, impl, head_dim, page, backend, want):
    """The one place the paged attend family is chosen: 'auto' says which
    path it took and why, and a forced 'flash' the compiled kernel cannot
    take raises at engine construction instead of quietly gathering."""
    from distributed_training_guide_tpu.serve import kv_pages

    monkeypatch.setattr(kv_pages.jax, "default_backend", lambda: backend)
    if want is ValueError:
        with pytest.raises(ValueError, match="attend_impl"):
            kv_pages.resolve_attend_impl(impl, head_dim, page)
        return
    got, reason = kv_pages.resolve_attend_impl(impl, head_dim, page)
    assert got == want and reason
    assert reason.startswith("auto" if impl == "auto" else "forced")


def test_int8_engine_builds_on_debug_geometry(llama):
    """llama-debug's head_dim 16 never had the compiled kernel, so its int8
    engines build under 'auto' and under a forced 'flash' (interpreted
    off-TPU)."""
    bundle, params = llama
    for impl in ("auto", "flash"):
        ServeEngine(bundle, params, n_slots=1, page_size=16, max_len=64,
                    kv_dtype="int8", attend_impl=impl)


def test_paged_flash_decode_scale_validation_and_eligibility():
    kq = jnp.zeros((2, 4, 4, 2, 16), jnp.int8)
    with pytest.raises(ValueError, match="half-quantized"):
        paged_flash_decode(jnp.zeros((1, 4, 16)), kq, kq, 1,
                           jnp.zeros((1, 2), jnp.int32),
                           jnp.zeros(1, jnp.int32),
                           k_scale=jnp.zeros((2, 4, 4, 2)), interpret=True)
    # one gate for float and int8 pools: the page axis is a whole-dimension
    # block, which the chip's compiler tiles for either payload
    # (tests/test_chip_compile.py compiles int8 at page 16)
    assert paged_decode_eligible(128, 16)
    assert not paged_decode_eligible(64, 16)


# ---- scale lifecycle -------------------------------------------------------

def test_chunk_scatter_int8_writes_scales_and_respects_start():
    """The one write site (``_scatter_new``, here as a prefill chunk that
    starts past a shared prefix): quantized payload + scales land at the
    same (page, offset); the shared prefix is never rewritten and the pad
    tail routes to the trash page, for BOTH leaves."""
    from tests.test_kv_pages import _scatter_every_layer

    layers, page, n_pages, h, d = 2, 4, 8, 2, 16
    rng = np.random.default_rng(5)
    pool = init_pages(
        type("C", (), {"num_layers": layers, "num_heads": h,
                       "head_size": d, "dtype": jnp.float32})(),
        n_pages, page, kv_dtype="int8")
    k_pages, v_pages = pool["k"], pool["v"]
    marker_q = k_pages.q.at[:, 5].set(7)
    marker_s = k_pages.scale.at[:, 5].set(3.0)
    k_pages = Quantized(marker_q, marker_s)
    k_dense = rng.standard_normal((layers, 8, h, d)).astype(np.float32)
    v_dense = rng.standard_normal((layers, 8, h, d)).astype(np.float32)
    table_row = jnp.asarray([5, 3, 0, 0], jnp.int32)
    # a 6-token prompt whose first 4 positions are shared: the chunk holds
    # positions 4..7, of which 2 are real
    nkp, nvp = _scatter_every_layer(k_pages, v_pages, k_dense[:, 4:],
                                    v_dense[:, 4:], table_row, start=4,
                                    n_valid=2)
    want = quantize_kv(jnp.asarray(k_dense))
    # the shared page (positions < start) is untouched in BOTH leaves
    np.testing.assert_array_equal(np.asarray(nkp.q[:, 5]),
                                  np.full((layers, page, h, d), 7, np.int8))
    np.testing.assert_array_equal(np.asarray(nkp.scale[:, 5]),
                                  np.full((layers, page, h, 1), 3.0))
    for t in (4, 5):                                   # committed tokens
        np.testing.assert_array_equal(
            np.asarray(nkp.q[:, 3, t % page]), np.asarray(want.q[:, t]))
        np.testing.assert_array_equal(
            np.asarray(nkp.scale[:, 3, t % page]),
            np.asarray(want.scale[:, t]))
    # the pad rows' logical places (positions 6, 7) keep the pool's bytes
    for got, was in ((nkp, k_pages), (nvp, v_pages)):
        np.testing.assert_array_equal(np.asarray(got.q[:, 3, 2:]),
                                      np.asarray(was.q[:, 3, 2:]))
        np.testing.assert_array_equal(np.asarray(got.scale[:, 3, 2:]),
                                      np.asarray(was.scale[:, 3, 2:]))


def test_cow_fork_copies_scales():
    """The CoW pin: copy_pages on a quantized pool duplicates payload AND
    scale rows — a forked page that kept the old scales would dequantize
    garbage the moment the fork diverges."""
    rng = np.random.default_rng(6)
    pool = Quantized(
        q=jnp.asarray(rng.integers(-127, 127, (2, 6, 4, 2, 16)), jnp.int8),
        scale=jnp.asarray(rng.uniform(0.01, 2.0, (2, 6, 4, 2, 1)),
                          jnp.float32))
    vpool = Quantized(q=pool.q + 1, scale=pool.scale * 2)
    nkp, nvp = jax.jit(copy_pages)((pool, vpool), jnp.asarray(3),
                                   jnp.asarray(5))
    for got, src in ((nkp, pool), (nvp, vpool)):
        np.testing.assert_array_equal(np.asarray(got.q[:, 5]),
                                      np.asarray(src.q[:, 3]))
        np.testing.assert_array_equal(np.asarray(got.scale[:, 5]),
                                      np.asarray(src.scale[:, 3]))
        others = [0, 1, 2, 4]
        np.testing.assert_array_equal(np.asarray(got.q[:, others]),
                                      np.asarray(src.q[:, others]))


# ---- engine-level pins -----------------------------------------------------

def test_int8_engine_batch1_identity_and_stats(llama):
    """Scheduling invariance carries into the quantized world: co-batched
    int8 completions equal their int8 batch-1 runs token for token, and
    the byte lever is visible on stats()/kv_report."""
    bundle, params = llama
    reqs = [Request(prompt_ids=[3 + i, 17, 42], max_new_tokens=8,
                    temperature=0.9 if i % 2 else 0.0, seed=i)
            for i in range(4)]
    eng = ServeEngine(bundle, params, n_slots=4, page_size=4, max_len=32,
                      kv_dtype="int8")
    res = generate_many(eng, reqs)
    ref = ServeEngine(bundle, params, n_slots=1, page_size=4, max_len=32,
                      kv_dtype="int8")
    for r, req in zip(res, reqs):
        assert r.token_ids == generate_many(ref, [_fresh(req)])[0].token_ids
    st = eng.stats()
    assert st["pool_dtype"] == "int8"
    assert st["bytes_per_page"] == kv_page_bytes(bundle.config, page_size=4,
                                                 kv_dtype="int8")
    rep = eng.kv_report()
    assert rep["pool_dtype"] == "int8"
    assert rep["bytes_vs_fp32"] <= 0.55
    assert rep["pool_bytes"] == eng.kv_cache_bytes()
    fp32_eng = ServeEngine(bundle, params, n_slots=4, page_size=4,
                           max_len=32)
    assert eng.kv_cache_bytes() / fp32_eng.kv_cache_bytes() <= 0.55


def test_int8_decode_hlo_pool_avals_are_int8(llama):
    """The lowered decode's only pool-shaped tensors are int8: payload in
    and out as s8, scales as small f32 rows, and NO fp32 tensor of the
    pool's 5-d shape anywhere — the program never materializes a
    dequantized pool (the gather transient is [S, M*page, ...], a
    different shape by construction)."""
    bundle, params = llama
    cfg = bundle.config
    eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=16,
                      kv_dtype="int8")
    arr = eng.scheduler.decode_arrays()
    lowered = eng._decode_fn.lower(
        eng.params, eng.pages,
        jnp.asarray(arr["tokens"]), jnp.asarray(arr["lengths"]),
        jnp.asarray(arr["tables"]), jnp.asarray(arr["seeds"]),
        jnp.asarray(arr["temps"]), jnp.asarray(arr["top_ks"]),
        jnp.asarray(arr["top_ps"]), jnp.asarray(arr["actives"]))
    text = lowered.as_text()
    pool_shape = (cfg.num_layers, eng.scheduler.pool.n_pages, 4,
                  cfg.num_kv_heads, cfg.head_size)
    assert (hlo_util.has_aval(text, "i8", pool_shape)      # StableHLO
            or hlo_util.has_aval(text, "s8", pool_shape)), \
        "int8 pool aval missing from the lowered decode"
    assert not hlo_util.has_aval(text, "f32", pool_shape), \
        "a full fp32 pool-shaped tensor appears in the int8 decode"
    # and the engine's resident pages really are int8 + f32 scales
    assert eng.pages["k"].q.dtype == jnp.int8
    assert eng.pages["k"].scale.shape == pool_shape[:-1] + (1,)


def test_int8_spec_identity_and_acceptance_meter(llama):
    """(a) spec-on == spec-off under the int8 pool (the verify forward
    reads the same quantized pages as plain decode, and quantize-at-write
    is deterministic per token); (b) THE quality meter: acceptance on the
    lookup-friendly workload within 0.02 of the fp32-KV control."""
    bundle, params = llama
    block = [7, 11, 13, 17, 19, 23, 29, 31]
    prompt = (block * 6)[:48]
    reqs = [Request(prompt_ids=prompt + [40 + i], max_new_tokens=48,
                    seed=i) for i in range(4)]

    def run(kv_dtype, speculate):
        eng = ServeEngine(bundle, params, n_slots=4, page_size=8,
                          max_len=128, kv_dtype=kv_dtype,
                          speculate=speculate, spec_k=6)
        res = generate_many(eng, [_fresh(r) for r in reqs])
        # .get: the key is OMITTED when nothing was drafted (spec off)
        return [r.token_ids for r in res], \
            eng.stats().get("spec_acceptance_rate", 0.0)

    toks_on, acc8 = run("int8", "ngram")
    toks_off, _ = run("int8", None)
    assert toks_on == toks_off, "spec-on != spec-off under int8 KV"
    _, acc32 = run(None, "ngram")
    assert acc8 > 0.0
    assert abs(acc8 - acc32) <= 0.02, \
        f"int8 KV moved spec acceptance by {acc8 - acc32:+.3f}"


def test_int8_prefix_share_and_preemption_pressure(llama):
    """CoW + prefix sharing + preemption-by-recompute on a TIGHT int8
    pool: completions stay token-identical to batch-1 (the replay rewrite
    re-quantizes the same values to the same bytes)."""
    bundle, params = llama
    prefix = [9, 9, 9, 9, 5, 6, 7, 8]
    reqs = [Request(prompt_ids=prefix + [20 + i], max_new_tokens=6, seed=i)
            for i in range(4)]
    eng = ServeEngine(bundle, params, n_slots=3, page_size=4, max_len=24,
                      n_pages=12, prefill_chunk=4, kv_dtype="int8")
    res = generate_many(eng, reqs)
    assert eng.scheduler.stats["prefix_hits"] > 0
    # same CHUNK SIZE as the engine under test: under int8 two chunkings
    # of one prompt write measurably different caches (a chunk attends
    # over already-quantized history), so identity is program-relative —
    # see serve/kv_pages.py docstring
    ref = ServeEngine(bundle, params, n_slots=1, page_size=4, max_len=24,
                      prefill_chunk=4, prefix_cache=False, kv_dtype="int8")
    for r, req in zip(res, reqs):
        assert r.token_ids == generate_many(ref, [_fresh(req)])[0].token_ids


def test_int8_disagg_handoff_moves_scales_for_free(llama):
    """The disaggregated pair over one int8 pool: page-id handoff moves
    payload AND scales by refcount (bytes_copied stays 0), and the pair
    equals the int8 monolith token for token."""
    from distributed_training_guide_tpu.serve.disagg import DisaggEngine

    bundle, params = llama
    reqs = [Request(prompt_ids=[3 + i, 17, 42], max_new_tokens=6, seed=i)
            for i in range(3)]
    pair = DisaggEngine(bundle, params, n_slots=2, n_prefill_slots=1,
                        page_size=4, max_len=32, kv_dtype="int8")
    res = generate_many(pair, [_fresh(r) for r in reqs])
    mono = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=32,
                       kv_dtype="int8")
    ref = generate_many(mono, [_fresh(r) for r in reqs])
    assert [r.token_ids for r in res] == [r.token_ids for r in ref]
    st = pair.stats()
    assert st["handoff_transfers"] > 0 and st["handoff_bytes_copied"] == 0
    assert st["pool_dtype"] == "int8"


def test_int8_sharded_pool_tp2(llama, eight_devices):
    """kv-head-sharded int8 pool (tp=2): token-identical to the
    replicated int8 engine, with each chip holding kvh/2 heads of payload
    AND scales — the per-(position, head) scale grain is what keeps the
    manual region collective-free."""
    from distributed_training_guide_tpu.parallel import make_mesh, make_plan

    bundle, params = llama
    cfg = bundle.config
    plan = make_plan("tp", make_mesh(tp=2, devices=eight_devices[:2]))
    reqs = [Request(prompt_ids=[3 + i, 17, 42], max_new_tokens=6, seed=i)
            for i in range(3)]
    eng = ServeEngine(bundle, params, n_slots=3, page_size=4, max_len=32,
                      plan=plan, shard_kv=True, kv_dtype="int8")
    res = generate_many(eng, [_fresh(r) for r in reqs])
    repl = ServeEngine(bundle, params, n_slots=3, page_size=4, max_len=32,
                       kv_dtype="int8")
    ref = generate_many(repl, [_fresh(r) for r in reqs])
    assert [r.token_ids for r in res] == [r.token_ids for r in ref]
    for leaf, trailing in ((eng.pages["k"].q, cfg.head_size),
                           (eng.pages["k"].scale, 1)):
        shard = leaf.addressable_shards[0].data
        assert shard.shape[3] == cfg.num_kv_heads // 2
        assert shard.shape[4] == trailing
