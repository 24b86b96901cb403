"""Paged flash kernel correctness: interpret-mode parity against the XLA
gather reference across the serving feature grid (GQA, sliding window —
static and traced, score scale, softcap, shuffled physical page layouts,
page-boundary lengths) at EVERY query-tile size — T=1 decode, T>1
verify/chunk tiles with ``n_valid`` pad tails, int8 and bf16 pools — and
over the walk itself (lengths at every page and block edge, mixed and dead
slots in one call, a window's lower edge mid-walk, the large-tile form) —
always on STACKED pools of three layers with different contents, addressed
by a layer index (the attend contract: ``serve/kv_pages.paged_attend``) —
plus the engine-level pins: flash and xla attends produce identical
tokens, and the flash decode/chunk/verify programs' HLO carries no
[S, M*page, Hkv, D] gathered view (the xla programs show it)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_guide_tpu.ops.attention import multihead_attention
from distributed_training_guide_tpu.utils import hlo as hlo_util
from distributed_training_guide_tpu.ops import paged_decode
from distributed_training_guide_tpu.ops.paged_decode import (
    paged_decode_eligible, paged_flash_attend, paged_flash_decode)
from distributed_training_guide_tpu.serve.kv_pages import (paged_attend,
                                                           quantize_kv)

pytestmark = [pytest.mark.serve, pytest.mark.flash_decode]


def _random_paged_state(rng, *, s, m, page, n_pages, hkv, d):
    """Shuffled non-overlapping physical pages per slot + dense mirrors."""
    phys = rng.permutation(np.arange(1, n_pages))
    tables = np.zeros((s, m), np.int32)
    for i in range(s):
        tables[i] = phys[i * m:(i + 1) * m]
    k_pages = rng.standard_normal((n_pages, page, hkv, d)).astype(np.float32)
    v_pages = rng.standard_normal((n_pages, page, hkv, d)).astype(np.float32)
    return tables, k_pages, v_pages


N_LAYERS = 3


def stacked_pool(pool, layer):
    """The ``[3, P, page, Hkv, D]`` pool whose layer ``layer`` is ``pool``
    and whose other layers hold other numbers (its pages rolled and scaled),
    so that an attend reading another layer's pages cannot pass."""
    pool = jnp.asarray(pool)
    return jnp.stack([pool if i == layer
                      else jnp.roll(pool, i + 1, axis=0) * (i + 2)
                      for i in range(N_LAYERS)])


def _gather_reference(q, k_pages, v_pages, tables, lengths, *, window=None,
                      scale=None, softcap=None):
    """The XLA logical-view attend (what serve ran before the kernel), on
    ONE layer's pool: the test's own reference, which knows no layer."""
    s, m = tables.shape
    page = k_pages.shape[1]
    kg = k_pages[tables].reshape(s, m * page, *k_pages.shape[2:])
    vg = v_pages[tables].reshape(s, m * page, *v_pages.shape[2:])
    kv_pos = jnp.broadcast_to(jnp.arange(m * page)[None], (s, m * page))
    return multihead_attention(
        jnp.asarray(q)[:, None], jnp.asarray(kg), jnp.asarray(vg),
        causal=True, positions=jnp.asarray(lengths)[:, None],
        kv_positions=kv_pos, impl="xla", standard_layout=False,
        window=window, scale=scale, logit_softcap=softcap)[:, 0]


FEATURE_GRID = [
    dict(),                                          # plain causal
    dict(window=4),                                  # SWA inside one page
    dict(window=9),                                  # SWA across pages
    dict(scale=0.3),                                 # Gemma-2 score scale
    dict(softcap=20.0),                              # Gemma-2 softcap
    dict(window=8, scale=0.25, softcap=50.0),        # full Gemma-2 decode
]


@pytest.mark.parametrize("hq,hkv", [(4, 2), (2, 2), (8, 1)])
@pytest.mark.parametrize("kw", FEATURE_GRID,
                         ids=lambda kw: "-".join(kw) or "causal")
def test_kernel_matches_gather_reference(hq, hkv, kw):
    """Interpret-mode kernel vs the XLA gather path at <= 1e-5 over
    shuffled physical layouts and lengths hitting page starts/ends/zero."""
    rng = np.random.default_rng(0)
    s, m, page, n_pages, d = 4, 4, 4, 20, 8
    tables, k_pages, v_pages = _random_paged_state(
        rng, s=s, m=m, page=page, n_pages=n_pages, hkv=hkv, d=d)
    # positions: page boundary, zero, mid-page, last valid slot
    lengths = np.array([4, 0, 9, 15], np.int32)
    q = rng.standard_normal((s, hq, d)).astype(np.float32)

    out = paged_flash_decode(
        jnp.asarray(q), stacked_pool(k_pages, 2), stacked_pool(v_pages, 2), 2,
        jnp.asarray(tables), jnp.asarray(lengths), interpret=True, **kw)
    ref = _gather_reference(q, jnp.asarray(k_pages), jnp.asarray(v_pages),
                            tables, lengths, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_kernel_traced_window_matches_static():
    """A traced window (the per-layer Gemma-2 schedule rides lax.scan) must
    equal the static bake AND the reference; 2**30 encodes full causal."""
    rng = np.random.default_rng(1)
    s, m, page, n_pages, hq, hkv, d = 3, 4, 4, 16, 4, 2, 8
    tables, k_pages, v_pages = _random_paged_state(
        rng, s=s, m=m, page=page, n_pages=n_pages, hkv=hkv, d=d)
    lengths = np.array([5, 11, 14], np.int32)
    q = rng.standard_normal((s, hq, d)).astype(np.float32)
    args = (jnp.asarray(q), stacked_pool(k_pages, 1),
            stacked_pool(v_pages, 1), 1, jnp.asarray(tables),
            jnp.asarray(lengths))

    traced = jax.jit(lambda w: paged_flash_decode(*args, window=w,
                                                  interpret=True))
    static = paged_flash_decode(*args, window=6, interpret=True)
    np.testing.assert_allclose(np.asarray(traced(jnp.asarray(6))),
                               np.asarray(static), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(traced(jnp.asarray(2 ** 30))),
        np.asarray(paged_flash_decode(*args, interpret=True)),
        rtol=1e-6, atol=1e-6)


def test_kernel_bf16_pages():
    """bf16 page pools (the serving dtype at scale): fp32 accumulation
    inside the kernel keeps parity with the gather reference at bf16
    tolerance."""
    rng = np.random.default_rng(2)
    s, m, page, n_pages, hq, hkv, d = 2, 2, 8, 8, 4, 2, 8
    tables, k_pages, v_pages = _random_paged_state(
        rng, s=s, m=m, page=page, n_pages=n_pages, hkv=hkv, d=d)
    kp = jnp.asarray(k_pages, jnp.bfloat16)
    vp = jnp.asarray(v_pages, jnp.bfloat16)
    lengths = np.array([3, 12], np.int32)
    q = jnp.asarray(rng.standard_normal((s, hq, d)), jnp.bfloat16)
    out = paged_flash_decode(q, stacked_pool(kp, 1), stacked_pool(vp, 1), 1,
                             jnp.asarray(tables), jnp.asarray(lengths),
                             interpret=True)
    ref = _gather_reference(q, kp, vp, tables, lengths)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_kernel_validates_bad_static_window_and_tiles():
    rng = np.random.default_rng(3)
    tables, k_pages, v_pages = _random_paged_state(
        rng, s=1, m=2, page=4, n_pages=4, hkv=2, d=8)
    q = jnp.zeros((1, 4, 8), jnp.float32)
    with pytest.raises(ValueError, match="window"):
        paged_flash_decode(q, stacked_pool(k_pages, 0),
                           stacked_pool(v_pages, 0), 0,
                           jnp.asarray(tables), jnp.zeros(1, jnp.int32),
                           window=0, interpret=True)
    assert paged_decode_eligible(128, 8)
    assert not paged_decode_eligible(64, 8)     # head_dim is a lane block
    assert not paged_decode_eligible(128, 4)    # page not tiled


def test_paged_attend_flash_matches_xla_dispatch():
    """The serve-layer dispatch: impl='flash' (interpret off-TPU) equals
    impl='xla' through the full paged_attend contract — scatter of the
    new token included."""
    rng = np.random.default_rng(4)
    s, m, page, n_pages, hq, hkv, d = 3, 4, 4, 16, 4, 2, 8
    tables, k_pages, v_pages = _random_paged_state(
        rng, s=s, m=m, page=page, n_pages=n_pages, hkv=hkv, d=d)
    lengths = jnp.asarray(np.array([5, 0, 11], np.int32))
    q = jnp.asarray(rng.standard_normal((s, 1, hq, d)).astype(np.float32))
    k_new = jnp.asarray(rng.standard_normal((s, 1, hkv, d)).astype(np.float32))
    v_new = jnp.asarray(rng.standard_normal((s, 1, hkv, d)).astype(np.float32))
    outs = {}
    for impl in ("flash", "xla"):
        attn, (kp, vp) = paged_attend(
            q, k_new, v_new, stacked_pool(k_pages, 1),
            stacked_pool(v_pages, 1), 1, jnp.asarray(tables), lengths,
            impl=impl, window=6, scale=0.3, softcap=30.0)
        outs[impl] = (np.asarray(attn), np.asarray(kp), np.asarray(vp))
    np.testing.assert_allclose(outs["flash"][0], outs["xla"][0],
                               rtol=1e-5, atol=1e-5)
    # the scatter is shared: pools must be BITWISE identical
    np.testing.assert_array_equal(outs["flash"][1], outs["xla"][1])
    np.testing.assert_array_equal(outs["flash"][2], outs["xla"][2])


# ---- the walk: live pages only, blocks of pages, all heads at once ----------

def _walk_state(rng, lengths, *, t, m, page, hq, hkv, d, dtype=np.float32):
    """One call's inputs for slots of the given lengths: each slot owns the
    shuffled physical pages its ``length + t`` tokens need, the rest of its
    table row is the trash page 0 (as the engine leaves it), and a slot of
    length -1 is a DEAD one: length 0, an all-trash row."""
    s = len(lengths)
    lens = np.maximum(np.asarray(lengths, np.int32), 0)
    need = [0 if l < 0 else -(-(int(l) + t) // page) for l in lengths]
    n_pages = sum(need) + 3
    phys = rng.permutation(np.arange(1, n_pages))
    tables = np.zeros((s, m), np.int32)
    at = 0
    for i, k in enumerate(need):
        tables[i, :k] = phys[at:at + k]
        at += k
    mk = lambda *shape: rng.standard_normal(shape).astype(dtype)
    return (tables, mk(n_pages, page, hkv, d), mk(n_pages, page, hkv, d),
            lens, mk(s, t, hq, d), mk(s, t, hkv, d), mk(s, t, hkv, d))


def _flash_and_xla(q, k_new, v_new, k_pages, v_pages, tables, lengths, *,
                   layer=1, int8=False, **kw):
    """Both impls' attention at ``layer`` of the stacked pools made of one
    layer's float ``k_pages`` / ``v_pages`` (quantized once stacked)."""
    kp, vp = stacked_pool(k_pages, layer), stacked_pool(v_pages, layer)
    if int8:
        kp, vp = quantize_kv(kp), quantize_kv(vp)
    return [paged_attend(jnp.asarray(q), jnp.asarray(k_new),
                         jnp.asarray(v_new), kp, vp, layer,
                         jnp.asarray(tables), jnp.asarray(lengths),
                         impl=impl, **kw)[0] for impl in ("flash", "xla")]


def _edge_lengths(t, m, page, n):
    """0, page - 1, page, one either side of every multiple of the block
    (the last position of a block is ``k * n * page - 1``), the full
    table, and a dead slot."""
    full = m * page - t
    edges = {0, page - 1, page, full, full - 1}
    for k in range(1, m // n + 1):
        edges |= {k * n * page + e - t for e in (-1, 0, 1)}
    return [-1] + sorted(e for e in edges if 0 <= e <= full)


WALK_HEADS = [(4, 2), (32, 32), (8, 1), (4, 4)]


@pytest.mark.parametrize("hq,hkv", WALK_HEADS)
@pytest.mark.parametrize("t", [1, 3, 8], ids=["decode", "verify", "chunk"])
def test_walk_edges_mixed_lengths_one_call(hq, hkv, t):
    """Slots of very different lengths in ONE call, a dead slot among them,
    shuffled physical pages: lengths of 0, page - 1, page, one either side
    of every multiple of the pages a block holds, and the full table. The
    chunk form carries ``n_valid`` tails (full, partial, one token)."""
    page, m, d = 4, 20, 8
    _, _, n = paged_decode._plan(t, hq // hkv, hkv, d, page, m,
                                 jnp.float32, jnp.float32)
    assert 1 < n < m, "the table must hold several blocks of the walk"
    lengths = _edge_lengths(t, m, page, n)
    rng = np.random.default_rng(21)
    tables, kp, vp, lens, q, k_new, v_new = _walk_state(
        rng, lengths, t=t, m=m, page=page, hq=hq, hkv=hkv, d=d)
    n_valid = np.array([(t, max(1, t - 1), 1)[i % 3]
                        for i in range(len(lens))], np.int32)
    flash, xla = _flash_and_xla(q, k_new, v_new, jnp.asarray(kp),
                                jnp.asarray(vp), tables, lens,
                                n_valid=jnp.asarray(n_valid))
    np.testing.assert_allclose(np.asarray(flash), np.asarray(xla),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pool", ["fp32", "bf16", "int8"])
@pytest.mark.parametrize("t", [1, 5], ids=["decode", "verify"])
def test_walk_pool_dtypes(pool, t):
    """Every pool dtype over a walk of several blocks, MHA: bf16 q and k
    meet the MXU as stored, an int8 pool's scales ride the walk."""
    page, m, hq, hkv, d = 8, 12, 4, 4, 8
    rng = np.random.default_rng(22)
    lengths = [0, 7, 8, 63, 64, 65, m * page - t, -1]
    tables, kp, vp, lens, q, k_new, v_new = _walk_state(
        rng, lengths, t=t, m=m, page=page, hq=hq, hkv=hkv, d=d)
    dtype = jnp.bfloat16 if pool == "bf16" else jnp.float32
    flash, xla = _flash_and_xla(
        *(jnp.asarray(x, dtype) for x in (q, k_new, v_new, kp, vp)),
        tables, lens, int8=pool == "int8", scale=0.3)
    tol = 3e-2 if pool == "bf16" else 1e-5
    assert flash.dtype == dtype
    np.testing.assert_allclose(np.asarray(flash, np.float32),
                               np.asarray(xla, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [3, 10, 37, 70])
@pytest.mark.parametrize("t", [1, 4], ids=["decode", "tile"])
def test_walk_window_lower_edge_inside_the_walk(window, t):
    """A window whose lower edge falls inside the walk: the first block
    starts at the page that holds the oldest position any row still sees
    (mid-table, not on a block edge), static and traced windows alike."""
    page, m, hq, hkv, d = 4, 20, 4, 2, 8
    rng = np.random.default_rng(23)
    lengths = [0, 2, 9, 31, 33, 50, 64, m * page - t]
    tables, kp, vp, lens, q, k_new, v_new = _walk_state(
        rng, lengths, t=t, m=m, page=page, hq=hq, hkv=hkv, d=d)
    flash, xla = _flash_and_xla(q, k_new, v_new, jnp.asarray(kp),
                                jnp.asarray(vp), tables, lens,
                                window=window, softcap=30.0)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(xla),
                               rtol=1e-5, atol=1e-5)
    traced = jax.jit(lambda w: paged_flash_attend(
        jnp.asarray(q), stacked_pool(kp, 2), stacked_pool(vp, 2), 2,
        jnp.asarray(tables), jnp.asarray(lens), window=w, softcap=30.0,
        interpret=True))
    static = paged_flash_attend(
        jnp.asarray(q), stacked_pool(kp, 2), stacked_pool(vp, 2), 2,
        jnp.asarray(tables), jnp.asarray(lens), window=window, softcap=30.0,
        interpret=True)
    np.testing.assert_allclose(np.asarray(traced(jnp.asarray(window))),
                               np.asarray(static), rtol=1e-6, atol=1e-6)


@pytest.mark.paged_multitok
@pytest.mark.parametrize("pool,hb", [("fp32", 1), ("bf16", 2), ("int8", 4)])
@pytest.mark.parametrize("split", [False, True], ids=["one-block", "split"])
def test_large_tile_takes_the_heads_of_one_word(pool, hb, split, monkeypatch):
    """A query tile too large for all heads at once (a prefill chunk) takes
    the heads that share a 32-bit word of the pool's dtype, picked out of
    the page block by a strided word load, and (``split``) fewer heads a
    grid step: same numbers as the gather path, ``n_valid`` tail and a
    windowed layer included."""
    page, m, hq, hkv, d, t = 8, 10, 8, 4, 128, 48    # the pick needs D = 128
    if split:   # a tile budget that holds two heads a grid step
        monkeypatch.setattr(paged_decode, "TILE_BUDGET", 2 * t * 2 * (
            4 * d * 4 + d * 4 + 2 * 128 * 4))
    dtype = jnp.bfloat16 if pool == "bf16" else jnp.float32
    pool_dtype = {"fp32": jnp.float32, "bf16": jnp.bfloat16,
                  "int8": jnp.int8}[pool]
    plan = paged_decode._plan(t, hq // hkv, hkv, d, page, m, dtype,
                              pool_dtype)
    assert plan[1] == hb and (plan[0] < hkv) == (split and hb < 4), plan
    rng = np.random.default_rng(24)
    lengths = [0, 13, m * page - t]
    tables, kp, vp, lens, q, k_new, v_new = _walk_state(
        rng, lengths, t=t, m=m, page=page, hq=hq, hkv=hkv, d=d)
    flash, xla = _flash_and_xla(
        *(jnp.asarray(x, dtype) for x in (q, k_new, v_new, kp, vp)),
        tables, lens, layer=2, int8=pool == "int8",
        n_valid=jnp.asarray([t, 5, t - 1], jnp.int32), window=29)
    tol = 3e-2 if pool == "bf16" else 1e-5
    np.testing.assert_allclose(np.asarray(flash, np.float32),
                               np.asarray(xla, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("case,want", [
    # the serve cell: 16 slots of 32/32 heads of 128, page 16, bf16
    (dict(t=1, groups=1, hkv=32, pool=jnp.bfloat16), (32, 32, 4)),
    (dict(t=5, groups=1, hkv=32, pool=jnp.bfloat16), (32, 32, 4)),
    (dict(t=512, groups=1, hkv=32, pool=jnp.bfloat16), (4, 2, 4)),
    # qwen3's GQA 16/8, an int8 pool, a one-head slice of a sharded pool
    (dict(t=1, groups=2, hkv=8, pool=jnp.bfloat16), (8, 8, 4)),
    (dict(t=64, groups=2, hkv=8, pool=jnp.int8), (8, 4, 4)),
    (dict(t=512, groups=4, hkv=1, pool=jnp.bfloat16), (1, 1, 2)),
])
def test_plan_follows_the_static_shapes(case, want):
    """``(heads a grid step, heads a product, pages a block)`` at real
    shapes: all heads while the query tile is small, the heads of one word
    for a chunk, fewer pages where the scores would outgrow their budget."""
    q_dtype = jnp.float32 if case["pool"] == jnp.int8 else jnp.bfloat16
    hs, hb, n = paged_decode._plan(case["t"], case["groups"], case["hkv"],
                                   128, 16, 256, q_dtype, case["pool"])
    assert (hs, hb, n) == want
    assert case["hkv"] % hs == 0 and hs % hb == 0 and n >= 1


# ---- the stacked pools, addressed by layer ---------------------------------

@pytest.mark.parametrize("layer", range(N_LAYERS))
@pytest.mark.parametrize("pool", ["fp32", "bf16", "int8"])
@pytest.mark.parametrize("t", [1, 4], ids=["decode", "tile"])
def test_stacked_pools_are_written_and_read_at_the_layer(pool, t, layer):
    """Three layers of different contents in one pool: at each layer, both
    impls equal the gather path on THAT layer alone (a one-layer stack,
    where no layer can be mistaken for another), over shuffled tables,
    lengths either side of a page edge and ``n_valid`` tails; the write
    lands in that layer, as it would in the layer alone, and leaves every
    other layer's bytes as they were."""
    page, m, hq, hkv, d = 4, 6, 4, 2, 8
    rng = np.random.default_rng(31 + layer)
    lengths = [0, page - 1, page, page + 1, 2 * page - t, m * page - t, -1]
    tables, kp, vp, lens, q, k_new, v_new = _walk_state(
        rng, lengths, t=t, m=m, page=page, hq=hq, hkv=hkv, d=d)
    dtype = jnp.bfloat16 if pool == "bf16" else jnp.float32
    pools = [stacked_pool(jnp.asarray(x, dtype), layer) for x in (kp, vp)]
    if pool == "int8":
        pools = [quantize_kv(x) for x in pools]
    n_valid = jnp.asarray([(t, max(1, t - 1), 1)[i % 3]
                           for i in range(len(lens))], jnp.int32)
    args = [jnp.asarray(x, dtype) for x in (q, k_new, v_new)]
    rest = (jnp.asarray(tables), jnp.asarray(lens))
    alone = [jax.tree.map(lambda x: x[layer][None], pl) for pl in pools]
    want, want_pools = paged_attend(*args, *alone, 0, *rest, impl="xla",
                                    n_valid=n_valid, window=7)
    tol = 3e-2 if pool == "bf16" else 1e-5
    for impl in ("flash", "xla"):
        got, got_pools = paged_attend(*args, *pools, layer, *rest, impl=impl,
                                      n_valid=n_valid, window=7)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)
        for before, after, one in zip(jax.tree.leaves(pools),
                                      jax.tree.leaves(got_pools),
                                      jax.tree.leaves(want_pools)):
            before, after = np.asarray(before), np.asarray(after)
            np.testing.assert_array_equal(after[layer], np.asarray(one)[0])
            assert not np.array_equal(after[layer], before[layer])
            others = [i for i in range(N_LAYERS) if i != layer]
            np.testing.assert_array_equal(after[others], before[others])


def serve_program_jaxprs(eng):
    """The decode and the chunk program of an engine, traced."""
    arr = eng.scheduler.decode_arrays()
    decode = jax.make_jaxpr(eng.programs._decode)(
        eng.params, eng.pages,
        *(jnp.asarray(arr[k]) for k in (
            "tokens", "lengths", "tables", "seeds", "temps", "top_ks",
            "top_ps", "actives")))
    t = eng.prefill_chunk
    chunk = jax.make_jaxpr(eng.programs.chunk_for(t))(
        eng.params, eng.pages,
        jnp.zeros((1, t), jnp.int32), jnp.zeros((1,), jnp.int32),
        jnp.zeros((1, eng.max_pages), jnp.int32),
        jnp.asarray(t - 1, jnp.int32), jnp.asarray([t], jnp.int32))
    return {"decode": decode, "chunk": chunk}


@pytest.mark.parametrize("impl", ["flash", "xla"])
def test_pools_ride_the_layer_scan_as_carry(impl):
    """The structure the decode step's time rests on: in the decode and the
    chunk program the two stacked pools are in the layer scan's CARRY, not
    among its scanned inputs or stacked outputs, and nothing in the scan's
    body slices a layer's pool out of them or puts one back."""
    from distributed_training_guide_tpu.models import get_model
    from distributed_training_guide_tpu.serve import ServeEngine

    bundle = get_model("llama-debug", dtype=jnp.float32)
    params = bundle.init(bundle.config, jax.random.key(0))
    eng = ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=16,
                      attend_impl=impl, prefill_chunk=8)
    for name, jaxpr in serve_program_jaxprs(eng).items():
        scans = hlo_util.scans_holding(jaxpr, eng.pages["k"].shape)
        assert scans == [{"carry": 2, "xs": 0, "ys": 0, "sliced": []}], (
            name, scans)


# ---- engine-level pins ------------------------------------------------------

def test_engine_flash_decode_tokens_and_hlo_pin():
    """(a) an engine forced onto the kernel produces the same tokens as
    the gather engine; (b) the flash decode program's lowered HLO holds NO
    tensor shaped like the gathered [S, M*page, Hkv, D] view — the
    acceptance pin that the decode step stopped materializing it."""
    from distributed_training_guide_tpu.models import get_model
    from distributed_training_guide_tpu.serve import Request, ServeEngine
    from distributed_training_guide_tpu.serve.api import generate_many

    bundle = get_model("llama-debug", dtype=jnp.float32)
    params = bundle.init(bundle.config, jax.random.key(0))
    reqs = [Request(prompt_ids=[3, 17, 42], max_new_tokens=5, seed=1),
            Request(prompt_ids=[5, 6], max_new_tokens=6, seed=2)]
    res = {}
    engines = {}
    for impl in ("flash", "xla"):
        eng = ServeEngine(bundle, params, n_slots=2, page_size=4,
                          max_len=16, attend_impl=impl)
        res[impl] = generate_many(eng, reqs)
        engines[impl] = eng
    for a, b in zip(res["flash"], res["xla"]):
        assert a.token_ids == b.token_ids

    cfg = bundle.config
    for impl, expect_view in (("flash", False), ("xla", True)):
        eng = engines[impl]
        arr = eng.scheduler.decode_arrays()
        lowered = eng._decode_fn.lower(
            eng.params, eng.pages,
            jnp.asarray(arr["tokens"]), jnp.asarray(arr["lengths"]),
            jnp.asarray(arr["tables"]), jnp.asarray(arr["seeds"]),
            jnp.asarray(arr["temps"]), jnp.asarray(arr["top_ks"]),
            jnp.asarray(arr["top_ps"]), jnp.asarray(arr["actives"]))
        view = (eng.n_slots, eng.max_pages * eng.page_size,
                cfg.num_kv_heads, cfg.head_size)
        assert (hlo_util.has_shape_run(lowered.as_text(), view)
                == expect_view), (
            f"{impl}: gathered-view tensor "
            f"{'missing' if expect_view else 'present'} in the decode HLO")


# ---- the multi-token tile (block_q = T): verify / chunked prefill ----------

def _multitok_case(rng, *, s=3, t=4, m=4, page=4, n_pages=16, hq=4, hkv=2,
                   d=8):
    """Shuffled physical layout + a fresh [S, T] call's inputs: lengths
    hit zero / mid-page / a page crossing, and n_valid exercises full,
    partial, and single-token tails (the padded final chunk / short-draft
    shapes)."""
    tables, k_pages, v_pages = _random_paged_state(
        rng, s=s, m=m, page=page, n_pages=n_pages, hkv=hkv, d=d)
    lengths = np.array([0, 5, 9], np.int32)[:s]
    n_valid = np.array([t, max(1, t - 1), 1], np.int32)[:s]
    q = rng.standard_normal((s, t, hq, d)).astype(np.float32)
    k_new = rng.standard_normal((s, t, hkv, d)).astype(np.float32)
    v_new = rng.standard_normal((s, t, hkv, d)).astype(np.float32)
    return tables, k_pages, v_pages, lengths, n_valid, q, k_new, v_new


@pytest.mark.paged_multitok
@pytest.mark.parametrize("hq,hkv", [(4, 2), (2, 2), (8, 1)])
@pytest.mark.parametrize("kw", FEATURE_GRID,
                         ids=lambda kw: "-".join(kw) or "causal")
def test_multitoken_flash_matches_gather(hq, hkv, kw):
    """The [S, T] form through the full paged_attend contract — scatter
    of the T new tokens (n_valid tails trash-routed) then attend — must
    agree flash-vs-xla at <= 1e-5 on EVERY query row (pad rows read the
    same pool bytes under the same positional mask), with the shared
    scatter leaving BITWISE-identical pools. Windows at 4 and 9 fall
    inside / across the 4-token pages."""
    rng = np.random.default_rng(11)
    tables, k_pages, v_pages, lengths, n_valid, q, k_new, v_new = \
        _multitok_case(rng, hq=hq, hkv=hkv)
    outs = {}
    for impl in ("flash", "xla"):
        attn, (kp, vp) = paged_attend(
            jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
            stacked_pool(k_pages, 2), stacked_pool(v_pages, 2), 2,
            jnp.asarray(tables), jnp.asarray(lengths), impl=impl,
            n_valid=jnp.asarray(n_valid), **kw)
        outs[impl] = (np.asarray(attn), np.asarray(kp), np.asarray(vp))
    np.testing.assert_allclose(outs["flash"][0], outs["xla"][0],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(outs["flash"][1], outs["xla"][1])
    np.testing.assert_array_equal(outs["flash"][2], outs["xla"][2])


@pytest.mark.paged_multitok
def test_multitoken_rank3_is_the_decode_form_bitwise():
    """T == 1 through the rank-4 tile IS the original decode kernel: the
    rank-3 entry point and a [S, 1, Hq, D] call must agree BITWISE (the
    row fold is a no-op transpose at T=1 — same layout, same op
    sequence)."""
    rng = np.random.default_rng(12)
    tables, k_pages, v_pages = _random_paged_state(
        rng, s=3, m=4, page=4, n_pages=16, hkv=2, d=8)
    lengths = np.array([3, 7, 12], np.int32)
    q = rng.standard_normal((3, 4, 8)).astype(np.float32)
    args = (stacked_pool(k_pages, 1), stacked_pool(v_pages, 1), 1,
            jnp.asarray(tables), jnp.asarray(lengths))
    r3 = paged_flash_decode(jnp.asarray(q), *args, window=5, interpret=True)
    r4 = paged_flash_attend(jnp.asarray(q)[:, None], *args, window=5,
                            interpret=True)
    np.testing.assert_array_equal(np.asarray(r3), np.asarray(r4[:, 0]))


@pytest.mark.paged_multitok
def test_multitoken_traced_window_matches_static():
    """A traced window at T > 1 (the per-layer Gemma-2 schedule under the
    chunk/verify scan) must equal the static bake; 2**30 encodes full
    causal."""
    rng = np.random.default_rng(13)
    tables, k_pages, v_pages, lengths, _, q, _, _ = \
        _multitok_case(rng, hq=4, hkv=2)
    args = (jnp.asarray(q), stacked_pool(k_pages, 1),
            stacked_pool(v_pages, 1), 1, jnp.asarray(tables),
            jnp.asarray(lengths))
    traced = jax.jit(lambda w: paged_flash_attend(*args, window=w,
                                                  interpret=True))
    static = paged_flash_attend(*args, window=6, interpret=True)
    np.testing.assert_allclose(np.asarray(traced(jnp.asarray(6))),
                               np.asarray(static), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(traced(jnp.asarray(2 ** 30))),
        np.asarray(paged_flash_attend(*args, interpret=True)),
        rtol=1e-6, atol=1e-6)


@pytest.mark.paged_multitok
def test_multitoken_bf16_pages():
    """bf16 pools at T > 1: fp32 accumulation inside the kernel keeps
    parity with the gather reference at bf16 tolerance."""
    rng = np.random.default_rng(14)
    tables, k_pages, v_pages, lengths, n_valid, q, k_new, v_new = \
        _multitok_case(rng, hq=4, hkv=2)
    outs = {}
    for impl in ("flash", "xla"):
        attn, _ = paged_attend(
            jnp.asarray(q, jnp.bfloat16), jnp.asarray(k_new, jnp.bfloat16),
            jnp.asarray(v_new, jnp.bfloat16),
            stacked_pool(jnp.asarray(k_pages, jnp.bfloat16), 0),
            stacked_pool(jnp.asarray(v_pages, jnp.bfloat16), 0), 0,
            jnp.asarray(tables), jnp.asarray(lengths), impl=impl,
            n_valid=jnp.asarray(n_valid))
        assert attn.dtype == jnp.bfloat16
        outs[impl] = np.asarray(attn, np.float32)
    np.testing.assert_allclose(outs["flash"], outs["xla"],
                               rtol=3e-2, atol=3e-2)


@pytest.mark.paged_multitok
@pytest.mark.kvquant
def test_multitoken_int8_flash_matches_int8_gather():
    """The quantized pool at T > 1: in-kernel dequant (scale rows riding
    the block-table prefetch) vs the dequantized gather view on the SAME
    int8 pool — 1e-5 (both read identical payload+scale bytes), and the
    quantize-at-write scatter is bitwise shared (payload AND scales)."""
    rng = np.random.default_rng(15)
    tables, k_pages, v_pages, lengths, n_valid, q, k_new, v_new = \
        _multitok_case(rng, hq=4, hkv=2)
    kq = quantize_kv(stacked_pool(k_pages, 1))
    vq = quantize_kv(stacked_pool(v_pages, 1))
    outs = {}
    for impl in ("flash", "xla"):
        attn, (kp, vp) = paged_attend(
            jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
            kq, vq, 1, jnp.asarray(tables), jnp.asarray(lengths), impl=impl,
            n_valid=jnp.asarray(n_valid), window=6, scale=0.3, softcap=30.0)
        outs[impl] = (np.asarray(attn), kp, vp)
    np.testing.assert_allclose(outs["flash"][0], outs["xla"][0],
                               rtol=1e-5, atol=1e-5)
    for leaf_f, leaf_x in zip(jax.tree.leaves(outs["flash"][1:]),
                              jax.tree.leaves(outs["xla"][1:])):
        np.testing.assert_array_equal(np.asarray(leaf_f), np.asarray(leaf_x))


# ---- engine-level multi-token pins ------------------------------------------

@pytest.mark.paged_multitok
def test_chunk_and_verify_programs_flash_hlo_pin():
    """THE acceptance pin for the kernel family: the chunk-prefill and
    spec-verify programs of a flash-family engine lower with NO gathered
    [S, M*page, Hkv, D] pool-shaped tensor, while the xla family's show
    it — chunked prefill and verify stopped paying the logical-view
    round-trip."""
    from distributed_training_guide_tpu.models import get_model
    from distributed_training_guide_tpu.serve import ServeEngine

    bundle = get_model("llama-debug", dtype=jnp.float32)
    params = bundle.init(bundle.config, jax.random.key(0))
    cfg = bundle.config
    for impl, expect_view in (("flash", False), ("xla", True)):
        eng = ServeEngine(bundle, params, n_slots=2, page_size=4,
                          max_len=16, attend_impl=impl, prefill_chunk=8,
                          speculate="ngram", spec_k=3)
        chunk = eng.programs.chunk_for(8).lower(
            eng.params, eng.pages,
            jnp.zeros((1, 8), jnp.int32), jnp.zeros((1,), jnp.int32),
            jnp.zeros((1, eng.max_pages), jnp.int32),
            jnp.asarray(7, jnp.int32), jnp.asarray([8], jnp.int32))
        view = (1, eng.max_pages * eng.page_size, cfg.num_kv_heads,
                cfg.head_size)
        assert (hlo_util.has_shape_run(chunk.as_text(), view)
                == expect_view), (
            f"{impl}: chunk program gathered view "
            f"{'missing' if expect_view else 'present'}")
        s = eng.n_slots
        verify = eng.programs.verify_for(4, greedy=True).lower(
            eng.params, eng.pages,
            jnp.zeros((s, 4), jnp.int32), jnp.zeros((s,), jnp.int32),
            jnp.zeros((s, eng.max_pages), jnp.int32),
            jnp.zeros((s,), jnp.int32), jnp.zeros((s,), jnp.float32),
            jnp.zeros((s,), jnp.int32), jnp.zeros((s,), jnp.float32),
            jnp.zeros((s,), jnp.bool_), jnp.zeros((s,), jnp.int32))
        view = (s, eng.max_pages * eng.page_size, cfg.num_kv_heads,
                cfg.head_size)
        assert (hlo_util.has_shape_run(verify.as_text(), view)
                == expect_view), (
            f"{impl}: verify program gathered view "
            f"{'missing' if expect_view else 'present'}")


@pytest.mark.paged_multitok
def test_engine_chunked_prefill_flash_tokens_match_gather():
    """An engine whose chunk program runs the multi-token kernel produces
    the same tokens as the gather engine — prompt long enough for several
    chunks incl. a padded final one, co-resident decodes riding along."""
    from distributed_training_guide_tpu.models import get_model
    from distributed_training_guide_tpu.serve import Request, ServeEngine
    from distributed_training_guide_tpu.serve.api import generate_many

    bundle = get_model("llama-debug", dtype=jnp.float32)
    params = bundle.init(bundle.config, jax.random.key(0))
    prompt = [3 + (i % 40) for i in range(19)]
    reqs = [Request(prompt_ids=prompt + [50 + i], max_new_tokens=5,
                    temperature=0.0 if i % 2 == 0 else 0.8, seed=i)
            for i in range(3)]
    res = {}
    for impl in ("flash", "xla"):
        eng = ServeEngine(bundle, params, n_slots=3, page_size=4,
                          max_len=32, attend_impl=impl, prefill_chunk=8)
        res[impl] = generate_many(eng, reqs)
    for a, b in zip(res["flash"], res["xla"]):
        assert a.token_ids == b.token_ids


@pytest.mark.paged_multitok
@pytest.mark.spec
@pytest.mark.slow
def test_sharded_tp2_flash_multitok_grid(eight_devices):
    """The >=2-device multi-token grid (slow): tp=2 sharded pool on the
    FLASH family with chunked prefill AND speculation — the chunk and
    verify tiles run the kernel per chip inside the manual region, and
    tokens equal the plain unsharded engine's."""
    from distributed_training_guide_tpu.models import get_model
    from distributed_training_guide_tpu.parallel import make_mesh, make_plan
    from distributed_training_guide_tpu.serve import Request, ServeEngine
    from distributed_training_guide_tpu.serve.api import generate_many

    bundle = get_model("llama-debug", dtype=jnp.float32)
    params = bundle.init(bundle.config, jax.random.key(0))
    plan = make_plan("tp", make_mesh(tp=2, devices=eight_devices[:2]))
    rep = [9, 8, 7] * 4
    reqs = [Request(prompt_ids=rep + [40 + i], max_new_tokens=8,
                    temperature=0.0 if i % 2 == 0 else 0.9, seed=i)
            for i in range(4)]
    ref = generate_many(
        ServeEngine(bundle, params, n_slots=2, page_size=8, max_len=32),
        reqs)
    eng = ServeEngine(bundle, params, n_slots=2, page_size=8, max_len=32,
                      plan=plan, shard_kv=True, attend_impl="flash",
                      prefill_chunk=8, speculate="ngram", spec_k=3)
    got = generate_many(eng, reqs)
    for a, b in zip(got, ref):
        assert a.token_ids == b.token_ids
    assert eng.spec["tokens_drafted"] > 0, "the grid never speculated"
